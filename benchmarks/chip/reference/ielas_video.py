"""Plain reference of the warm-start iELAS video program, for the benchmark's output check.

Two answers, both written from the algorithm's definition and independent
of the program (no ``repro`` import, no tiling, scan or kernel):

* :func:`disparity`, a frame's cold answer: the frame program of
  ``ielas.py``, whose functions it is composed from, with the support
  filter's redundancy removal in libelas's order (``redun_order`` =
  ``"sequential"``; ``ielas.py`` covers the parallel one):
  ``Elas::removeRedundantSupportPoints`` (Geiger et al., ACCV 2010,
  ``src/elas.cpp``), written here as its nested loops in NumPy: the
  vertical pass, then the horizontal pass on its output, each over
  columns ``u`` and, inside, rows ``v``, removing nodes in place, so a
  removed node no longer supports the next.
* :func:`warm_disparity`, a frame's warm answer, seeded by the previous
  frame's delivered disparity, as the service states its warm rule: the
  prior is sampled on the support lattice, interpolated with the iELAS
  rule and planed; the left view takes the prior's own value wherever it
  was valid and the plane elsewhere, the right view the plane through the
  re-projected lattice; each view then matches only the band
  ``|d - round(mu)| <= warm_band`` with the rational prior energy
  ``beta * SAD - 1 / (1 + (d - mu)^2 / (2 sigma^2))``, and the two maps are
  post-processed as a cold frame's.

Departures from libelas: the redundancy window is the configuration's
``redun_max_dist`` (libelas passes 5); the warm answer is no part of
libelas at all, it is the service's own temporal rule; the rest are those
of ``ielas.py``.

``ftype`` is the floating type every real-valued quantity is computed in,
the prior and both energies included: ``float32`` as the configuration
states, ``bfloat16`` for the control that must fail the check.  Float32
products run at ``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np


def _load_sibling(stem: str):
    """``reference/<stem>.py`` under the name the harness's ``load_module``
    gives it, so both share one module."""
    name = f"bench.reference.{stem}"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, Path(__file__).with_name(f"{stem}.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


_plain = _load_sibling("ielas")
INVALID = _plain.INVALID
BIG, BIGF = _plain.BIG, _plain.BIGF


@dataclasses.dataclass(frozen=True)
class Params(_plain.Params):
    """``ielas.py``'s parameters, and the order of the redundancy filter."""

    redun_order: str = "parallel"


def params_from(config: dict) -> Params:
    return Params(**config["params"])


def _check(p: Params) -> None:
    if p.disp_min != 0 or p.median_radius != 1 or p.invalid != INVALID:
        raise ValueError("the reference covers disp_min=0, a 3x3 median, invalid=-1")
    if p.redun_order != "sequential":
        raise ValueError(f"the video reference covers redun_order='sequential', "
                         f"not {p.redun_order!r} (see reference/ielas.py)")


# ------------------------------------------------------------ cold answer
def libelas_redundancy(grid: np.ndarray, max_dist: int, threshold: float) -> np.ndarray:
    """libelas's redundancy removal on a (GH, GW) support grid, INVALID
    (negative) where no node is: the vertical pass, then the horizontal."""
    g = np.array(grid, copy=True)
    gh, gw = g.shape
    for vertical in (True, False):
        dirs = ((-1, 0), (1, 0)) if vertical else ((0, -1), (0, 1))
        for u in range(gw):
            for v in range(gh):
                d = g[v, u]
                if d < 0:
                    continue
                redundant = True
                for dv, du in dirs:
                    support = False
                    v2, u2 = v, u
                    for _ in range(max_dist):
                        v2, u2 = v2 + dv, u2 + du
                        if v2 < 0 or u2 < 0 or v2 >= gh or u2 >= gw:
                            break
                        d2 = g[v2, u2]
                        if d2 >= 0 and abs(d - d2) <= threshold:
                            support = True
                            break
                    if not support:
                        redundant = False
                        break
                if redundant:
                    g[v, u] = INVALID
    return g


@functools.partial(jax.jit, static_argnames=("p", "ftype"))
def _support(left, right, p: Params, ftype: str):
    """Descriptors and the support grid with its inconsistent nodes removed
    (``ielas.py``'s filter with an empty redundancy window removes no
    redundant node)."""
    dl, dr = _plain.descriptors(left), _plain.descriptors(right)
    grid = _plain.support_grid(dl, dr, p, ftype)
    return dl, dr, _plain.filter_support(grid, dataclasses.replace(p, redun_max_dist=0))


@functools.partial(jax.jit, static_argnames=("p",))
def _dense(dl, dr, grid, p: Params):
    h, w, _ = dl.shape
    sup = _plain.interpolate(grid, p)
    sup_r = _plain.interpolate(_plain.right_view_support(sup, p), p)
    disp_l = _plain.dense_view(dl, dr, _plain.plane_prior(sup, h, w, p),
                               _plain.grid_vector(sup, p), -1, p)
    disp_r = _plain.dense_view(dr, dl, _plain.plane_prior(sup_r, h, w, p),
                               _plain.grid_vector(sup_r, p), +1, p)
    return _plain.postprocess(disp_l, disp_r, p).astype(jnp.float32)


def disparity(left, right, p: Params, ftype: str = "float32"):
    """(H, W) left and right images -> (H, W) float32 left disparity."""
    _check(p)
    with jax.default_matmul_precision("highest"):
        dl, dr, grid = _support(left, right, p, ftype)
        kept = libelas_redundancy(np.asarray(grid, np.float32), p.redun_max_dist,
                                  p.redun_threshold)
        return _dense(dl, dr, jnp.asarray(kept, ftype), p)


# ------------------------------------------------------------ warm answer
def lattice_samples(disp, p: Params):
    """The map's values at the support lattice's nodes (``candidate_step``
    apart from ``candidate_step // 2``): a (H // step, W // step) grid."""
    h, w = disp.shape
    step, off = p.candidate_step, p.candidate_step // 2
    return disp[off::step, off::step][: h // step, : w // step]


def band_view(src, dst, mu, sign, p: Params, warm_band: int):
    """Disparity of the ``src`` view over the band around ``mu`` alone:
    (H, W) in mu's type, INVALID where no band disparity is inside the
    image or the texture is too low."""
    ft = mu.dtype
    d = jnp.arange(p.num_disp) + p.disp_min
    cost = _plain.sad_volume(src, dst, p.num_disp, sign)          # (D, H, W)
    r = jnp.round(mu)
    lo = jnp.clip(r - warm_band, p.disp_min, p.disp_max)
    hi = jnp.clip(r + warm_band, p.disp_min, p.disp_max)
    df = d.astype(ft)[:, None, None]
    in_band = (df >= lo[None]) & (df <= hi[None])
    diff = df - mu[None]
    prior = -1.0 / (1.0 + diff * diff / (2.0 * p.sigma * p.sigma))
    energy = p.beta * cost.astype(ft) + prior
    energy = jnp.where(in_band & (cost < BIG), energy, jnp.asarray(BIGF, ft))
    best = (jnp.argmin(energy, axis=0) + p.disp_min).astype(ft)
    ok = (jnp.min(energy, axis=0) < BIGF) & (_plain.texture(src) >= p.match_texture)
    return jnp.where(ok, best, jnp.asarray(INVALID, ft))


@functools.partial(jax.jit, static_argnames=("p", "warm_band", "ftype"))
def _warm(left, right, prior, p: Params, warm_band: int, ftype: str):
    h, w = left.shape
    prior = prior.astype(ftype)
    grid = _plain.interpolate(lattice_samples(prior, p), p)
    mu_l = jnp.where(prior != INVALID, prior, _plain.plane_prior(grid, h, w, p))
    grid_r = _plain.interpolate(_plain.right_view_support(grid, p), p)
    mu_r = _plain.plane_prior(grid_r, h, w, p)
    dl, dr = _plain.descriptors(left), _plain.descriptors(right)
    disp_l = band_view(dl, dr, mu_l, -1, p, warm_band)
    disp_r = band_view(dr, dl, mu_r, +1, p, warm_band)
    return _plain.postprocess(disp_l, disp_r, p).astype(jnp.float32)


def warm_disparity(left, right, prior, p: Params, warm_band: int, ftype: str = "float32"):
    """(H, W) images and the previous frame's delivered (H, W) disparity ->
    the (H, W) float32 warm disparity."""
    _check(p)
    with jax.default_matmul_precision("highest"):
        return _warm(left, right, prior, p, warm_band, ftype)
