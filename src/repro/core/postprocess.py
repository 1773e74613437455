"""Post-processing: left/right consistency, gap interpolation, median filter.

All stages are branch-free static shifts and selects (the same
nearest-valid-neighbour machinery as the support interpolation), so the
whole post-process chain stays on-device and holds no gather.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.interpolation import nearest_valid_lr
from repro.core.params import ElasParams

INVALID = -1.0


@functools.partial(jax.jit, static_argnames=("p",))
def lr_consistency(
    disp_left: jax.Array, disp_right: jax.Array, p: ElasParams
) -> jax.Array:
    """Invalidate pixels whose right-image counterpart disagrees.

    ``disp_left`` must hold a dense scan's output: whole disparities
    ``d`` in ``[disp_min, disp_max]`` (as float32) or INVALID.  A pixel
    at column ``u`` reads ``disp_right[:, clip(u - d, 0, W - 1)]``, i.e.
    the ``d``-th static shift of an edge-padded ``disp_right``, picked by
    a select over the ``num_disp`` shifts -- no gather.  An INVALID pixel
    matches no shift and fails the check whatever it would read.
    """
    h, w = disp_left.shape
    lead, trail = max(p.disp_max, 0), max(-p.disp_min, 0)
    padded = jnp.pad(disp_right, ((0, 0), (lead, trail)), mode="edge")
    d_r = jnp.full_like(disp_right, INVALID)
    for d in range(p.disp_min, p.disp_max + 1):
        d_r = jnp.where(disp_left == d, padded[:, lead - d : lead - d + w], d_r)
    ok = (
        (disp_left != INVALID)
        & (d_r != INVALID)
        & (jnp.abs(disp_left - d_r) <= p.lr_check_threshold)
    )
    return jnp.where(ok, disp_left, INVALID)


@functools.partial(jax.jit, static_argnames=("p",))
def gap_interpolation(disp: jax.Array, p: ElasParams) -> jax.Array:
    """Fill horizontal invalid runs of length <= ipol_gap_width.

    Smooth gaps (end difference <= 5) are filled linearly; discontinuities
    take the min (background wins, occlusion-aware) -- libelas semantics.
    A gap's ends each lie within ``ipol_gap_width`` of its pixels, so the
    nearest-valid search looks no further; beyond it the distance reads
    ``ipol_gap_width + 1`` and the gap is too wide to fill.
    """
    val_l, dist_l, val_r, dist_r = nearest_valid_lr(disp, p.ipol_gap_width)
    gap = dist_l + dist_r - 1
    fillable = (disp == INVALID) & (gap <= p.ipol_gap_width)
    t = dist_l.astype(jnp.float32) / jnp.maximum(dist_l + dist_r, 1).astype(jnp.float32)
    linear = val_l + t * (val_r - val_l)
    fill = jnp.where(jnp.abs(val_l - val_r) <= 5.0, linear, jnp.minimum(val_l, val_r))
    return jnp.where(fillable, fill, disp)


@jax.jit
def median3x3(disp: jax.Array) -> jax.Array:
    """3x3 median over valid pixels; invalid pixels stay invalid.

    Invalid neighbours are replaced by the centre value so they do not bias
    the median (equivalent to clamping the window to valid support).  The
    median itself is Paeth's 19-op min/max selection network
    (:func:`repro.kernels.ref.median9`) -- value-identical to sorting the
    window and taking element 4, but ~10x cheaper under XLA:CPU, which
    matters because this filter sits inside the gated dense stage.
    """
    from repro.kernels.ref import median9   # late import: kernels build on core

    h, w = disp.shape
    padded = jnp.pad(disp, 1, mode="edge")
    wins = []
    for dy in range(3):
        for dx in range(3):
            win = padded[dy : dy + h, dx : dx + w]
            wins.append(jnp.where(win == INVALID, disp, win))
    med = median9(wins)
    return jnp.where(disp == INVALID, INVALID, med)


@functools.partial(jax.jit, static_argnames=("p",))
def postprocess(
    disp_left: jax.Array, disp_right: jax.Array, p: ElasParams
) -> jax.Array:
    d = lr_consistency(disp_left, disp_right, p)
    d = gap_interpolation(d, p)
    d = median3x3(d)
    return d
