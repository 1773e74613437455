"""Slanted-plane disparity prior from the *regular* support grid.

After iELAS interpolation the support points have fixed coordinates on a
regular lattice, so their Delaunay triangulation is known statically: each
lattice cell splits along its TL-BR diagonal into two triangles.  The prior
mu(p) at a pixel is the plane through the pixel's containing triangle --
a closed-form, branch-free computation whose only data movement is a
static nearest upsample of the grid.  This is the payoff of the paper's
technique: the irregular mesh data structure disappears.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.params import ElasParams


def _upsample(x: jax.Array, idx: np.ndarray, axis: int) -> jax.Array:
    """``jnp.take(x, idx, axis)`` for a static sorted ``idx`` that steps by
    0 or 1, as slices, broadcasts and reshapes -- no element gather.

    Consecutive source entries repeated the same number of times form one
    segment: a slice, broadcast along a new axis and merged into ``axis``.
    """
    values, counts = np.unique(idx, return_counts=True)
    assert np.all(np.diff(values) == 1), "idx must step by 0 or 1"
    parts = []
    start = 0
    for end in range(1, len(values) + 1):
        if end < len(values) and counts[end] == counts[start]:
            continue
        seg = jax.lax.slice_in_dim(x, int(values[start]), int(values[end - 1]) + 1,
                                   axis=axis)
        rep = list(seg.shape)
        rep.insert(axis + 1, int(counts[start]))
        merged = list(x.shape)
        merged[axis] = seg.shape[axis] * int(counts[start])
        parts.append(jnp.broadcast_to(jnp.expand_dims(seg, axis + 1), rep)
                     .reshape(merged))
        start = end
    return jnp.concatenate(parts, axis=axis)


@functools.partial(jax.jit, static_argnames=("height", "width", "p"))
def plane_prior(
    support: jax.Array,        # (GH, GW) complete (interpolated) support grid
    height: int,
    width: int,
    p: ElasParams,
) -> jax.Array:
    """Per-pixel prior mu of shape (height, width), float32.

    Pixels outside the node hull extrapolate along the nearest cell's
    planes (equivalent to libelas' corner support points).
    """
    gh, gw = support.shape
    step = p.candidate_step
    off = step // 2

    y = jnp.arange(height, dtype=jnp.float32)
    x = jnp.arange(width, dtype=jnp.float32)

    iy = jnp.clip(jnp.floor((y - off) / step).astype(jnp.int32), 0, gh - 2)
    jx = jnp.clip(jnp.floor((x - off) / step).astype(jnp.int32), 0, gw - 2)
    fy = (y - off) / step - iy.astype(jnp.float32)       # may be <0 / >1 at borders
    fx = (x - off) / step - jx.astype(jnp.float32)

    # The same cells (iy, jx) from the shapes alone: a static nearest
    # upsample of the grid reads each pixel's four corners.  fy and fx keep
    # the device's own floor: with a constant there, the compiler may fuse
    # the quotient's multiply into the subtraction and change the last bit.
    cy = np.clip((np.arange(height) - off) // step, 0, gh - 2)
    cx = np.clip((np.arange(width) - off) // step, 0, gw - 2)
    top = _upsample(support, cy, 0)                      # (height, GW)
    bottom = _upsample(support, cy + 1, 0)
    d_tl = _upsample(top, cx, 1)
    d_tr = _upsample(top, cx + 1, 1)
    d_bl = _upsample(bottom, cx, 1)
    d_br = _upsample(bottom, cx + 1, 1)

    fyb = fy[:, None]
    fxb = fx[None, :]
    # Upper-right triangle (TL, TR, BR): plane d = TL + fx*(TR-TL) + fy*(BR-TR)
    upper = d_tl + fxb * (d_tr - d_tl) + fyb * (d_br - d_tr)
    # Lower-left triangle (TL, BR, BL): plane d = TL + fy*(BL-TL) + fx*(BR-BL)
    lower = d_tl + fyb * (d_bl - d_tl) + fxb * (d_br - d_bl)
    return jnp.where(fxb >= fyb, upper, lower)


@functools.partial(jax.jit, static_argnames=("p",))
def support_from_disparity(
    disp: jax.Array,           # (H, W) disparity map (INVALID sentinels ok)
    p: ElasParams,
) -> jax.Array:
    """Re-grid a dense disparity map onto the support lattice.

    Samples the map at the regular support-node coordinates
    (``candidate_step // 2 + i * candidate_step``, the same lattice
    :func:`plane_prior` interpolates from), yielding a (GH, GW) support
    grid.  INVALID pixels stay INVALID -- downstream callers run
    :func:`~repro.core.interpolation.interpolate_support` to fill the
    holes, exactly as they do for the sparse support search's output.
    This is the warm-start seam: frame *t-1*'s delivered disparity
    becomes frame *t*'s plane prior without re-running the support
    search.
    """
    h, w = disp.shape
    gh, gw = p.grid_shape(h, w)
    step = p.candidate_step
    off = step // 2
    # Strided slice, not an advanced-index gather: the node lattice is
    # static, so this is the same Mosaic-friendly access pattern the
    # support decision uses for candidate-column texture.
    return jax.lax.slice(
        disp,
        (off, off),
        (off + (gh - 1) * step + 1, off + (gw - 1) * step + 1),
        (step, step),
    )


def right_view_support(
    support_left: jax.Array,   # (GH, GW) left-view grid (may contain INVALID)
    p: ElasParams,
) -> jax.Array:
    """Re-express support points in right-image coordinates.

    A left node at column u with disparity d corresponds to right column
    u - d.  For each right-view node we take the disparity of the nearest
    projected left node within one grid pitch (the first such node on a
    tie); otherwise INVALID.  This is a regular (GW x GW per row)
    min-reduction -- no scatter, and the value at the argmin is picked by
    a one-hot max, not a gather.
    """
    from repro.core.support import INVALID

    gh, gw = support_left.shape
    step = p.candidate_step
    us = jnp.arange(gw, dtype=jnp.float32) * step + step // 2    # node pixel columns

    valid = support_left != INVALID
    proj = us[None, :] - support_left                             # right-image columns
    big = jnp.float32(1e9)
    # dist[i, j_right, k_left]
    dist = jnp.abs(proj[:, None, :] - us[None, :, None])
    dist = jnp.where(valid[:, None, :], dist, big)
    dmin = jnp.min(dist, axis=-1)                                 # (GH, GW)
    pick = jnp.arange(gw) == jnp.argmin(dist, axis=-1)[..., None]
    dval = jnp.max(jnp.where(pick, support_left[:, None, :], -jnp.inf), axis=-1)
    return jnp.where(dmin <= step, dval, INVALID)
