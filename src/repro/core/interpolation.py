"""iELAS support-point interpolation (Sec. II-B) -- THE paper's technique.

Fills every vacant node of the support grid so the set of support points has
*fixed number and coordinates*, which turns Delaunay triangulation into a
static regular mesh (see :mod:`repro.core.prior`).

Rules, faithful to the paper's text:

1. **Horizontal**: find nearest valid nodes (P_L, P_R) within ``s_delta`` on
   both sides.  If ``|D_L - D_R| <= epsilon`` interpolate with the mean,
   else with ``min(D_L, D_R)`` (occlusion-aware: the farther surface wins).
2. **Vertical**: same rule along columns if no horizontal pair exists.
3. **Constant**: fill ``C`` if neither direction yields a pair.

``border_extend=True`` adds the causal single-sided rule visible in the
paper's Fig. 2 worked example: when the *trailing* half of the search
window (right / bottom) is truncated by the image boundary, the leading
(left / top) value alone is used -- exactly what a streaming line-buffer
implementation produces at frame edges.

Everything is O(GH*GW*s_delta) static shifted slices and selects over the
``s_delta`` window -- no data-dependent control flow, no scatter, no
gather: the "regular manner" the paper advertises, expressed in
XLA-native form.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.params import ElasParams
from repro.core.support import INVALID


def nearest_valid_lr(
    grid: jax.Array, reach: int
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Nearest valid value/distance to the left and right along rows,
    looking at most ``reach`` entries away (distance 0 is the entry itself).

    Returns (val_l, dist_l, val_r, dist_r); dist is ``reach + 1`` and val
    INVALID where no valid entry lies within ``reach`` on that side.  The
    search is a static loop of shifted slices, far to near so the nearest
    valid entry writes last -- no gather, no data-dependent index.
    """
    gw = grid.shape[1]
    reach = max(reach, 0)
    n = min(reach, gw - 1)
    padded = jnp.pad(grid, ((0, 0), (n, n)), constant_values=INVALID)
    none = jnp.full(grid.shape, reach + 1, jnp.int32)
    val_l = val_r = jnp.full_like(grid, INVALID)
    dist_l = dist_r = none
    for s in range(n, -1, -1):
        left = padded[:, n - s : n - s + gw]
        right = padded[:, n + s : n + s + gw]
        val_l = jnp.where(left != INVALID, left, val_l)
        dist_l = jnp.where(left != INVALID, s, dist_l)
        val_r = jnp.where(right != INVALID, right, val_r)
        dist_r = jnp.where(right != INVALID, s, dist_r)
    return val_l, dist_l, val_r, dist_r


def _pair_rule(val_a: jax.Array, val_b: jax.Array, epsilon: float) -> jax.Array:
    """mean if |a-b| <= eps else min -- the paper's interpolation rule."""
    return jnp.where(
        jnp.abs(val_a - val_b) <= epsilon,
        0.5 * (val_a + val_b),
        jnp.minimum(val_a, val_b),
    )


def _axis_interpolation(
    grid: jax.Array, p: ElasParams, border_extend: bool
) -> tuple[jax.Array, jax.Array]:
    """One-axis (horizontal) interpolation: returns (value, found_mask)."""
    gw = grid.shape[1]
    col = jnp.arange(gw)[None, :]
    val_l, dist_l, val_r, dist_r = nearest_valid_lr(grid, p.s_delta)

    has_l = dist_l <= p.s_delta
    has_r = dist_r <= p.s_delta
    pair_val = _pair_rule(val_l, val_r, p.epsilon)
    found = has_l & has_r
    value = jnp.where(found, pair_val, INVALID)

    if border_extend:
        # Trailing window truncated by the boundary -> leading value extends.
        trailing_cut = (col + p.s_delta) >= gw
        ext = has_l & trailing_cut & ~found
        value = jnp.where(ext, val_l, value)
        found = found | ext
    return value, found


@functools.partial(jax.jit, static_argnames=("p", "border_extend"))
def interpolate_support(
    grid: jax.Array, p: ElasParams, border_extend: bool = True
) -> jax.Array:
    """Fill every vacant node; valid nodes pass through untouched.

    Output grid has NO invalid entries -- the fixed-coordinate support set
    that regularises triangulation.
    """
    h_val, h_found = _axis_interpolation(grid, p, border_extend)
    v_val_t, v_found_t = _axis_interpolation(grid.T, p, border_extend)
    v_val, v_found = v_val_t.T, v_found_t.T

    filled = jnp.where(
        h_found, h_val, jnp.where(v_found, v_val, p.const_fill)
    )
    valid = grid != INVALID
    return jnp.where(valid, grid, filled)
