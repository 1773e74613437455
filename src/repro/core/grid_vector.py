"""Grid vector: per-cell candidate disparity sets (Sec. II-A / III-C).

For every ``grid_size``-pixel cell, pool the support disparities from the
cell and its 8 neighbours and keep a STATIC top-K representative set
(K = ``grid_vector_k`` = 20, the paper's "Grid Vector Optimization" -- the
original stores all 256).  Dense matching then only evaluates these K
candidates plus the plane-prior neighbourhood.

Because the support nodes sit on a regular lattice whose pitch divides the
cell size, the pooling is a static reshape and shifted slices -- no
histograms, no variable-length sets, no gather.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.params import ElasParams
from repro.core.support import INVALID


@functools.partial(jax.jit, static_argnames=("p",))
def build_grid_vector(support: jax.Array, p: ElasParams) -> jax.Array:
    """(CH, CW, K) float32 candidate disparities per cell.

    ``support`` may be the sparse (filtered) or the interpolated grid;
    invalid entries are ignored.  Cells with no valid support fall back to
    ``const_fill``.  Representatives are evenly-spaced order statistics of
    the pooled neighbourhood (a static surrogate for "the set of observed
    disparities", robust to duplicates).
    """
    gh, gw = support.shape
    step = p.candidate_step
    assert p.grid_size % step == 0, "grid_size must be a multiple of candidate_step"
    npc = p.grid_size // step                       # nodes per cell per axis
    ch, cw = gh // npc, gw // npc
    k = p.grid_vector_k

    # Neighbourhood = cell +/- 1 cell -> 3*npc nodes per axis.
    win = 3 * npc
    padded = jnp.pad(
        support[: ch * npc, : cw * npc],
        ((npc, npc), (npc, npc)),
        constant_values=INVALID,
    )
    # Node (cy*npc + dy, cx*npc + dx) of the padded grid, as a reshape to
    # (cell, node-in-cell) per axis and three shifted cell slices per axis.
    def shifts(x, axis, n):       # (.., n, ..) -> (.., n, 3, ..): cells q, q+1, q+2
        return jnp.stack(
            [jax.lax.slice_in_dim(x, q, q + n, axis=axis) for q in range(3)],
            axis=axis + 1,
        )

    r = padded.reshape(ch + 2, npc, cw + 2, npc)
    rows = shifts(r, 0, ch).reshape(ch, win, cw + 2, npc)           # (CH, dy, ., .)
    both = shifts(rows, 2, cw).reshape(ch, win, cw, win)            # (CH, dy, CW, dx)
    pool = both.transpose(0, 2, 1, 3).reshape(ch, cw, win * win)    # (CH, CW, win*win)

    valid = pool != INVALID
    big = jnp.float32(1e9)
    sorted_pool = jnp.sort(jnp.where(valid, pool, big), axis=-1)
    n_valid = jnp.sum(valid, axis=-1)               # (CH, CW)

    # Evenly-spaced order statistics over the valid prefix.
    ranks = jnp.arange(k, dtype=jnp.float32)[None, None, :]
    scale = jnp.maximum(n_valid - 1, 0).astype(jnp.float32)[..., None]
    idx = jnp.where(
        n_valid[..., None] > 0,
        jnp.round(ranks * scale / jnp.maximum(k - 1, 1)).astype(jnp.int32),
        0,
    )
    # Rank selection as a one-hot max over the win*win sorted slots.
    pick = idx[..., None] == jnp.arange(win * win)
    reps = jnp.max(jnp.where(pick, sorted_pool[..., None, :], -jnp.inf), axis=-1)
    return jnp.where(n_valid[..., None] > 0, reps, p.const_fill)


def cell_index(height: int, width: int, p: ElasParams) -> tuple[jax.Array, jax.Array]:
    """Map every pixel to its grid-vector cell (clipped at borders)."""
    npc_px = p.grid_size
    ch = height // npc_px
    cw = width // npc_px
    cy = jnp.clip(jnp.arange(height) // npc_px, 0, ch - 1)
    cx = jnp.clip(jnp.arange(width) // npc_px, 0, cw - 1)
    return cy, cx
