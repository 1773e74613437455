"""Support-point filtering (Sec. II-A "Filtering").

Two removals, both expressed as static shifts, selects and (for the
sequential order) a running max on the dense support grid:

* **implausible**: a node must have at least ``incon_min_support`` valid
  neighbours within a ``(2*incon_window+1)^2`` window whose disparity is
  within ``incon_threshold`` -- otherwise it is inconsistent with its
  surroundings and corrupts the coarse representation.
* **redundant**: a node whose neighbours on BOTH sides of a line, within
  ``redun_max_dist``, hold (near-)identical disparity adds nothing to the
  coarse mesh and is removed.  ``ElasParams.redun_order`` picks how:

  - ``"parallel"``: every node is judged against the unfiltered grid, in
    its column and in its row at once.  On planar scenes nearly every node
    has a like neighbour on both sides, so a run of like nodes is emptied
    down to its two ends and the interpolation is left with gaps wider
    than ``s_delta``.
  - ``"sequential"``: libelas's ``Elas::removeRedundantSupportPoints``
    (``src/elas.cpp``): down each column, then along each row of the
    column pass's output, each node judged in scan order against the grid
    as already filtered, so a removed node no longer supports the next
    one and a run of like nodes is thinned to every other node.  At
    ``redun_max_dist`` 1 that order has a closed form (:func:`_thin_runs`)
    of a running max and selects, with no gather and no serial loop.

  Two orders exist only because the benchmark's cold configurations pin
  the parallel one; once they move to the libelas order, the parallel
  order and the option go.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.params import ElasParams
from repro.core.support import INVALID


def _shift2d(x: jax.Array, dy: int, dx: int, fill: float) -> jax.Array:
    """Shift a 2-D array by (dy, dx), filling vacated cells."""
    gh, gw = x.shape
    padded = jnp.pad(x, ((abs(dy), abs(dy)), (abs(dx), abs(dx))), constant_values=fill)
    return jax.lax.dynamic_slice(padded, (abs(dy) - dy, abs(dx) - dx), (gh, gw))


def remove_inconsistent(grid: jax.Array, p: ElasParams) -> jax.Array:
    valid = grid != INVALID
    count = jnp.zeros(grid.shape, jnp.int32)
    for dy in range(-p.incon_window, p.incon_window + 1):
        for dx in range(-p.incon_window, p.incon_window + 1):
            if dy == 0 and dx == 0:
                continue
            nb = _shift2d(grid, dy, dx, INVALID)
            ok = (nb != INVALID) & (jnp.abs(nb - grid) <= p.incon_threshold)
            count = count + ok.astype(jnp.int32)
    keep = valid & (count >= p.incon_min_support)
    return jnp.where(keep, grid, INVALID)


def _redundant_axis(grid: jax.Array, p: ElasParams, axis: int) -> jax.Array:
    """True where a node has near-identical valid neighbours on both sides
    along ``axis`` within ``redun_max_dist``."""
    before = jnp.zeros(grid.shape, bool)
    after = jnp.zeros(grid.shape, bool)
    for k in range(1, p.redun_max_dist + 1):
        dy, dx = (k, 0) if axis == 0 else (0, k)
        nb_b = _shift2d(grid, dy, dx, INVALID)      # neighbour from before (above/left)
        nb_a = _shift2d(grid, -dy, -dx, INVALID)    # neighbour from after (below/right)
        before |= (nb_b != INVALID) & (jnp.abs(nb_b - grid) <= p.redun_threshold)
        after |= (nb_a != INVALID) & (jnp.abs(nb_a - grid) <= p.redun_threshold)
    return before & after


def _thin_runs(grid: jax.Array, p: ElasParams, axis: int) -> jax.Array:
    """One libelas redundancy pass along ``axis`` at ``redun_max_dist`` 1.

    Two neighbours are *linked* when both are valid and their disparities
    differ by at most ``redun_threshold``.  Scanning in order and removing
    in place, a node is removed when it is valid and both its predecessor
    (as already filtered) and its successor (not yet visited) are linked
    to it.  A run's first node has no linked predecessor and stays; the
    second goes if the run goes on; the third then has a removed
    predecessor and stays; and so on.  So a node goes exactly when it lies
    at an odd position of its run of linked nodes and its successor is
    linked.
    """
    valid = grid != INVALID
    dy, dx = (-1, 0) if axis == 0 else (0, -1)
    succ = _shift2d(grid, dy, dx, INVALID)
    link_next = valid & (succ != INVALID) & (jnp.abs(succ - grid) <= p.redun_threshold)
    link_prev = _shift2d(link_next, -dy, -dx, False)
    idx = jax.lax.broadcasted_iota(jnp.int32, grid.shape, axis)
    run_start = jax.lax.cummax(jnp.where(link_prev, 0, idx), axis=axis)
    odd = ((idx - run_start) % 2) == 1
    return jnp.where(odd & link_next, INVALID, grid)


def remove_redundant(grid: jax.Array, p: ElasParams) -> jax.Array:
    if p.redun_order == "sequential":
        return _thin_runs(_thin_runs(grid, p, axis=0), p, axis=1)
    valid = grid != INVALID
    redundant = _redundant_axis(grid, p, axis=0) | _redundant_axis(grid, p, axis=1)
    keep = valid & ~redundant
    return jnp.where(keep, grid, INVALID)


def filter_support(grid: jax.Array, p: ElasParams) -> jax.Array:
    return remove_redundant(remove_inconsistent(grid, p), p)
