"""Lane-major layout and shifted SAD rows shared by the two search kernels.

Mosaic vectorises over (8 sublanes x 128 lanes) tiles, so the search
kernels put the image WIDTH on lanes: descriptors travel as
``(16, rows, Wp)`` int32 (one lane-dense plane per descriptor channel,
``Wp`` the width rounded up to 128) instead of ``(rows, W, 16)`` int8,
which would use 16 of 128 lanes and need 32-row int8 tiles.  A shift by
the traced disparity ``d`` is then a lane rotation (``pltpu.roll``) -- the
one dynamic shift Mosaic lowers on vector values -- and the wrap-around
columns it brings in are exactly the ones the callers mask as out of
range (``u < d`` on the left view, ``u + d >= W`` on the right).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def pad2(x: jax.Array, rows: int, cols: int) -> jax.Array:
    """Zero-pad the last two axes of ``x`` up to ``(rows, cols)``."""
    pads = [(0, 0)] * (x.ndim - 2)
    pads += [(0, rows - x.shape[-2]), (0, cols - x.shape[-1])]
    return jnp.pad(x, pads)


def desc_lanes(desc: jax.Array, rows: int, cols: int) -> jax.Array:
    """(H, W, K) int8 descriptors -> (K, rows, cols) int32, zero-padded.

    int32 because the kernels accumulate the SAD in int32 (exact: the
    16-sample SAD is at most 16 * 255) and 32-bit rows tile as (8, 128).
    """
    planes = jnp.transpose(desc.astype(jnp.int32), (2, 0, 1))
    return pad2(planes, rows, cols)


def sad_row(dl_ref, dr_ref, d: jax.Array) -> jax.Array:
    """(rows, Wp) int32 SAD row at disparity ``d`` for the LEFT view.

    ``out[u] = sum_k |dl[k, u] - dr[k, u - d]|`` wherever ``u >= d``; the
    columns ``u < d`` hold wrapped-around values the caller masks.
    """
    acc = None
    for k in range(dl_ref.shape[0]):
        shifted = pltpu.roll(dr_ref[k], d, 1)
        term = jnp.abs(dl_ref[k] - shifted)
        acc = term if acc is None else acc + term
    return acc


def shift_left(row: jax.Array, d: jax.Array) -> jax.Array:
    """``out[u] = row[u + d]`` (the right-view diagonal of a cost row);
    columns with ``u + d`` past the padded width wrap and must be masked."""
    wp = row.shape[-1]
    return pltpu.roll(row, jax.lax.rem(wp - d, wp), row.ndim - 1)
