"""Pallas TPU kernel: streaming support-point disparity search (Fig. 6).

One program instance processes a block of candidate ROWS and runs the
disparity sweep of :func:`repro.kernels.ref.support_match_rows_streaming`:
a carry-only ``fori_loop`` over ``d`` computes one shifted SAD cost row per
step and folds it into 4-deep running (cost, d) registers -- for the left
view at EVERY column and, via the diagonal identity CV_R[d, u] =
CV[d, u + d] (a lane rotation of the same row), for the right view at
every column.  This is the module the original design spent 271.6 ms on.

The kernel stops at the registers.  The strided pick of the candidate
columns, the texture / uniqueness tests and the one-hot L/R cross check
(:func:`repro.kernels.ref._support_decision`) run in XLA on the kernel's
outputs: they have no disparity loop, and the one-hot alone would be
``(rows, GW, W)`` int32 in VMEM (~10 MB at KITTI).  Picking the
candidate columns out of all-column registers is elementwise-equal to
folding the picked columns, so the result is bitwise identical to the
streaming oracle.

VMEM per program at KITTI width (Wp=1280, 8 rows): descriptors
2 x (16, 8, 1280) int32 ~ 1.3 MB and registers 4 x (4, 8, 1280) int32
~ 0.66 MB, each double-buffered -- O(W), constant in D.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref
from repro.kernels.lanes import LANES, desc_lanes, round_up, sad_row, shift_left


def _insert4(regs, v, d):
    """:func:`repro.kernels.ref._insert4` on a tuple of registers (no
    stacking inside the kernel): insert cost ``v`` at disparity ``d``."""
    (v1, v2, v3, v4), (i1, i2, i3, i4) = regs
    b1, b2, b3, b4 = v < v1, v < v2, v < v3, v < v4
    return (
        (jnp.where(b1, v, v1),
         jnp.where(b1, v1, jnp.where(b2, v, v2)),
         jnp.where(b2, v2, jnp.where(b3, v, v3)),
         jnp.where(b3, v3, jnp.where(b4, v, v4))),
        (jnp.where(b1, d, i1),
         jnp.where(b1, i1, jnp.where(b2, d, i2)),
         jnp.where(b2, i2, jnp.where(b3, d, i3)),
         jnp.where(b3, i3, jnp.where(b4, d, i4))),
    )


def _support_kernel(
    dl_ref,                     # (16, bh, Wp) int32 lane-major descriptors
    dr_ref,
    vals_l_ref,                 # (4, bh, Wp) int32 outputs: sorted costs
    idxs_l_ref,                 # (4, bh, Wp) int32: their disparities
    vals_r_ref,
    idxs_r_ref,
    *,
    width: int,
    num_disp: int,
):
    _, bh, wp = dl_ref.shape
    u = jax.lax.broadcasted_iota(jnp.int32, (bh, wp), 1)

    def step(d, carry):
        left, right = carry
        sad = sad_row(dl_ref, dr_ref, d)
        cost = jnp.where(u >= d, sad, ref.BIG)
        diag = jnp.where(u + d < width, shift_left(sad, d), ref.BIG)
        return _insert4(left, cost, d), _insert4(right, diag, d)

    def init():
        big = jnp.full((bh, wp), ref.BIG, jnp.int32)
        zero = jnp.zeros((bh, wp), jnp.int32)
        return (big,) * 4, (zero,) * 4

    left, right = jax.lax.fori_loop(0, num_disp, step, (init(), init()))
    for out_ref, regs in ((vals_l_ref, left[0]), (idxs_l_ref, left[1]),
                          (vals_r_ref, right[0]), (idxs_r_ref, right[1])):
        for j, reg in enumerate(regs):
            out_ref[j] = reg


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_disp",
        "step",
        "offset",
        "support_texture",
        "support_ratio",
        "lr_threshold",
        "disp_min",
        "block_rows",
        "interpret",
    ),
)
def support_match_pallas(
    desc_l_rows: jax.Array,     # (GH, W, 16) int8 -- left descriptors, candidate rows
    desc_r_rows: jax.Array,     # (GH, W, 16) int8
    *,
    num_disp: int,
    step: int,
    offset: int,
    support_texture: int,
    support_ratio: float,
    lr_threshold: int,
    disp_min: int,
    block_rows: int = 8,
    interpret: bool = True,
) -> jax.Array:
    """(GH, GW) float32 support grid rows (disparity or INVALID).

    The wrapper lays the descriptors out width-on-lanes (padded to a
    multiple of 128, rows padded to whole blocks), the kernel produces the
    running registers, and XLA finishes with the strided candidate pick
    and :func:`repro.kernels.ref._support_decision`.
    """
    gh, w, k = desc_l_rows.shape
    gw = w // step
    bh = min(block_rows, gh)
    ghp, wp = round_up(gh, bh), round_up(w, LANES)
    in_spec = pl.BlockSpec((k, bh, wp), lambda i: (0, i, 0))
    reg_spec = pl.BlockSpec((4, bh, wp), lambda i: (0, i, 0))
    reg_shape = jax.ShapeDtypeStruct((4, ghp, wp), jnp.int32)

    kernel = functools.partial(_support_kernel, width=w, num_disp=num_disp)
    vals_l, idxs_l, vals_r, idxs_r = (
        r[:, :gh, :w] for r in pl.pallas_call(
            kernel,
            grid=(ghp // bh,),
            in_specs=[in_spec, in_spec],
            out_specs=[reg_spec] * 4,
            out_shape=[reg_shape] * 4,
            interpret=interpret,
        )(desc_lanes(desc_l_rows, ghp, wp), desc_lanes(desc_r_rows, ghp, wp))
    )

    def candidates(regs):
        return jax.lax.slice_in_dim(
            regs, offset, offset + (gw - 1) * step + 1, stride=step, axis=2
        )

    best_l, min1_l, min2_l = ref._finalize4(candidates(vals_l), candidates(idxs_l))
    best_r, min1_r, min2_r = ref._finalize4(vals_r, idxs_r)
    return ref._support_decision(
        best_l, min1_l, min2_l, best_r, min1_r, min2_r, desc_l_rows, desc_r_rows,
        step=step, offset=offset, support_texture=support_texture,
        support_ratio=support_ratio, lr_threshold=lr_threshold,
        disp_min=disp_min,
    )
