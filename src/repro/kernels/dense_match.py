"""Pallas TPU kernels: row-tiled dense matching for BOTH views.

The heaviest stage (374.4 ms in the original design).  The kernel grid
walks the image in row tiles of ``block_rows`` rows -- the software
analogue of the FPGA's line-buffered tiling -- and both disparity maps
come from one pass over the descriptors (a beyond-paper fusion: the FPGA
design computes the two views independently).

* :func:`dense_match_stream_pallas` -- the default path on every backend
  (``gather="stream"``) and the one ``pallas_tpu`` compiles with Mosaic.
  Its body is a TPU-native rewrite of the gather-free scan
  :func:`repro.kernels.ref.dense_match_rows_stream_ref`: width on lanes,
  a carry-only ``fori_loop`` over ``d``, lane rotations for the shifts,
  an int32 SAD.  It evaluates the same float expressions in the same
  order as the oracle, so interpret mode (the ``pallas`` backend) is
  bitwise equal to it; on the chip Mosaic's ``log``/``exp`` may round
  differently from XLA's.
* :func:`dense_match_pallas` -- the windowed candidate-window kernel
  (``gather`` in ``take``/``onehot``/``slice``).  Its body calls
  :func:`repro.kernels.ref.dense_match_rows_windowed_ref`, whose gathers
  and scans Mosaic does not lower, so it runs in interpret mode only and
  is not on any default path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref
from repro.kernels.lanes import LANES, desc_lanes, pad2, round_up, sad_row, shift_left


def _dense_kernel(
    desc_l_ref,
    desc_r_ref,
    mu_l_ref,
    mu_r_ref,
    cand_l_ref,
    cand_r_ref,
    out_l_ref,
    out_r_ref,
    *,
    num_disp: int,
    beta: float,
    gamma: float,
    sigma: float,
    match_texture: int,
    gather_impl: str,
    disp_min: int,
):
    disp_l, disp_r = ref.dense_match_rows_windowed_ref(
        desc_l_ref[...],
        desc_r_ref[...],
        mu_l_ref[...],
        mu_r_ref[...],
        cand_l_ref[...],
        cand_r_ref[...],
        num_disp=num_disp,
        beta=beta,
        gamma=gamma,
        sigma=sigma,
        match_texture=match_texture,
        gather_impl=gather_impl,
        disp_min=disp_min,
    )
    out_l_ref[...] = disp_l
    out_r_ref[...] = disp_r


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_disp", "beta", "gamma", "sigma", "match_texture",
        "block_rows", "interpret", "gather_impl", "disp_min",
    ),
)
def dense_match_pallas(
    desc_l: jax.Array,          # (H, W, 16) int8
    desc_r: jax.Array,          # (H, W, 16) int8
    mu_l: jax.Array,            # (H, W) float32
    mu_r: jax.Array,            # (H, W) float32
    cand_l: jax.Array,          # (H, W, C) int32
    cand_r: jax.Array,          # (H, W, C) int32
    *,
    num_disp: int,
    beta: float,
    gamma: float,
    sigma: float,
    match_texture: int,
    block_rows: int = 4,
    interpret: bool = True,
    gather_impl: str = "take",
    disp_min: int = 0,
) -> tuple[jax.Array, jax.Array]:
    """Row-tiled candidate-window dense matching; ``block_rows`` is the
    tile height (dense matching has no cross-row dependency, so any tile
    height yields bitwise-identical output) and ``gather_impl`` the
    candidate-gather formulation (any choice is bitwise identical)."""
    h, w, k = desc_l.shape
    c = cand_l.shape[-1]
    bh = min(block_rows, h)
    grid = (pl.cdiv(h, bh),)

    desc_spec = pl.BlockSpec((bh, w, k), lambda i: (i, 0, 0))
    map_spec = pl.BlockSpec((bh, w), lambda i: (i, 0))
    cand_spec = pl.BlockSpec((bh, w, c), lambda i: (i, 0, 0))

    kernel = functools.partial(
        _dense_kernel,
        num_disp=num_disp,
        beta=beta,
        gamma=gamma,
        sigma=sigma,
        match_texture=match_texture,
        gather_impl=gather_impl,
        disp_min=disp_min,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[desc_spec, desc_spec, map_spec, map_spec, cand_spec, cand_spec],
        out_specs=[map_spec, map_spec],
        out_shape=[
            jax.ShapeDtypeStruct((h, w), jnp.float32),
            jax.ShapeDtypeStruct((h, w), jnp.float32),
        ],
        interpret=interpret,
    )(desc_l, desc_r, mu_l, mu_r, cand_l, cand_r)


def _dense_stream_kernel(
    dl_ref,                     # (16, bh, Wp) int32 lane-major descriptors
    dr_ref,
    mu_l_ref,                   # (bh, Wp) float32
    mu_r_ref,
    gm_l_ref,                   # (D, bh, CWp) float32 per-cell bitmask
    gm_r_ref,
    up_ref,                     # (CWp, Wp) float32 cell -> column one-hot
    out_l_ref,                  # (bh, Wp) float32
    out_r_ref,
    *,
    width: int,
    num_disp: int,
    disp_min: int,
    plane_radius: int,
    beta: float,
    gamma: float,
    sigma: float,
    match_texture: int,
):
    """One row block of :func:`repro.kernels.ref.dense_match_rows_stream_ref`.

    Same per-step math in the same order (so interpret mode is bitwise
    equal to the oracle), laid out for Mosaic: a carry-only ``fori_loop``
    over ``d``, lane rotations for the shifts, the bitmask row for step
    ``i`` read as ``gm_ref[i]`` off a leading axis and upsampled to pixel
    columns by a one-hot matmul (0/1 products, exact), int32 SAD.
    """
    bh, wp = mu_l_ref.shape
    u = jax.lax.broadcasted_iota(jnp.int32, (bh, wp), 1)
    lo_d = float(disp_min)
    hi_d = float(disp_min + num_disp - 1)

    def prior_band(mu):
        r = jnp.round(mu)
        return (jnp.clip(r - plane_radius, lo_d, hi_d),
                jnp.clip(r + plane_radius, lo_d, hi_d))

    band_l = prior_band(mu_l_ref[...])
    band_r = prior_band(mu_r_ref[...])

    def update(state, sad, valid, mu, band, gcells, d, df):
        best_e, best_d = state
        mask = jnp.dot(gcells, up_ref[...],
                       preferred_element_type=jnp.float32) > 0.5
        mask = mask | ((df >= band[0]) & (df <= band[1]))
        diff = df - mu
        prior = -jnp.log(gamma + jnp.exp(-(diff * diff) / (2.0 * sigma * sigma)))
        e = beta * sad.astype(jnp.float32) + prior
        e = jnp.where(mask & valid, e, ref.BIGF)
        better = e < best_e
        return jnp.where(better, e, best_e), jnp.where(better, d, best_d)

    def step(i, carry):
        left, right = carry
        d = i + disp_min
        df = d.astype(jnp.float32)
        sad = sad_row(dl_ref, dr_ref, d)
        left = update(left, sad, u >= d, mu_l_ref[...], band_l, gm_l_ref[i],
                      d, df)
        right = update(right, shift_left(sad, d), u + d < width,
                       mu_r_ref[...], band_r, gm_r_ref[i], d, df)
        return left, right

    def init():
        return (jnp.full((bh, wp), ref.BIGF, jnp.float32),
                jnp.zeros((bh, wp), jnp.int32))

    (emin_l, best_l), (emin_r, best_r) = jax.lax.fori_loop(
        0, num_disp, step, (init(), init())
    )

    def finish(emin, best, desc_ref):
        tex = sum(jnp.abs(desc_ref[k]) for k in range(desc_ref.shape[0]))
        valid = (emin < ref.BIGF) & (tex >= match_texture)
        return jnp.where(valid, best.astype(jnp.float32), ref.INVALID)

    out_l_ref[...] = finish(emin_l, best_l, dl_ref)
    out_r_ref[...] = finish(emin_r, best_r, dr_ref)


def _cell_upsampler(cw: int, cwp: int, w: int, wp: int, cell_px: int) -> jax.Array:
    """(CWp, Wp) float32 one-hot: column ``u`` reads cell
    ``min(u // cell_px, cw - 1)`` -- :func:`repro.kernels.ref.upsample_cells`'
    repeat-and-extend-the-tail mapping as a matrix.  Pad columns read
    nothing."""
    u = jnp.arange(wp)
    cell = jnp.where(u < w, jnp.minimum(u // cell_px, cw - 1), -1)
    return (jnp.arange(cwp)[:, None] == cell[None, :]).astype(jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_disp", "disp_min", "plane_radius", "cell_px", "beta", "gamma",
        "sigma", "match_texture", "block_rows", "interpret", "precision",
    ),
)
def dense_match_stream_pallas(
    desc_l: jax.Array,          # (H, W, 16) int8
    desc_r: jax.Array,          # (H, W, 16) int8
    mu_l: jax.Array,            # (H, W) float32
    mu_r: jax.Array,            # (H, W) float32
    gmask_l: jax.Array,         # (H, CW, D) bool grid-vector bitmask rows
    gmask_r: jax.Array,         # (H, CW, D) bool
    *,
    num_disp: int,
    disp_min: int,
    plane_radius: int,
    cell_px: int,
    beta: float,
    gamma: float,
    sigma: float,
    match_texture: int,
    block_rows: int = 8,
    interpret: bool = True,
    precision: str = "int8",
) -> tuple[jax.Array, jax.Array]:
    """Row-tiled STREAMING dense matching: the gather-free scan over ``d``.

    Each program runs one ``block_rows``-row block through the disparity
    sweep of :func:`repro.kernels.ref.dense_match_rows_stream_ref` --
    shifted SAD rows folded into running (best energy, best d) registers
    under the grid-vector bitmask / plane-prior-band mask.  The wrapper
    re-lays the inputs for Mosaic: width on lanes padded to a multiple of
    128, rows padded to whole blocks, the bitmask as ``(D, H, CWp)``
    float32.  VMEM per program at KITTI width (Wp=1280, D=128, 8 rows):
    descriptors 2 x 655 KB, bitmasks 2 x 524 KB, upsampler 655 KB, each
    double-buffered -- constant in H.  ``precision`` is accepted for the
    registry's common signature; both datapaths are exact, and the kernel
    accumulates in int32.
    """
    del precision
    h, w, _ = desc_l.shape
    cw = gmask_l.shape[1]
    bh = min(block_rows, h)
    hp, wp, cwp = round_up(h, bh), round_up(w, LANES), round_up(cw, LANES)

    def bitmask(g):
        return pad2(jnp.transpose(g, (2, 0, 1)).astype(jnp.float32), hp, cwp)

    k = desc_l.shape[-1]
    desc_spec = pl.BlockSpec((k, bh, wp), lambda i: (0, i, 0))
    map_spec = pl.BlockSpec((bh, wp), lambda i: (i, 0))
    mask_spec = pl.BlockSpec((num_disp, bh, cwp), lambda i: (0, i, 0))
    up_spec = pl.BlockSpec((cwp, wp), lambda i: (0, 0))

    kernel = functools.partial(
        _dense_stream_kernel,
        width=w,
        num_disp=num_disp,
        disp_min=disp_min,
        plane_radius=plane_radius,
        beta=beta,
        gamma=gamma,
        sigma=sigma,
        match_texture=match_texture,
    )
    out_l, out_r = pl.pallas_call(
        kernel,
        grid=(hp // bh,),
        in_specs=[desc_spec, desc_spec, map_spec, map_spec,
                  mask_spec, mask_spec, up_spec],
        out_specs=[map_spec, map_spec],
        out_shape=[
            jax.ShapeDtypeStruct((hp, wp), jnp.float32),
            jax.ShapeDtypeStruct((hp, wp), jnp.float32),
        ],
        interpret=interpret,
    )(
        desc_lanes(desc_l, hp, wp), desc_lanes(desc_r, hp, wp),
        pad2(mu_l, hp, wp), pad2(mu_r, hp, wp),
        bitmask(gmask_l), bitmask(gmask_r),
        _cell_upsampler(cw, cwp, w, wp, cell_px),
    )
    return out_l[:h, :w], out_r[:h, :w]
