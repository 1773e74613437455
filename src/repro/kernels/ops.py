"""jit'd public wrappers around the kernel backends.

``backend`` names an entry in the kernel registry (:mod:`repro.kernels.registry`):

  * ``"ref"``     -- pure-jnp math, streaming-scan formulation (default on
                     CPU: bitwise identical to the materialised oracles in
                     :mod:`repro.kernels.ref`, fast under XLA:CPU).
  * ``"pallas"``  -- the Pallas kernels with ``interpret=True`` (kernel
                     bodies execute in Python on CPU -- correctness mode).
  * ``"pallas_tpu"`` -- the Pallas kernels compiled for TPU.

Core pipeline code calls these wrappers, so switching the whole stereo
system between oracle and kernel execution is one registry name.  The name
stays a jit-static string; the wrapper resolves it to a
:class:`~repro.kernels.registry.KernelBackend` at trace time and dispatches
through the registry rather than an if/elif ladder per op.  Dispatch is
device-aware: ``backend=None`` resolves through
:func:`~repro.kernels.registry.default_backend` (``pallas_tpu`` on TPU,
``ref`` elsewhere) and ``tile=None`` through the resolved backend's
:meth:`~repro.core.tiling.TileCapability.default_tile`, so no call site
needs to name a backend or tile shape; the explicit
:data:`~repro.core.tiling.UNTILED` sentinel opts out of tiling.

Dense matching and the support search additionally accept a
:class:`~repro.core.tiling.TileSpec`: each backend declares its per-stage
tiling capability in the registry, and the wrappers route to the backend's
row-tiled entry points (bitwise identical to the untiled paths) when the
caller asks for tiling and the backend supports it, threading the tile's
``gather`` formulation (take_along_axis / one-hot matmul / windowed
dynamic slices / the gather-free streaming scan -- all bitwise identical)
and its ``precision`` (f32 / int8 SAD datapath -- also bitwise identical)
into the dense kernels.  ``gather="stream"`` -- every built-in backend's
default -- runs :func:`dense_match_stream`, which consumes grid-vector
bitmasks instead of candidate tensors.  Both untiled "ref" search ops are
the STREAMING scan formulations -- the materialised oracles stay in
:mod:`repro.kernels.ref` as the ground truth the streaming paths are
pinned against, so no registered backend materialises a ``(rows, D, W)``
volume anywhere.
"""
from __future__ import annotations

import functools
from typing import Literal, Optional

import jax
import jax.numpy as jnp

from repro.core.params import ElasParams
from repro.core.tiling import TileArg, TileCapability, TileSpec
from repro.kernels import ref
from repro.kernels.dense_match import dense_match_pallas, dense_match_stream_pallas
from repro.kernels.median import median3x3_pallas
from repro.kernels.registry import (
    KernelBackend,
    available_backends,
    get_backend,
    register_backend,
    resolve_backend,
    resolve_dispatch,
)
from repro.kernels.sobel import sobel_pallas
from repro.kernels.support_match import support_match_pallas

Backend = Optional[Literal["ref", "pallas", "pallas_tpu"]]


# --------------------------------------------------------------- ref backend
def _sobel_ref(image: jax.Array) -> tuple[jax.Array, jax.Array]:
    h, w = image.shape
    padded = jnp.pad(image.astype(jnp.int32), 1, mode="edge")
    return ref.sobel_rows_ref(
        padded[0:h, :], padded[1 : h + 1, :], padded[2 : h + 2, :]
    )


def _median3x3_ref(disp: jax.Array) -> jax.Array:
    h, w = disp.shape
    padded = jnp.pad(disp, 1, mode="edge")
    return ref.median3x3_rows_ref(
        padded[0:h, :], padded[1 : h + 1, :], padded[2 : h + 2, :]
    )


def _dense_tiled_ref(*args, **kwargs):
    """Row-tiled XLA fallback (late import: core.dense builds on kernels)."""
    from repro.core.dense import dense_match_tiled_xla

    return dense_match_tiled_xla(*args, **kwargs)


def _dense_stream_ref(*args, **kwargs):
    """Streaming gather-free dense path (late import: core builds on kernels)."""
    from repro.core.dense import dense_match_stream_xla

    return dense_match_stream_xla(*args, **kwargs)


def _support_tiled_ref(*args, **kwargs):
    """Row-block-tiled XLA fallback (late import: core builds on kernels)."""
    from repro.core.support import support_match_tiled_xla

    return support_match_tiled_xla(*args, **kwargs)


register_backend(KernelBackend(
    name="ref",
    sobel=_sobel_ref,
    support_match=ref.support_match_rows_streaming,
    dense_match=ref.dense_match_rows_streaming,
    median3x3=_median3x3_ref,
    dense_match_tiled=_dense_tiled_ref,
    support_match_tiled=_support_tiled_ref,
    dense_match_stream=_dense_stream_ref,
    tiling=TileCapability(
        tiled_dense=True, batched_map=True, default_rows=64,
        tiled_support=True, support_default_rows=8,
        default_gather="stream",   # gather-free scan: fastest under XLA too
        default_precision="int8",  # int16 SAD: exact, ~1.5x on AVX lanes
    ),
    description="pure-jnp streaming-scan math (XLA:CPU friendly)",
))


# ------------------------------------------------------------ pallas backends
def _pallas_backend(name: str, interpret: bool, description: str) -> KernelBackend:
    def dense_tiled(*args, tile_rows: int, **kwargs):
        return dense_match_pallas(
            *args, block_rows=tile_rows, interpret=interpret, **kwargs
        )

    def dense_stream(*args, tile_rows: int, **kwargs):
        return dense_match_stream_pallas(
            *args, block_rows=tile_rows, interpret=interpret, **kwargs
        )

    def support_tiled(*args, tile_rows: int, **kwargs):
        return support_match_pallas(
            *args, block_rows=tile_rows, interpret=interpret, **kwargs
        )

    return KernelBackend(
        name=name,
        sobel=functools.partial(sobel_pallas, interpret=interpret),
        support_match=functools.partial(support_match_pallas, interpret=interpret),
        dense_match=functools.partial(dense_match_pallas, interpret=interpret),
        median3x3=functools.partial(median3x3_pallas, interpret=interpret),
        dense_match_tiled=dense_tiled,
        support_match_tiled=support_tiled,
        dense_match_stream=dense_stream,
        tiling=TileCapability(
            # 8-row blocks: the f32 sublane tile (Mosaic refuses 4-row maps)
            tiled_dense=True, default_rows=8, max_rows=64,
            tiled_support=True, support_default_rows=8, support_max_rows=64,
            default_gather="stream",   # the kernel Mosaic lowers
            default_precision="int8",  # the kernels accumulate int32 either way
        ),
        description=description,
    )


register_backend(_pallas_backend(
    "pallas", interpret=True,
    description="Pallas kernels, interpret mode (CPU correctness)",
))
register_backend(_pallas_backend(
    "pallas_tpu", interpret=False,
    description="Pallas kernels compiled for TPU",
))


# -------------------------------------------------------------- public wrappers
@functools.partial(jax.jit, static_argnames=("backend",))
def sobel(image: jax.Array, backend: Backend = None) -> tuple[jax.Array, jax.Array]:
    return get_backend(resolve_backend(backend)).sobel(image)


@functools.partial(jax.jit, static_argnames=("p", "backend", "tile"))
def support_match(
    desc_l_rows: jax.Array,
    desc_r_rows: jax.Array,
    p: ElasParams,
    backend: Backend = None,
    tile: TileArg = None,
) -> jax.Array:
    """Support search over candidate descriptor rows.

    ``backend=None`` resolves to the device default and ``tile=None`` to
    the resolved backend's default tile (``UNTILED`` forces the untiled
    path).  A tile dispatches to the backend's declared row-block-tiled
    support entry point (clamped to the backend's capability); backends
    without tiled support run their untiled path -- the output is bitwise
    identical either way.
    """
    backend, tile = resolve_dispatch(backend, tile)
    be = get_backend(backend)
    kwargs = dict(
        num_disp=p.num_disp,
        step=p.candidate_step,
        offset=p.candidate_step // 2,
        support_texture=p.support_texture,
        support_ratio=p.support_ratio,
        lr_threshold=p.lr_threshold,
        disp_min=p.disp_min,
    )
    rows = be.tiling.clamp_support(tile)
    if rows is not None:
        return be.support_match_tiled(
            desc_l_rows, desc_r_rows, tile_rows=rows, **kwargs
        )
    return be.support_match(desc_l_rows, desc_r_rows, **kwargs)


@functools.partial(jax.jit, static_argnames=("p", "backend", "tile"))
def dense_match_candidates(
    desc_l: jax.Array,
    desc_r: jax.Array,
    mu_l: jax.Array,
    mu_r: jax.Array,
    cand_l: jax.Array,
    cand_r: jax.Array,
    p: ElasParams,
    backend: Backend = None,
    tile: TileArg = None,
) -> tuple[jax.Array, jax.Array]:
    """Dense matching from pre-built candidate tensors.

    ``backend``/``tile`` resolve as in :func:`support_match`.  A tile
    dispatches to the backend's declared row-tiled dense entry point
    (clamped to the backend's capability) with the tile's ``gather``
    formulation; backends without tiling support run their untiled path
    -- the output is bitwise identical either way.
    """
    backend, tile = resolve_dispatch(backend, tile)
    be = get_backend(backend)
    kwargs = dict(
        num_disp=p.num_disp,
        beta=p.beta,
        gamma=p.gamma,
        sigma=p.sigma,
        match_texture=p.match_texture,
    )
    eff = be.tiling.clamp(tile)
    if eff is not None:
        gather = eff.gather
        if gather == "stream":
            # The streaming scan consumes grid bitmasks, not the candidate
            # tensors this entry is given (dense_both_views routes stream
            # requests to dense_match_stream before candidates exist).
            # For pre-built candidates the windowed "slice" sweep is the
            # bitwise-identical O(1)-in-D formulation.
            gather = "slice"
        return be.dense_match_tiled(
            desc_l, desc_r, mu_l, mu_r, cand_l, cand_r,
            tile_rows=eff.rows, gather_impl=gather,
            disp_min=p.disp_min, **kwargs,
        )
    return be.dense_match(
        desc_l, desc_r, mu_l, mu_r, cand_l, cand_r,
        disp_min=p.disp_min, **kwargs,
    )


# Historical public name; the candidate tensors are always pre-built by
# the caller, so the two entry points are one function.
dense_match = dense_match_candidates


@functools.partial(jax.jit, static_argnames=("p", "backend", "tile"))
def dense_match_stream(
    desc_l: jax.Array,          # (H, W, 16) or (B, H, W, 16) int8
    desc_r: jax.Array,
    mu_l: jax.Array,            # (H, W) or (B, H, W) float32
    mu_r: jax.Array,
    gmask_l: jax.Array,         # (H, CW, D) or (B, H, CW, D) bool
    gmask_r: jax.Array,
    p: ElasParams,
    backend: Backend = None,
    tile: TileArg = None,
) -> tuple[jax.Array, jax.Array]:
    """Gather-free streaming dense matching from per-cell candidate bitmasks.

    The candidate set never becomes a tensor: ``gmask`` is the grid-vector
    bitmask (:func:`repro.core.dense.candidate_bitmask_rows`) and the
    plane-prior band is derived from ``mu`` inside the scan.  ``backend``
    / ``tile`` resolve as in :func:`dense_match_candidates`; the tile's
    ``rows`` and ``precision`` reach the backend's streaming entry (an
    :data:`~repro.core.tiling.UNTILED` request runs one full-height
    block).  Accepts single frames or a leading batch axis; a backend
    without ``batched_map`` is vmapped per frame.
    """
    backend, tile = resolve_dispatch(backend, tile)
    be = get_backend(backend)
    if be.dense_match_stream is None:
        raise ValueError(
            f"backend {backend!r} has no streaming dense entry "
            f"(dense_match_stream); use a windowed gather TileSpec instead"
        )
    eff = be.tiling.clamp(tile)
    rows = eff.rows if eff is not None else desc_l.shape[-3]
    precision = (
        eff.precision if eff is not None
        else tile.precision if isinstance(tile, TileSpec) else "f32"
    )
    kwargs = dict(
        num_disp=p.num_disp,
        disp_min=p.disp_min,
        plane_radius=p.plane_radius,
        cell_px=p.grid_size,
        beta=p.beta,
        gamma=p.gamma,
        sigma=p.sigma,
        match_texture=p.match_texture,
        tile_rows=rows,
        precision=precision,
    )
    if desc_l.ndim == 4 and not be.tiling.batched_map:
        per_frame = lambda *a: be.dense_match_stream(*a, **kwargs)  # noqa: E731
        return jax.vmap(per_frame)(desc_l, desc_r, mu_l, mu_r, gmask_l, gmask_r)
    return be.dense_match_stream(
        desc_l, desc_r, mu_l, mu_r, gmask_l, gmask_r, **kwargs
    )


@functools.partial(jax.jit, static_argnames=("backend",))
def median3x3(disp: jax.Array, backend: Backend = None) -> jax.Array:
    return get_backend(resolve_backend(backend)).median3x3(disp)
