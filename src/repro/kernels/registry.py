"""Kernel backend registry.

Historically the backend choice travelled through the stereo stack as a bare
string compared against literals inside every wrapper (``if backend ==
"ref": ...``).  The registry replaces that string-threading with a first-class
object: a :class:`KernelBackend` bundles one implementation of each compute
hot spot (sobel, support match, dense match, median), and call sites resolve
the name exactly once via :func:`get_backend`.

The *name* remains the unit that crosses jit boundaries — strings are
hashable and stable, so ``backend: str`` stays a ``static_argnames`` entry —
but dispatch inside the traced function is a registry lookup, not an if/elif
ladder.  Adding a backend (e.g. a future Mosaic or GPU variant) is a single
:func:`register_backend` call; every wrapper, pipeline stage, and the serving
engine picks it up with no further edits.

Built-in backends (registered by :mod:`repro.kernels.ops` on import):

* ``ref``         -- pure-jnp oracle math (default on CPU/GPU).
* ``pallas``      -- Pallas kernels in interpret mode (correctness on CPU).
* ``pallas_tpu``  -- Pallas kernels compiled for TPU (default on TPU).

Dispatch is *device-aware*: call sites that pass ``backend=None`` resolve
it through :func:`default_backend`, which probes ``jax.default_backend()``
and picks the registered backend that compiles natively for the platform;
:func:`resolve_dispatch` additionally resolves a ``tile=None`` request to
the chosen backend's :meth:`~repro.core.tiling.TileCapability.default_tile`
so the tiled, Mosaic-ready kernel paths are the default everywhere without
any call site hard-coding a backend or tile shape.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Callable, Dict, Optional, Tuple

from repro.core.tiling import TileArg, TileCapability, TileSpec


@dataclasses.dataclass(frozen=True)
class KernelBackend:
    """One implementation of each iELAS compute hot spot.

    The callables use keyword-exploded algorithm parameters (not
    ``ElasParams``) so each backend stays importable without the core
    algorithm modules and trivially testable against the others.

    Every backend also *declares its tiling capability*: ``tiling`` says
    whether (and how) the backend can run the dense and support stages in
    row tiles / row blocks, and ``dense_match_tiled`` /
    ``support_match_tiled`` -- when declared -- are the tiled entry points
    (same signatures as the untiled ops plus ``tile_rows=``).
    ``dense_match_stream`` is the gather-free streaming dense entry
    (candidate bitmasks + plane-prior band instead of candidate tensors;
    see :func:`repro.kernels.ref.dense_match_rows_stream_ref`) -- required
    whenever the capability's ``default_gather`` is ``"stream"``.  Callers
    pick the path through :class:`~repro.core.tiling.TileCapability`
    rather than hard-coding backend names.
    """

    name: str
    sobel: Callable            # (image) -> (gx, gy)
    support_match: Callable    # (desc_l_rows, desc_r_rows, **kw) -> grid
    dense_match: Callable      # (dl, dr, mu_l, mu_r, cand_l, cand_r, **kw)
    median3x3: Callable        # (disp) -> disp
    dense_match_tiled: Optional[Callable] = None   # (..., tile_rows=, **kw)
    support_match_tiled: Optional[Callable] = None  # (..., tile_rows=, **kw)
    dense_match_stream: Optional[Callable] = None  # (dl, dr, mu_l, mu_r,
    #                                  gmask_l, gmask_r, tile_rows=, **kw)
    tiling: TileCapability = TileCapability()
    description: str = ""

    def __post_init__(self):
        if not self.name:
            raise ValueError("backend name must be non-empty")
        if self.tiling.tiled_dense and self.dense_match_tiled is None:
            raise ValueError(
                f"backend {self.name!r} declares tiled_dense but provides "
                f"no dense_match_tiled callable"
            )
        if self.tiling.tiled_support and self.support_match_tiled is None:
            raise ValueError(
                f"backend {self.name!r} declares tiled_support but provides "
                f"no support_match_tiled callable"
            )
        if self.tiling.default_gather == "stream" and self.dense_match_stream is None:
            raise ValueError(
                f"backend {self.name!r} defaults to the 'stream' gather but "
                f"provides no dense_match_stream callable"
            )


_REGISTRY: Dict[str, KernelBackend] = {}


def register_backend(backend: KernelBackend, *, overwrite: bool = False) -> KernelBackend:
    """Add a backend to the registry; ``overwrite=True`` replaces an entry."""
    if backend.name in _REGISTRY and not overwrite:
        raise ValueError(
            f"kernel backend {backend.name!r} already registered "
            f"(pass overwrite=True to replace)"
        )
    _REGISTRY[backend.name] = backend
    return backend


def get_backend(name: str) -> KernelBackend:
    """Resolve a backend name; raises with the available names on a miss."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown kernel backend {name!r}; available: {available_backends()}"
        ) from None


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def default_backend() -> str:
    """The kernel backend for the current device, by platform probe.

    Resolution order:

    1. The ``IELAS_BACKEND`` environment variable, when set to a
       registered name -- the operational escape hatch (e.g. force
       ``pallas`` to run the kernel bodies in interpret mode on CPU, or
       pin ``ref`` on a TPU host while debugging a Mosaic lowering).
    2. ``jax.default_backend() == "tpu"`` -> ``pallas_tpu``: the support
       and streaming dense kernels compiled by Mosaic (the gather-free
       ``"stream"`` scan their capability declares as ``default_gather``).
       Nothing falls back at run time: a kernel that fails to compile
       fails the call.
    3. Anything else (``cpu``, ``gpu``) -> ``ref``: the pure-jnp
       streaming-scan formulation, which XLA compiles natively everywhere
       (interpret-mode Pallas is a correctness harness, never a
       performance default).

    Call sites pass ``backend=None`` and let :func:`resolve_dispatch`
    apply this probe exactly once per entry; the resolved *name* is what
    crosses jit boundaries, so device-aware dispatch adds no trace-time
    work.
    """
    forced = os.environ.get("IELAS_BACKEND")
    if forced:
        if forced not in _REGISTRY:
            raise KeyError(
                f"IELAS_BACKEND={forced!r} is not a registered backend; "
                f"available: {available_backends()}"
            )
        return forced
    import jax  # deferred: keep the registry importable without a device

    if jax.default_backend() == "tpu" and "pallas_tpu" in _REGISTRY:
        return "pallas_tpu"
    return "ref"


def resolve_backend(name: Optional[str]) -> str:
    """A concrete backend name: ``name`` itself, or the device default."""
    return name if name is not None else default_backend()


def resolve_dispatch(backend: Optional[str], tile: TileArg) -> Tuple[str, TileArg]:
    """Resolve a call site's ``(backend, tile)`` pair to concrete values.

    ``backend=None`` becomes :func:`default_backend`; ``tile=None``
    becomes the resolved backend's
    :meth:`~repro.core.tiling.TileCapability.default_tile`.  The explicit
    :data:`~repro.core.tiling.UNTILED` sentinel passes through AS the
    sentinel (never ``None``), so an untiled request survives every
    nested resolution instead of being re-defaulted.  Idempotent:
    concrete inputs pass through unchanged, so every pipeline layer may
    resolve defensively.
    """
    name = resolve_backend(backend)
    return name, get_backend(name).tiling.resolve(tile)
