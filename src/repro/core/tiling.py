"""Row-tile specifications for the dense-matching AND support stages.

The iELAS FPGA keeps the matching working sets on-chip with line-buffered
tiling and ping-pong BRAMs; the software analogue is to process the image
in fixed-height row tiles whose intermediates fit the per-core cache
instead of materialising a full ``(B, H, W, D)`` cost volume.  Neither
dense matching nor the support-point search has cross-row data
dependencies (the cost volume is built row by row), so any row tiling is
*bitwise* equivalent to the untiled computation -- tiling is purely a
memory-locality decision.

Two small types live here:

* :class:`TileSpec` -- how a caller wants the stages tiled: ``rows`` image
  rows per dense tile, optionally ``support_rows`` candidate-grid rows
  per support block (defaulting to ``rows``), and ``gather`` -- which
  formulation the tiled dense stage uses for its per-pixel candidate
  lookup (see :data:`GATHER_IMPLS`).  Frozen and hashable so it can
  travel through ``jax.jit`` as a static argument alongside
  ``ElasParams``.
* :class:`TileCapability` -- what a kernel backend *declares* it can do
  (see :mod:`repro.kernels.registry`), per stage: ``tiled_dense`` /
  ``tiled_support`` entry points, preferred and maximum block heights,
  whether the tiled entries natively walk a flat batch x block grid
  (``batched_map``), and the gather formulation the backend's compiler
  prefers (``default_gather``).  Callers consult it to pick between the
  backend's tiled entry point, a batched ``lax.map`` fallback, and the
  plain untiled path.

``tile=None`` at the public entry points no longer means "untiled": it
resolves through :meth:`TileCapability.resolve` to the backend's
:meth:`TileCapability.default_tile`.  Tiling is bitwise invisible, so the
resolved default only changes memory locality, never output.  Callers who
really want the untiled volume-free streaming path pass the explicit
:data:`UNTILED` sentinel (a plain string, so it stays a valid jit-static
argument).

This module is dependency-free (stdlib only) so the kernel registry can
import it without pulling in the rest of the core package.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Union

#: The candidate-*gather* formulations of the windowed dense path (all
#: bitwise identical; each fetches the per-pixel candidate descriptors from
#: a pre-built ``(.., W, C)`` candidate tensor):
#:
#: ``"take"``
#:     ``jnp.take_along_axis`` along the row axis -- the XLA-native gather;
#:     a data-dependent gather Mosaic cannot lower.
#: ``"onehot"``
#:     the gather as a one-hot matmul over the row axis -- MXU-friendly,
#:     gather-free.
#: ``"slice"``
#:     windowed ``lax.dynamic_slice`` sweep over the disparity axis with a
#:     compare-and-select per candidate slot -- shifted slices only, with
#:     an O(1)-in-D jaxpr.
WINDOWED_GATHERS = ("take", "onehot", "slice")

#: All dense-stage candidate-evaluation formulations a ``TileSpec`` may
#: request.  On top of the three windowed gathers, ``"stream"`` is the
#: gather-free streaming scan (the default everywhere): one ``lax.scan``
#: over the disparity axis computes a shifted-slice SAD row for ALL pixels
#: per step and folds it into running ``(best energy, best d)`` registers
#: under a cheap per-step candidate mask (the grid-vector bitmask upsampled
#: per grid cell OR a ``|d - round(mu)| <= plane_radius`` band around the
#: plane prior) -- no candidate tensor, no gather, O(W x rows) live set.
#: Every formulation is bitwise identical to the others.
GATHER_IMPLS = WINDOWED_GATHERS + ("stream",)

#: Dense-stage SAD arithmetic precisions (bitwise identical -- see
#: :class:`TileSpec`):
#:
#: ``"f32"``
#:     the reference arithmetic: descriptors widened to int32 for the SAD,
#:     energies in float32.
#: ``"int8"``
#:     the low-precision datapath: descriptors stay int8 and the SAD
#:     accumulates in int16 (exact -- the 16-sample SAD is bounded by
#:     16 * 255 = 4080 < 2^15) before the float32 energy.  Bitwise
#:     identical outputs by construction.  The XLA scan honours it; the
#:     Pallas kernels accumulate in int32 for either value.
PRECISION_IMPLS = ("f32", "int8")

#: Explicit "run the untiled path" request, now that ``tile=None`` resolves
#: to the backend's default tile.  A string so it remains hashable and
#: jit-static wherever a TileSpec is accepted.
UNTILED = "untiled"

#: What the public entry points accept for their ``tile`` argument.
TileArg = Union["TileSpec", None, str]


@dataclasses.dataclass(frozen=True)
class TileSpec:
    """How to tile the matching stages.

    ``rows`` is the dense-stage tile height in image rows;
    ``support_rows`` is the support-stage block height in *candidate-grid*
    rows (one grid row per ``candidate_step`` image rows) and defaults to
    ``rows`` when unset.  Both must be positive; the last tile of an
    extent that is not a multiple of the tile height is padded and cropped
    (a partial tile), so odd sizes need no special handling by callers.
    ``gather`` picks the dense stage's candidate-evaluation formulation
    (one of :data:`GATHER_IMPLS`; ``"stream"`` is the gather-free scan
    over the disparity axis) and ``precision`` its SAD arithmetic (one of
    :data:`PRECISION_IMPLS`); all combinations are bitwise identical, so
    like the tile heights they are purely lowering/locality decisions.
    """

    rows: int = 16
    support_rows: Optional[int] = None
    gather: str = "take"
    precision: str = "f32"

    def __post_init__(self):
        if self.rows < 1:
            raise ValueError(f"tile rows must be >= 1, got {self.rows}")
        if self.support_rows is not None and self.support_rows < 1:
            raise ValueError(
                f"support tile rows must be >= 1, got {self.support_rows}"
            )
        if self.gather not in GATHER_IMPLS:
            raise ValueError(
                f"gather must be one of {GATHER_IMPLS}, got {self.gather!r}"
            )
        if self.precision not in PRECISION_IMPLS:
            raise ValueError(
                f"precision must be one of {PRECISION_IMPLS}, "
                f"got {self.precision!r}"
            )

    @property
    def support_block_rows(self) -> int:
        """Support-stage block height (grid rows); falls back to ``rows``."""
        return self.rows if self.support_rows is None else self.support_rows

    def num_tiles(self, height: int) -> int:
        """Tiles covering ``height`` rows (the last one possibly partial)."""
        return -(-height // self.rows)

    def padded_height(self, height: int) -> int:
        """``height`` rounded up to a whole number of tiles."""
        return self.num_tiles(height) * self.rows

    @classmethod
    def for_cache(
        cls,
        width: int,
        num_candidates: int,
        budget_bytes: int = 1 << 21,
        max_rows: int = 64,
    ) -> "TileSpec":
        """Pick a tile height whose candidate-energy working set
        (``rows * width * num_candidates`` f32 + the int32 SAD of the same
        shape) stays under ``budget_bytes`` (default 2 MiB, a typical
        per-core L2)."""
        per_row = max(1, width * num_candidates * 8)
        rows = max(1, min(max_rows, budget_bytes // per_row))
        return cls(rows=rows)


@dataclasses.dataclass(frozen=True)
class TileCapability:
    """A kernel backend's declared per-stage tiling support.

    ``tiled_dense``
        the backend has a row-tiled dense entry point (``dense_match_tiled``
        in the registry) accepting ``tile_rows=``.
    ``tiled_support``
        the backend has a row-block-tiled support entry point
        (``support_match_tiled`` in the registry) accepting ``tile_rows=``
        in candidate-grid rows.
    ``batched_map``
        the tiled entry points natively accept a leading batch axis and
        walk the flat batch x block grid themselves (the ``lax.map``
        fallback); when False, batched callers ``vmap`` the per-frame
        tiled call instead.
    ``default_rows`` / ``max_rows``
        the dense tile height the backend prefers, and an optional hard
        cap (e.g. a VMEM bound for a compiled kernel).
    ``support_default_rows`` / ``support_max_rows``
        the same pair for the support stage, in candidate-grid rows.
    ``default_gather``
        the candidate-evaluation formulation the backend's compiler
        prefers (one of :data:`GATHER_IMPLS`); used when a resolved
        default tile is built and as documentation of what the backend
        can lower.
    ``default_precision``
        the dense-stage SAD arithmetic the backend prefers (one of
        :data:`PRECISION_IMPLS`); ``"int8"`` keeps the descriptor
        datapath narrow on backends whose vector units reward it.
    """

    tiled_dense: bool = False
    batched_map: bool = False
    default_rows: int = 16
    max_rows: Optional[int] = None
    tiled_support: bool = False
    support_default_rows: int = 16
    support_max_rows: Optional[int] = None
    default_gather: str = "take"
    default_precision: str = "f32"

    def __post_init__(self):
        if self.default_gather not in GATHER_IMPLS:
            raise ValueError(
                f"default_gather must be one of {GATHER_IMPLS}, "
                f"got {self.default_gather!r}"
            )
        if self.default_precision not in PRECISION_IMPLS:
            raise ValueError(
                f"default_precision must be one of {PRECISION_IMPLS}, "
                f"got {self.default_precision!r}"
            )

    def clamp(self, tile: TileArg) -> Optional[TileSpec]:
        """Fit a requested spec to this capability (None if unsupported).

        ``None`` and the :data:`UNTILED` sentinel both mean "no tiling"
        here: clamp sits at the consumption end of the dispatch chain,
        after :meth:`resolve` has already made the untiled/tiled choice.
        """
        if not isinstance(tile, TileSpec) or not self.tiled_dense:
            return None
        if self.max_rows is not None and tile.rows > self.max_rows:
            return dataclasses.replace(tile, rows=self.max_rows)
        return tile

    def clamp_support(self, tile: TileArg) -> Optional[int]:
        """Effective support block height (grid rows) for a requested spec,
        or None when the caller asked for no tiling (``None`` / the
        :data:`UNTILED` sentinel) or the backend has no tiled support
        entry."""
        if not isinstance(tile, TileSpec) or not self.tiled_support:
            return None
        rows = tile.support_block_rows
        if self.support_max_rows is not None:
            rows = min(rows, self.support_max_rows)
        return rows

    def default_tile(self) -> Optional[TileSpec]:
        """The TileSpec this backend prefers (None if it cannot tile)."""
        if not self.tiled_dense:
            return None
        return TileSpec(
            rows=self.default_rows,
            support_rows=self.support_default_rows if self.tiled_support else None,
            gather=self.default_gather,
            precision=self.default_precision,
        )

    def resolve(self, tile: TileArg) -> Union[TileSpec, str]:
        """Resolve a caller's ``tile`` argument against this capability.

        ``None`` (the everywhere-default) resolves to
        :meth:`default_tile` (or :data:`UNTILED` for a backend with no
        tiled dense entry); the explicit :data:`UNTILED` sentinel and a
        concrete :class:`TileSpec` pass through unchanged.  The resolved
        domain therefore never contains ``None``: an explicit untiled
        request stays :data:`UNTILED` through every nested pipeline
        layer instead of being mistaken for "unspecified" and re-resolved
        to the default tile.  Idempotent, so the stages can resolve at
        every layer without drift; :meth:`clamp` / :meth:`clamp_support`
        map :data:`UNTILED` to the untiled path at the consumption end.
        """
        if tile is None:
            default = self.default_tile()
            return default if default is not None else UNTILED
        if isinstance(tile, str):
            if tile != UNTILED:
                raise ValueError(
                    f"tile must be a TileSpec, None, or {UNTILED!r}; "
                    f"got {tile!r}"
                )
            return UNTILED
        return tile
