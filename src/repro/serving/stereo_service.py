"""Continuous-batching stereo serving engine.

The FPGA design overlaps frame i's compute with frame i+1's arrival via
ping-pong BRAMs (paper Fig. 7), and the regularized interpolation step makes
the whole frame one static program.  This module is the service-level
generalisation of both ideas for many concurrent streams:

* **Dynamic wave assembly** -- requests from any number of streams are
  grouped into *waves* of up to ``batch`` frames.  A partial wave is padded
  (slots replicate a real frame) and masked at emit time rather than
  stalled, so a single slow stream never blocks the others.  Within a
  resolution bucket, wave order is submission order, so each stream's
  results come back in the order it submitted them; with ``in_order=True``
  a per-stream reordering buffer extends that guarantee ACROSS buckets
  (delivery deferred, wave assembly untouched).

* **Frame-program cache** -- compiled wave programs are cached per
  ``(H, W, batch, backend, params)``; with ``bucket > 1`` resolutions are
  rounded up to bucket multiples (inputs edge-padded, outputs cropped) so
  mixed-resolution traffic collapses onto a few programs.  ``warmup()``
  pre-compiles; :class:`ServiceStats` reports hits/misses, so "zero
  recompiles after warm-up" is an assertable property.

* **Per-bucket auto-batching** -- with ``autobatch=True``, ``warmup()``
  first benchmarks candidate wave widths per resolution bucket on dummy
  frames and records the per-frame-fastest width; wave assembly then uses
  that width for the bucket.  Wide waves win at small resolutions but lose
  once per-frame intermediates outgrow per-core cache, so the right width
  is resolution-dependent -- and with a ``tile``
  (:class:`~repro.core.tiling.TileSpec`) the dense stage runs the flat
  batch x row-tile grid one tile at a time, moving that crossover far to
  the right (see ROADMAP "Tiled dense stage").

* **Staged async pipeline** -- ingest/assembly, the support stage
  (descriptors + sparse support + the paper's interpolation), the dense
  stage (prior + dense matching + post-processing) and emit each run on
  their own thread connected by bounded queues of depth ``depth``.  Host
  ingest of wave i+1 overlaps device compute of wave i -- the ping-pong
  BRAM, at wave granularity.  The stage seam is the public API of
  :mod:`repro.core.pipeline` (``ielas_support_stage`` /
  ``ielas_interpolate_stage`` / ``ielas_dense_stage``), the same module
  boundary as the paper's Fig. 3 subsystems.

* **Accounting** -- per-request latency, wave occupancy, backpressure time
  spent blocked in ``submit()``, program-cache counters, compiles on the
  serving path, admission / containment counters and per-stage liveness,
  snapshotted by :meth:`StereoService.stats`.

* **Tracing** (:mod:`repro.serving.tracing`) -- every unit of work runs in
  a ``stereo.*`` span: a profiler annotation carrying the wave index plus
  ``time.monotonic()`` stamps.  The stamps become each delivered frame's
  :class:`~repro.serving.tracing.FrameTiming`, whose parts (host work,
  program runs, waits) sum to its ``latency_s``.

The split wave programs produce *bitwise identical* output to the fused
single-frame :func:`~repro.core.pipeline.ielas_disparity` program (pinned by
tests/test_stereo_serving.py), so batching is purely a throughput decision.

Failure model
-------------
The paper's consumers (robot navigation, autonomous vehicles) are
hard-real-time: the engine must keep producing frames under transient
faults and load spikes instead of dying on the first exception.  The
containment rules (proved by ``tests/test_serving_faults.py`` via the
:mod:`repro.serving.faults` injection harness):

* **What fails a frame** -- an exception while executing a wave's support
  or dense program fails *only that wave's frames*: the wave is retried
  once as single-frame fallback waves (batch-1 programs, compiled on the
  cold path), so a transient fault recovers completely and a *poison
  frame* -- one whose retry fails again -- is quarantined alone while its
  wave-mates recover.  Failed frames are delivered on the normal result
  path as :class:`CompletedFrame` with ``error`` set (``disparity=None``);
  ``collect`` / ``results`` / ``run_stream`` surface them, and with
  ``in_order=True`` they advance the stream's sequence like any other
  delivery, so later frames are never held behind a dead one.  Requests
  whose ``deadline`` passed before compute are shed at wave assembly the
  same way (error frames, ``shed``/``expired`` counters) without spending
  device time.

* **What fails the engine** -- only *systemic* failure: ``max_wave_failures``
  CONSECUTIVE waves failing completely (no slot recovered) aborts the
  engine, stores the error, and every later ``submit``/``stop`` re-raises
  it.  Any recovered slot resets the count.

* **Degraded mode** -- with ``degrade_watermark`` set, an assembly backlog
  past the watermark switches new waves to a dense program with the
  plane-prior band narrowed to ``degraded_band`` (the streaming scan's
  cost is linear in band width -- a real quality-for-latency knob);
  full quality returns once the backlog falls below ``clear_watermark``
  (hysteresis).  The non-degraded path is bitwise untouched -- golden-frame
  conformance is pinned against exactly that path.

* **Liveness** -- every stage thread beats a
  :class:`~repro.runtime.fault_tolerance.HeartbeatMonitor` once per queue
  poll, so ``stats()`` reports per-stage liveness, and
  ``stop(drain=True)`` detects a dead/aborted pipeline promptly instead of
  sleeping out its timeout.

* **Temporal warm-start** (``warm_start=True``; proved by
  tests/test_warm_start.py and the warm cases of the faults suite) --
  per-stream state (the last successfully delivered frame's disparity +
  a block-mean thumbnail of its left image,
  :class:`~repro.serving.warmstart.WarmState`) seeds the next frame:
  warm-classified frames skip the sparse support search (their support
  program is descriptor extraction only) and run a band-only dense scan
  within ``+-warm_band`` of the previous disparity
  (:func:`~repro.core.pipeline.ielas_warm_dense_stage_batched`).  The
  state machine around it:

  - **Classification** happens ONCE, as the frame enters assembly, and
    pins the frame's prior at that instant (a state reset later in
    flight cannot retroactively change an assembled wave).  A frame is
    COLD when warm-start is off, the stream has no state (first frame,
    or the state was reset), the state is not the frame's immediate
    predecessor (``stale_seq``: something between them was lost, shed,
    or reordered), the resolution changed, the warm streak hit
    ``refresh_interval`` (bounded-drift forced refresh), or the
    thumbnail SAD against the previous frame exceeds
    ``scene_change_threshold`` (measured calibration: normal motion ~4
    levels/px, cuts ~30; default threshold 20.0).  Every cold reason
    except "warm-start off / no state" also RESETS the state, so the
    cold frame that follows re-seeds the chain.  Cold frames run the
    bitwise-unchanged cold programs -- the golden-frame conformance
    suite pins first / refresh / post-cut frames of a warm stream
    against the ``warm_start=False`` path.

  - **Warm and cold frames never share a wave** (the wave key carries
    the classification), so a warm wave's programs are uniform and the
    cold path's programs are untouched.

  - **Post-hoc self-check** -- at emit, every warm frame's result is
    scored against the very prior that seeded it
    (:func:`~repro.serving.warmstart.prior_disagreement`, INVALID
    output pixels counting as maximal disagreement); past
    ``rerun_threshold * num_disp`` (healthy warm frames measure <= 3%
    of the range, corrupt-seeded ones >= 33%) the frame is
    retroactively RE-RUN COLD on the single-frame fallback path (batch-1 cold programs -- bitwise
    equal to the cold search) before delivery.  Warm waves keep their
    host frames until emit precisely so this re-run is possible.

  - **State transitions** -- state is written ONLY by a successful
    in-sequence delivery; an error delivery (compute fault after
    retry, admission shed) or an out-of-sequence delivery resets it,
    so a quarantined or shed frame can never seed its successor.  Warm
    state survives the single-frame retry path (the retry slices the
    wave's pinned prior), and degraded mode composes by intersection
    (a degraded warm wave runs band ``min(warm_band, degraded_band)``).

  - ``serving/faults.py`` grows ``stage="warm"`` injection kinds
    (``scene_cut`` / ``corrupt_prior`` / ``stale_state``) so every
    transition above is deterministically testable; ``stats()`` exposes
    ``warm_frames`` / ``cold_frames`` / ``scene_changes`` /
    ``warm_refreshes`` / ``warm_reruns`` / ``warm_resets``.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import queue
import threading
import time
from typing import Callable, Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.params import ElasParams
from repro.core.pipeline import (
    ielas_dense_stage_batched,
    ielas_descriptor_stage_batched,
    ielas_interpolate_stage,
    ielas_support_stage_batched,
    ielas_warm_dense_stage_batched,
)
from repro.core.tiling import TileArg, TileSpec
from repro.kernels.registry import resolve_dispatch
from repro.runtime.fault_tolerance import HeartbeatMonitor
from repro.serving.admission import AdmissionController
from repro.serving.faults import FaultPlan
from repro.serving.tracing import CompileCounter, FrameTiming, span
from repro.serving.warmstart import (
    WarmState,
    frame_thumbnail,
    prior_disagreement,
)
from repro.serving import warmstart as _warmstart

_EOS = object()          # end-of-stream sentinel flowing through the stages

_STAGES = ("assemble", "support", "dense", "emit")


# ---------------------------------------------------------------------------
# public result / stats types
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class CompletedFrame:
    """One finished request, as delivered by :meth:`StereoService.collect`.

    ``error`` is the terminal failure state: ``None`` for a successful
    frame (``disparity`` is the (H, W) float32 map), else a message
    describing why the frame failed (compute fault after retry, or shed
    for a passed deadline) with ``disparity=None``.
    """

    request_id: int
    stream_id: int
    frame_id: int
    disparity: Optional[np.ndarray]    # (H, W) float32, native resolution
    latency_s: float                   # submit() -> emitted
    error: Optional[str] = None        # terminal failure reason, if any
    timing: Optional[FrameTiming] = None   # where latency_s went (ok frames)

    @property
    def ok(self) -> bool:
        return self.error is None


@dataclasses.dataclass(frozen=True)
class ServiceStats:
    """Point-in-time snapshot of the engine's accounting."""

    submitted: int
    completed: int
    dropped: int                   # discarded by stop(drain=False)
    pending: int                   # submitted - completed - dropped - failed - shed
    waves: int
    padded_slots: int              # batch slots filled by padding, not work
    wave_occupancy: float          # real frames / total wave slots
    cache_hits: int
    cache_misses: int              # == wave programs compiled
    programs_cached: int
    backpressure_seconds: float    # total time submit() spent blocked
    latency_avg_ms: float
    latency_p50_ms: float
    latency_p95_ms: float
    latency_max_ms: float
    throughput_fps: float          # completed / (last emit - first submit)
    calibrations: int = 0          # auto-batch calibration passes run
    batch_by_bucket: tuple = ()    # ((H, W), wave width) per calibrated bucket
    backend: str = ""              # RESOLVED kernel backend the waves run on
    tile: Optional[TileSpec] = None  # resolved TileSpec; None == untiled
                                     # (an explicit UNTILED request)
    # ---- fault containment / admission control (PR 6) ----
    shed: int = 0                  # requests shed pre-compute by admission
    expired: int = 0               # subset of shed: deadline already passed
    retried: int = 0               # single-frame retry attempts run
    failed_frames: int = 0         # frames delivered with a compute error
    degraded_waves: int = 0        # waves run with the narrowed prior band
    degraded: bool = False         # current degraded-mode state
    admitted_by_stream: tuple = () # ((stream_id, admitted), ...) fairness view
    shed_by_stream: tuple = ()     # ((stream_id, shed), ...)
    stage_liveness: tuple = ()     # ((stage, alive), ...) from the heartbeat
    compiles_after_warmup: int = 0  # XLA compiles (cache loads included) run
                                    # by the stage threads, not by warmup()
    compiles_by_stage: tuple = ()  # ((stage, compiles), ...) of the above
    # ---- temporal warm-start (PR 10; all zero with warm_start=False) ----
    warm_frames: int = 0           # frames classified warm (band-only scan)
    cold_frames: int = 0           # warm-start frames classified cold
    scene_changes: int = 0         # cold because the thumbnail SAD tripped
    warm_refreshes: int = 0        # cold because the streak hit refresh_interval
    warm_reruns: int = 0           # warm frames re-run cold by the post-hoc check
    warm_resets: int = 0           # state dropped (error/shed/out-of-seq/stale)
    warm_scored: int = 0           # warm frames the post-hoc check scored
    warm_score_sum: float = 0.0    # sum of their scores over the re-run bound
                                   # (rerun_threshold * num_disp)


# ---------------------------------------------------------------------------
# frame-program cache
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class WavePrograms:
    """The compiled halves of one wave-shaped frame program."""

    key: tuple                     # (H, W) bucketed
    batch: int                     # wave width the programs were traced at
    support: object                # (B,H,W)x2 -> (dl, dr, interpolated support)
    dense: object                  # (dl, dr, support) -> (B,H,W) disparity
    dense_degraded: object = None  # same, with the narrowed prior band
                                   # (present only when the cache was built
                                   # with degraded_radius)
    # warm-start variants (present only when the cache was built with
    # warm_band; warm and cold frames never share a wave, so a warm wave
    # runs exactly this pair):
    support_warm: object = None    # (B,H,W)x2 -> (dl, dr): descriptors only,
                                   # no sparse support search
    dense_warm: object = None      # (dl, dr, prior) -> (B,H,W) disparity,
                                   # band-only scan around the prior
    dense_warm_degraded: object = None   # band = min(warm_band, degraded)


class FrameProgramCache:
    """Compiled wave programs keyed on ``(H, W, batch)`` under fixed
    ``(backend, params)``, with optional resolution bucketing and a
    per-bucket wave width.

    With ``bucket > 1`` a request's resolution is rounded up to the next
    bucket multiple, so nearby resolutions share one program (inputs are
    edge-padded on ingest and outputs cropped on emit; with the default
    ``bucket=1`` results are exact).  ``hits``/``misses`` count :meth:`get`
    resolutions; a miss is exactly one new program compilation, so a warmed
    cache serving repeated resolutions shows ``misses == 0``.

    ``batch`` is the *maximum* wave width; :meth:`calibrate` benchmarks
    candidate widths for one bucket on dummy frames and records the
    fastest per-frame width, which :meth:`batch_for` then reports to wave
    assembly (wave batching loses to narrower waves once per-frame
    intermediates outgrow per-core cache, so the best width is
    resolution-dependent).  Programs are cached per ``(shape, width)`` so
    the batch-1 fallback programs the containment retry path compiles
    never evict a bucket's calibrated hot program.  ``tile`` threads a
    :class:`~repro.core.tiling.TileSpec` into BOTH wave programs: the
    dense stage's row tiles and the support stage's row-block streaming
    scan (bitwise identical; a memory-locality decision).  ``backend`` /
    ``tile`` accept None and resolve to the device defaults once, here,
    so every program the cache ever builds shares one concrete dispatch.
    With ``degraded_radius`` set, every program additionally carries a
    ``dense_degraded`` variant whose plane-prior band is narrowed to that
    radius -- the serving engine's overload quality-for-latency knob.
    With ``warm_band`` set, every program additionally carries the
    warm-start pair (``support_warm``: descriptor extraction only;
    ``dense_warm``: the band-only scan seeded by a previous disparity) --
    and, when combined with ``degraded_radius``, a ``dense_warm_degraded``
    variant whose band is the INTERSECTION ``min(warm_band,
    degraded_radius)`` (both narrow the same scan, so overload pressure
    composes with temporal coherence instead of overriding it).
    """

    def __init__(self, params: ElasParams, batch: int,
                 backend: Optional[str] = None, bucket: int = 1,
                 tile: TileArg = None,
                 degraded_radius: Optional[int] = None,
                 warm_band: Optional[int] = None):
        if batch < 1:
            raise ValueError(f"batch must be >= 1, got {batch}")
        if bucket < 1:
            raise ValueError(f"bucket must be >= 1, got {bucket}")
        if degraded_radius is not None and degraded_radius < 0:
            raise ValueError(
                f"degraded_radius must be >= 0 or None, got {degraded_radius}"
            )
        if warm_band is not None and warm_band < 0:
            raise ValueError(
                f"warm_band must be >= 0 or None, got {warm_band}"
            )
        self.params = params
        self.batch = batch
        # Resolve the device-aware defaults exactly once, at construction:
        # every wave program is then built from the concrete pair, so the
        # probe can never introduce a hot-path retrace.
        self.backend, self.tile = resolve_dispatch(backend, tile)
        self.bucket = bucket
        self.degraded_radius = degraded_radius
        self.warm_band = warm_band
        self.hits = 0
        self.misses = 0
        self.calibrations = 0
        self._lock = threading.Lock()
        self._programs: dict[tuple, WavePrograms] = {}   # (key, batch) ->
        self._batch_choice: dict[tuple, int] = {}

    def bucket_shape(self, h: int, w: int) -> tuple[int, int]:
        b = self.bucket
        return (math.ceil(h / b) * b, math.ceil(w / b) * b)

    def batch_for(self, h: int, w: int) -> int:
        """Wave width for a *bucketed* shape (calibrated, or the default)."""
        return self._batch_choice.get((h, w), self.batch)

    def batch_choices(self) -> tuple:
        with self._lock:
            return tuple(sorted(self._batch_choice.items()))

    def __len__(self) -> int:
        return len(self._programs)

    def get(self, h: int, w: int, batch: Optional[int] = None) -> WavePrograms:
        """Resolve the wave program for a *bucketed* shape at the given
        wave width, compiling on miss.

        ``batch`` is the wave width the caller actually assembled; a cached
        program traced at a different width would silently retrace inside
        jit, so each width gets its own cache entry (the batch-1 fallback
        programs the retry path uses live alongside the calibrated hot
        width instead of evicting it).
        """
        key = (h, w)
        want = batch if batch is not None else self.batch_for(*key)
        with self._lock:
            prog = self._programs.get((key, want))
            if prog is not None:
                self.hits += 1
                return prog
            self.misses += 1
            prog = self._build(key, want)
            self._programs[(key, want)] = prog
            return prog

    def warm(self, h: int, w: int, batch: Optional[int] = None,
             cold_only: bool = False) -> WavePrograms:
        """Pre-compile the program for (h, w) at ``batch`` (default: the
        bucket's wave width) without touching hit/miss counters, and force
        actual XLA compilation with a dummy wave.  ``cold_only`` runs only
        the cold ``support``/``dense`` pair."""
        key = self.bucket_shape(h, w)
        want = batch if batch is not None else self.batch_for(*key)
        with self._lock:
            prog = self._programs.get((key, want))
            if prog is None:
                prog = self._build(key, want)
                self._programs[(key, want)] = prog
        self._run_dummy(prog, cold_only)
        return prog

    def calibrate(self, h: int, w: int,
                  candidates: Optional[Sequence[int]] = None,
                  reps: int = 2) -> int:
        """Benchmark candidate wave widths for (h, w)'s bucket on dummy
        frames; record and return the per-frame-fastest width.

        The winning width's compiled programs are kept, so a calibrated
        warm-up leaves the bucket hot (``misses == 0`` afterwards).
        Idempotent per bucket: repeated calls return the recorded choice.
        """
        key = self.bucket_shape(h, w)
        with self._lock:
            if key in self._batch_choice:
                return self._batch_choice[key]
        if candidates is None:
            candidates = _default_batch_candidates(self.batch)
        best_b, best_t, best_prog = self.batch, float("inf"), None
        for b in candidates:
            b = max(1, min(int(b), self.batch))
            prog = self._build(key, b)
            self._run_dummy(prog)              # compile outside the timing
            t = float("inf")
            for _ in range(max(1, reps)):
                t0 = time.perf_counter()
                self._run_dummy(prog)
                t = min(t, (time.perf_counter() - t0) / b)
            if t < best_t:
                best_b, best_t, best_prog = b, t, prog
        with self._lock:
            self._batch_choice[key] = best_b
            self._programs[(key, best_b)] = best_prog
            self.calibrations += 1
        return best_b

    def _run_dummy(self, prog: WavePrograms, cold_only: bool = False) -> None:
        zeros = jnp.zeros((prog.batch, *prog.key), jnp.float32)
        dl, dr, sup = prog.support(zeros, zeros)
        prog.dense(dl, dr, sup).block_until_ready()
        if cold_only:
            return
        if prog.dense_degraded is not None:
            prog.dense_degraded(dl, dr, sup).block_until_ready()
        if prog.dense_warm is not None:
            wdl, wdr = prog.support_warm(zeros, zeros)
            prior = jnp.zeros((prog.batch, *prog.key), jnp.float32)
            prog.dense_warm(wdl, wdr, prior).block_until_ready()
            if prog.dense_warm_degraded is not None:
                prog.dense_warm_degraded(wdl, wdr, prior).block_until_ready()

    def _build(self, key: tuple, batch: int) -> WavePrograms:
        p, backend, tile = self.params, self.backend, self.tile

        def support_wave(left, right):
            # The wave-shaped support stage: with a tile, the streaming
            # disparity scan walks the flat batch x row-block grid (one
            # O(W)-register block live at a time) at the calibrated wave
            # width, mirroring the dense stage's tiled path.
            dl, dr, sup = ielas_support_stage_batched(
                left, right, p, backend=backend, tile=tile
            )
            return dl, dr, jax.vmap(
                lambda s: ielas_interpolate_stage(s, p)
            )(sup)

        def dense_wave(dl, dr, sup):
            return ielas_dense_stage_batched(
                dl, dr, sup, p, backend=backend, tile=tile
            )

        dense_degraded = None
        if self.degraded_radius is not None:
            radius = self.degraded_radius

            def dense_wave_degraded(dl, dr, sup):
                return ielas_dense_stage_batched(
                    dl, dr, sup, p, backend=backend, tile=tile,
                    band_radius=radius,
                )

            dense_degraded = jax.jit(dense_wave_degraded)

        support_warm = dense_warm = dense_warm_degraded = None
        if self.warm_band is not None:
            band = self.warm_band

            def support_warm_wave(left, right):
                # Warm waves skip the sparse support search entirely: the
                # previous frame's disparity replaces it as the prior, so
                # the support stage reduces to descriptor extraction.
                return ielas_descriptor_stage_batched(left, right)

            def dense_warm_wave(dl, dr, prior):
                return ielas_warm_dense_stage_batched(
                    dl, dr, prior, p, backend=backend, tile=tile,
                    warm_band=band,
                )

            support_warm = jax.jit(support_warm_wave)
            dense_warm = jax.jit(dense_warm_wave)
            if self.degraded_radius is not None:
                dradius = self.degraded_radius

                def dense_warm_degraded_wave(dl, dr, prior):
                    return ielas_warm_dense_stage_batched(
                        dl, dr, prior, p, backend=backend, tile=tile,
                        warm_band=band, band_radius=dradius,
                    )

                dense_warm_degraded = jax.jit(dense_warm_degraded_wave)

        return WavePrograms(
            key=key,
            batch=batch,
            support=jax.jit(support_wave),
            dense=jax.jit(dense_wave),
            dense_degraded=dense_degraded,
            support_warm=support_warm,
            dense_warm=dense_warm,
            dense_warm_degraded=dense_warm_degraded,
        )


def _default_batch_candidates(batch: int) -> tuple:
    """1, 2, 4, ... up to and including ``batch``."""
    cands = []
    b = 1
    while b < batch:
        cands.append(b)
        b *= 2
    cands.append(batch)
    return tuple(cands)


# ---------------------------------------------------------------------------
# internal request / wave records
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _Request:
    request_id: int
    stream_id: int
    frame_id: int
    left: np.ndarray
    right: np.ndarray
    h: int
    w: int
    t_submit: float
    t_enqueued: float = 0.0    # handed to the ingest put
    t_finished: float = 0.0    # reached _finish (in_order hold starts)
    stamps: Optional[dict] = None      # its wave's _Wave.t, set at emit
    seq: int = 0               # per-stream submission sequence (in_order
                               # reordering AND warm-start chain identity)
    deadline: Optional[float] = None   # absolute time.monotonic() budget
    # warm-start classification result, pinned at assembly time:
    warm: bool = False                 # ride a warm (band-only) wave
    prior: Optional[np.ndarray] = None  # (h, w) seed disparity (warm only)
    thumb: Optional[np.ndarray] = None  # left-frame thumbnail (warm_start only)


@dataclasses.dataclass
class _Wave:
    key: tuple                     # bucketed (H, W)
    requests: list                 # valid slots, in submission order
    left: object                   # (B, H, W) device array
    right: object
    index: int = 0                 # global wave-assembly index (fault keys)
    degraded: bool = False         # run the narrowed-band dense program
    warm: bool = False             # run the warm (band-only) programs
    prior: object = None           # (B, H, W) device prior (warm waves only)
    programs: Optional[WavePrograms] = None
    mid: Optional[tuple] = None    # (dl, dr, support) between stages
    disp: object = None
    t: dict = dataclasses.field(default_factory=dict)   # its FrameTiming
                                   # fields: wave index, build/program/emit


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------
class StereoService:
    """Continuous-batching stereo disparity service.

    Parameters
    ----------
    params:      algorithm parameters (jit-static; part of the program key).
    batch:       wave width -- max frames fused into one device program.
    depth:       bound of each inter-stage queue (2 == ping-pong).
    backend:     kernel registry name ("ref" | "pallas" | "pallas_tpu"),
                 or None to probe the device default
                 (:func:`repro.kernels.registry.default_backend`).
    bucket:      resolution bucketing multiple (1 == exact shapes only).
    tile:        TileSpec for the support- and dense-stage wave programs;
                 None resolves to the backend's default tile, the
                 UNTILED sentinel forces the untiled path (tiling is
                 bitwise identical, purely a locality decision).  The
                 resolved choice is exposed as ``service.backend`` /
                 ``service.tile`` and in :meth:`stats`.
    autobatch:   benchmark candidate wave widths per resolution bucket at
                 warmup() time and use the per-frame-fastest width for that
                 bucket's waves (``batch`` remains the upper bound).
    in_order:    per-stream in-order completion.  Waves are assembled per
                 resolution bucket, so by default a later same-bucket
                 request can complete before an earlier other-bucket one
                 (documented: A0, B1, A2 -> A0, A2, B1).  With
                 ``in_order=True`` the emitter holds each finished frame
                 in a per-stream reordering buffer until every earlier
                 submission of the SAME stream has been delivered, so each
                 stream observes strict submission order even across
                 buckets (A0, B1, A2 on one stream -> A0, B1, A2).  Wave
                 assembly is unchanged -- only delivery is deferred, so
                 throughput is untouched and held frames' latency includes
                 the hold time.  Failed and shed frames deliver their
                 sequence slot like any other frame, so a dead frame never
                 blocks its stream.
    wave_linger: how long assembly waits to fill a partial wave before
                 dispatching it padded (seconds).
    max_pending: ingest queue bound; submit() blocks beyond this
                 (the backpressure point, measured in stats).
    fault_plan:  a :class:`~repro.serving.faults.FaultPlan` for
                 deterministic fault injection in the stage loops
                 (testing/chaos engineering; None in production).
    max_wave_failures: consecutive fully-failed waves (no slot recovered
                 by retry) that count as SYSTEMIC failure and abort the
                 engine.  Isolated wave/frame failures never do.
    degrade_watermark: assembly backlog depth that engages degraded mode
                 (None disables it); see ``degraded_band``.
    clear_watermark: backlog depth that clears degraded mode (default:
                 half the degrade watermark; hysteresis).
    degraded_band: plane-prior band half-width for degraded waves (the
                 normal band is ``params.plane_radius``; the streaming
                 dense scan's cost is linear in band width).
    warm_start:  enable temporal warm-start for video streams (see the
                 module docstring's failure-model section): each stream's
                 last successfully delivered frame seeds the next frame's
                 dense search, guarded by the scene-change detector, the
                 prior-integrity state machine, the bounded-drift forced
                 refresh, and the post-hoc disagreement re-run.  Cold
                 frames (including every frame with ``warm_start=False``)
                 run the bitwise-unchanged cold programs.
    warm_band:   disparity band half-width for warm frames -- the scan
                 searches ``prior +- warm_band`` per pixel (cost linear in
                 band width, like ``degraded_band``; the two compose by
                 ``min`` when a warm wave runs degraded).
    scene_change_threshold: thumbnail-SAD score past which a frame is
                 declared a scene cut and runs cold with a state reset.
                 Measured calibration: normal motion scores ~4, cuts ~30.
    refresh_interval: force a cold frame (bounded-drift refresh) after
                 this many consecutive warm frames.
    rerun_threshold: post-hoc disagreement bound as a FRACTION of the
                 disparity range (``num_disp``): a warm result whose
                 :func:`~repro.serving.warmstart.prior_disagreement`
                 against its own seed exceeds ``rerun_threshold *
                 num_disp`` is retroactively re-run cold.  A fraction --
                 not levels -- because the signal is dominated by the
                 INVALID-pixel term, which is weighted ``num_disp``.
                 Measured: healthy warm frames score <= 0.03 of the
                 range, frames seeded by a corrupted prior >= 0.33.
    heartbeat_timeout: stage heartbeat staleness (seconds) after which a
                 stage thread reports dead in :meth:`stats`.
    clock:       monotonic clock for the heartbeat monitor (injectable for
                 fake-clock tests; does not affect latency accounting).
    """

    def __init__(self, params: ElasParams, batch: int = 1, depth: int = 2,
                 backend: Optional[str] = None, bucket: int = 1,
                 tile: TileArg = None, autobatch: bool = False,
                 in_order: bool = False, wave_linger: float = 0.002,
                 max_pending: int = 64,
                 fault_plan: Optional[FaultPlan] = None,
                 max_wave_failures: int = 3,
                 degrade_watermark: Optional[int] = None,
                 clear_watermark: Optional[int] = None,
                 degraded_band: int = 1,
                 warm_start: bool = False,
                 warm_band: int = 8,
                 scene_change_threshold: float = 20.0,
                 refresh_interval: int = 30,
                 rerun_threshold: float = 0.15,
                 heartbeat_timeout: float = 60.0,
                 clock: Callable[[], float] = time.monotonic):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        if max_wave_failures < 1:
            raise ValueError(
                f"max_wave_failures must be >= 1, got {max_wave_failures}"
            )
        if warm_start:
            if warm_band < 0:
                raise ValueError(f"warm_band must be >= 0, got {warm_band}")
            if refresh_interval < 1:
                raise ValueError(
                    f"refresh_interval must be >= 1, got {refresh_interval}"
                )
            if not 0.0 < rerun_threshold <= 1.0:
                raise ValueError(
                    f"rerun_threshold is a fraction of the disparity range "
                    f"in (0, 1], got {rerun_threshold}"
                )
        self.params = params
        self.batch = batch
        self.depth = depth
        self.autobatch = autobatch
        self.in_order = in_order
        self.wave_linger = wave_linger
        self.fault_plan = fault_plan
        self.max_wave_failures = max_wave_failures
        self.warm_start = warm_start
        self.warm_band = warm_band
        self.scene_change_threshold = float(scene_change_threshold)
        self.refresh_interval = refresh_interval
        self.rerun_threshold = float(rerun_threshold)
        self.heartbeat_timeout = heartbeat_timeout
        self._clock = clock
        self._admission = AdmissionController(
            degrade_watermark=degrade_watermark,
            clear_watermark=clear_watermark,
        )
        self._cache = FrameProgramCache(
            params, batch, backend, bucket=bucket, tile=tile,
            degraded_radius=(degraded_band
                             if degrade_watermark is not None else None),
            warm_band=(warm_band if warm_start else None),
        )
        # mirror the cache's resolved dispatch (device-aware defaults)
        self.backend = self._cache.backend
        self.tile = self._cache.tile

        self._ingest: queue.Queue = queue.Queue(maxsize=max_pending)
        self._waves: queue.Queue = queue.Queue(maxsize=depth)
        self._mid: queue.Queue = queue.Queue(maxsize=depth)
        self._ready: queue.Queue = queue.Queue(maxsize=depth)
        self._out: queue.Queue = queue.Queue()

        self._drain = threading.Event()    # finish queued work, then stop
        self._abort = threading.Event()    # stop now, discard queued work
        self._done = threading.Event()     # emitter saw EOS
        self._threads: list[threading.Thread] = []
        self._error: Optional[BaseException] = None
        self._monitor = HeartbeatMonitor(
            hosts=list(_STAGES), timeout=heartbeat_timeout, clock=clock
        )
        self._compiles = CompileCounter()

        # Warm-start lock: guards the per-stream WarmState map and the warm
        # counters.  Touched by assembly (classification), emit (post-hoc
        # re-run accounting) and delivery (state transitions).  Leaf lock:
        # nothing takes _slock or _olock while holding it.
        self._wlock = threading.Lock()
        self._warm_state: dict = {}    # stream_id -> WarmState
        self._warm_frames = 0
        self._cold_frames = 0
        self._scene_changes = 0
        self._warm_refreshes = 0
        self._warm_reruns = 0
        self._warm_resets = 0
        self._warm_scored = 0
        self._warm_score_sum = 0.0

        self._slock = threading.Lock()
        # Ordering lock: guards the in_order reordering state, which is
        # touched by BOTH the emit loop and the assembly loop (shed frames
        # deliver their sequence slot directly from assembly).  Never held
        # while taking _slock's critical sections in reverse -- _deliver
        # takes _slock inside _olock, and nothing takes _olock under _slock
        # while threads run.
        self._olock = threading.Lock()
        self._next_request_id = 0
        self._stream_seq: dict = collections.defaultdict(int)   # next seq to assign
        self._reorder: dict = {}       # stream_id -> {seq: (req, disp, err)}
        self._next_emit: dict = collections.defaultdict(int)    # next seq to deliver
        self._lost_seqs: dict = collections.defaultdict(set)    # never deliverable
        self._inflight: dict = {}      # request_id -> (stream_id, frame_id)
        self._submitted = 0
        self._completed = 0
        self._dropped = 0
        self._failed = 0               # frames delivered with a compute error
        self._shed = 0                 # frames shed pre-compute by admission
        self._retried = 0              # single-frame retry attempts
        self._degraded_waves = 0
        self._consec_wave_failures = 0
        self._waves_built = 0
        self._wave_slots = 0
        self._padded_slots = 0
        self._backpressure_s = 0.0
        self._latencies: collections.deque = collections.deque(maxlen=4096)
        self._lat_sum = 0.0
        self._lat_max = 0.0
        self._t_first_submit: Optional[float] = None
        self._t_last_emit: Optional[float] = None

    # ------------------------------------------------------------ lifecycle
    def start(self) -> "StereoService":
        if self._threads:
            raise RuntimeError("service already started")
        # restart after stop(): reset lifecycle state so the stage loops run.
        # Requests still in the ingest queue are served now; waves stranded in
        # the stage queues by an aborted stop lost their host frames already
        # and stay dropped -- discard them (and any stale _EOS sentinel) so
        # the fresh stage threads don't consume a poisoned pipeline.
        self._drain.clear()
        self._abort.clear()
        self._done.clear()
        self._error = None
        self._consec_wave_failures = 0
        self._monitor = HeartbeatMonitor(
            hosts=list(_STAGES), timeout=self.heartbeat_timeout,
            clock=self._clock,
        )
        for q in (self._waves, self._mid, self._ready):
            while True:
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
        with self._olock:
            # Frames stranded in the reordering buffer by an aborted stop
            # lost their results and can never be delivered.
            self._reorder.clear()
        with self._slock:
            # Every assigned seq that is neither already delivered nor still
            # waiting in the ingest queue (ingest survivors ARE served
            # after restart, so their seqs stay live) is dead.  Mark the
            # dead seqs so the in-order flush skips over them instead of
            # holding all later frames forever.  (Threads are stopped here,
            # so touching the _olock-guarded maps under _slock cannot
            # deadlock or race the emitter.)
            with self._ingest.mutex:
                surviving = {
                    (r.stream_id, r.seq) for r in list(self._ingest.queue)
                }
            for sid, assigned in self._stream_seq.items():
                for seq in range(self._next_emit[sid], assigned):
                    if (sid, seq) not in surviving:
                        self._lost_seqs[sid].add(seq)
            # Compact quiescent streams (everything assigned was delivered
            # or marked lost, nothing surviving in ingest): their counters
            # may safely restart from zero, so a long-lived in_order
            # service with churning stream ids does not grow per-stream
            # state forever.  Threads are stopped here, so this is the one
            # place the pruning cannot race the emitter.
            live = {sid for sid, _ in surviving}
            for sid in list(self._stream_seq):
                quiescent = (
                    sid not in live
                    and self._next_emit[sid] + len(self._lost_seqs[sid])
                    >= self._stream_seq[sid]
                )
                if quiescent:
                    self._stream_seq.pop(sid, None)
                    self._next_emit.pop(sid, None)
                    self._lost_seqs.pop(sid, None)
            self._dropped = max(
                0, self._submitted - self._completed - self._failed
                - self._shed - self._ingest.qsize()
            )
        loops = (self._assemble_loop, self._support_loop, self._dense_loop,
                  self._emit_loop)
        for stage, target in zip(_STAGES, loops):
            t = threading.Thread(target=self._guard(target, stage),
                                 name=f"stereo-{stage}", daemon=True)
            t.start()
            self._threads.append(t)
        return self

    def stop(self, drain: bool = True, timeout: float = 120.0) -> None:
        """Shut down.  ``drain=True`` finishes all queued work first;
        ``drain=False`` discards queued work (counted as ``dropped``) and
        returns as soon as the stage threads exit.

        The drain wait watches for a dead pipeline: an abort or a stored
        worker error ends the wait promptly (the stored error is re-raised
        below) instead of sleeping out the full ``timeout``.  Those two
        signals are sufficient -- a stage thread can only die abnormally
        through ``_guard``, which always stores the error and aborts.  (A
        stage exiting is NOT a death signal by itself: during a normal
        drain the stages shut down in order as EOS passes through them.)
        """
        if not self._threads:
            return
        if drain and self._error is None:
            self._drain.set()
            t_end = time.monotonic() + timeout
            while not self._done.is_set() and time.monotonic() < t_end:
                if self._abort.is_set() or self._error is not None:
                    break           # pipeline died mid-drain: stop waiting
                self._done.wait(0.1)
        self._abort.set()
        for t in self._threads:
            t.join(timeout=10.0)
        self._threads = []
        with self._slock:
            self._dropped = max(
                0, self._submitted - self._completed - self._failed
                - self._shed
            )
        if self._error is not None:
            raise RuntimeError("stereo service worker failed") from self._error

    def __enter__(self) -> "StereoService":
        return self.start()

    def __exit__(self, exc_type, exc, tb) -> None:
        try:
            self.stop(drain=exc_type is None)
        except RuntimeError:
            if exc_type is None:    # don't mask the exception already in flight
                raise

    def _guard(self, target, stage: str):
        def run():
            self._compiles.bind(stage)
            try:
                target()
            except BaseException as e:            # noqa: BLE001
                self._error = e
                self._abort.set()
                self._done.set()
        return run

    # ------------------------------------------------------------------ api
    def warmup(self, shapes: Sequence[tuple[int, int]],
               calibrate: Optional[bool] = None) -> None:
        """Pre-compile wave programs for the given (H, W) resolutions.

        With ``calibrate`` (default: the service's ``autobatch`` setting)
        and ``batch > 1``, each resolution bucket first runs a tiny
        calibration pass benchmarking candidate wave widths on dummy
        frames; the winner becomes that bucket's wave width and its
        compiled programs are kept, so the hot path still sees zero
        recompiles after warm-up.  Under ``warm_start`` it also compiles
        the batch-1 cold programs the post-hoc re-run uses, so a re-run
        compiles nothing either.
        """
        if calibrate is None:
            calibrate = self.autobatch
        for h, w in shapes:
            calibrated = False
            if calibrate and self.batch > 1:
                before = self._cache.calibrations
                self._cache.calibrate(h, w)
                # the pass compiled + exercised the winner
                calibrated = self._cache.calibrations != before
            if not calibrated:
                self._cache.warm(h, w)
            if self.warm_start:
                self._cache.warm(h, w, batch=1, cold_only=True)

    def submit(self, frame_id: int, left: np.ndarray, right: np.ndarray,
               stream_id: int = 0,
               deadline: Optional[float] = None) -> int:
        """Enqueue one stereo pair; returns the request id.

        ``deadline`` is an absolute ``time.monotonic()`` timestamp: a
        request whose deadline passes before its wave is assembled is shed
        without spending device time and delivered as an error frame
        (``shed``/``expired`` in :meth:`stats`).  ``None`` == no deadline.

        Blocks only when ``max_pending`` requests are already in flight --
        the backpressure point (time spent blocked is accounted in
        :meth:`stats`)."""
        with span("stereo.submit", stream=stream_id):
            if self._error is not None:
                raise RuntimeError("stereo service worker failed") from self._error
            left = np.asarray(left, np.float32)
            right = np.asarray(right, np.float32)
            if left.shape != right.shape or left.ndim != 2:
                raise ValueError(
                    f"expected matching (H, W) pairs, got {left.shape} vs {right.shape}"
                )
            min_dim = max(self.params.grid_size, self.params.candidate_step)
            if left.shape[0] < min_dim or left.shape[1] < min_dim:
                raise ValueError(
                    f"frame {left.shape} too small: needs at least one "
                    f"{min_dim}x{min_dim} grid cell (grid_size={self.params.grid_size})"
                )
            if deadline is not None:
                deadline = float(deadline)
            now = time.monotonic()
            with self._slock:
                rid = self._next_request_id
                self._next_request_id += 1
                # Sequence numbers exist for the in_order reordering buffer and
                # for warm-start chain identity (the state machine must prove a
                # frame's seed is its immediate predecessor); without either,
                # skip the per-stream dict so a service fed fresh stream ids
                # per client never accumulates bookkeeping.
                seq = 0
                if self.in_order or self.warm_start:
                    seq = self._stream_seq[stream_id]
                    self._stream_seq[stream_id] = seq + 1
                if self._t_first_submit is None:
                    self._t_first_submit = now
                self._inflight[rid] = (stream_id, frame_id)
            req = _Request(
                request_id=rid, stream_id=stream_id, frame_id=frame_id,
                left=left, right=right, h=left.shape[0], w=left.shape[1],
                t_submit=now, seq=seq, deadline=deadline,
            )
            # Stamped before the put: the assembler may take the request
            # before put() returns, and a blocked put is a queue wait.
            req.t_enqueued = time.monotonic()
            while True:     # abort-aware put: never deadlock on a dead service
                if self._error is not None:
                    raise RuntimeError(
                        "stereo service worker failed") from self._error
                try:
                    self._ingest.put(req, timeout=0.05)
                    break
                except queue.Full:
                    if not self._threads:
                        raise RuntimeError(
                            "ingest queue full and service not running"
                        ) from None
            waited = time.monotonic() - req.t_enqueued
            with self._slock:
                self._submitted += 1
                self._backpressure_s += waited
            return rid

    def collect(self, n: int, timeout: float = 60.0,
                strict: bool = False) -> list[CompletedFrame]:
        """Up to ``n`` completed frames (successes AND terminal failures),
        waiting at most ``timeout`` seconds TOTAL -- the deadline covers
        the whole call, not each frame, so ``n`` slow frames can never
        stretch the wait to ``n x timeout``.

        With ``strict=True``, fewer than ``n`` frames inside the deadline
        raises :class:`TimeoutError` naming the still-outstanding frame
        ids; the partial results are attached as ``err.partial``.  The
        default returns the partial list (compatible with pollers like
        :meth:`run_stream` that call with tiny timeouts).
        """
        out: list[CompletedFrame] = []
        deadline = time.monotonic() + timeout
        while len(out) < n:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            try:
                out.append(self._out.get(timeout=min(0.05, remaining)))
                continue
            except queue.Empty:
                pass
            # only surface a worker failure once finished frames are drained
            if self._error is not None:
                raise RuntimeError("stereo service worker failed") from self._error
        if strict and len(out) < n:
            with self._slock:
                missing = sorted(
                    fid for _, fid in self._inflight.values()
                )
            err = TimeoutError(
                f"collect() got {len(out)}/{n} frames within {timeout:.3f}s; "
                f"outstanding frame ids: {missing[:32]}"
                + (" ..." if len(missing) > 32 else "")
            )
            err.partial = out
            raise err
        return out

    def results(self, n: int, timeout: float = 60.0) -> list[tuple[int, np.ndarray]]:
        """Compatibility shim: ``(frame_id, disparity)`` tuples (disparity
        is None for frames that failed or were shed)."""
        return [(c.frame_id, c.disparity) for c in self.collect(n, timeout)]

    def run_stream(
        self, frames: Iterator[tuple[np.ndarray, np.ndarray]], n_frames: int,
        timeout: float = 600.0,
    ) -> tuple[list, float]:
        """Process a single stream; returns ``((frame_id, disp) list, wall_s)``.

        Returns whatever completed within ``timeout`` (possibly fewer than
        ``n_frames``) rather than blocking forever on a lost frame.  Failed
        or shed frames appear with ``disp=None``."""
        t0 = time.monotonic()
        deadline = t0 + timeout
        submitted = 0
        results: list = []
        it = iter(frames)
        while len(results) < n_frames and time.monotonic() < deadline:
            if submitted < n_frames:
                try:
                    left, right = next(it)
                    self.submit(submitted, left, right)
                    submitted += 1
                except StopIteration:
                    submitted = n_frames
            results.extend(self.results(
                1, timeout=0.01 if submitted < n_frames
                else max(0.0, min(1.0, deadline - time.monotonic()))
            ))
        return results, time.monotonic() - t0

    def stats(self) -> ServiceStats:
        adm = self._admission.counters()
        with self._wlock:
            warm = (self._warm_frames, self._cold_frames,
                    self._scene_changes, self._warm_refreshes,
                    self._warm_reruns, self._warm_resets,
                    self._warm_scored, self._warm_score_sum)
        dead = set(self._monitor.dead_hosts()) if self._threads else set()
        liveness = tuple(
            (s, s not in dead) for s in _STAGES
        ) if self._threads else ()
        compiles = self._compiles.snapshot()
        with self._slock:
            lats = sorted(self._latencies)
            n = len(lats)
            avg = (self._lat_sum / self._completed) if self._completed else 0.0
            p50 = lats[n // 2] if n else 0.0
            p95 = lats[min(n - 1, int(n * 0.95))] if n else 0.0
            span = (
                (self._t_last_emit - self._t_first_submit)
                if self._t_last_emit is not None and self._t_first_submit is not None
                else 0.0
            )
            return ServiceStats(
                submitted=self._submitted,
                completed=self._completed,
                dropped=self._dropped,
                pending=(self._submitted - self._completed - self._dropped
                         - self._failed - self._shed),
                waves=self._waves_built,
                padded_slots=self._padded_slots,
                wave_occupancy=(
                    1.0 - self._padded_slots / self._wave_slots
                    if self._wave_slots else 0.0
                ),
                cache_hits=self._cache.hits,
                cache_misses=self._cache.misses,
                programs_cached=len(self._cache),
                backpressure_seconds=self._backpressure_s,
                latency_avg_ms=avg * 1e3,
                latency_p50_ms=p50 * 1e3,
                latency_p95_ms=p95 * 1e3,
                latency_max_ms=self._lat_max * 1e3,
                throughput_fps=(self._completed / span) if span > 0 else 0.0,
                calibrations=self._cache.calibrations,
                batch_by_bucket=self._cache.batch_choices(),
                backend=self.backend,
                tile=self.tile if isinstance(self.tile, TileSpec) else None,
                shed=self._shed,
                expired=adm["expired"],
                retried=self._retried,
                failed_frames=self._failed,
                degraded_waves=self._degraded_waves,
                degraded=adm["degraded"],
                admitted_by_stream=adm["admitted_by_stream"],
                shed_by_stream=adm["shed_by_stream"],
                stage_liveness=liveness,
                compiles_after_warmup=sum(n for _, n in compiles),
                compiles_by_stage=compiles,
                warm_frames=warm[0],
                cold_frames=warm[1],
                scene_changes=warm[2],
                warm_refreshes=warm[3],
                warm_reruns=warm[4],
                warm_resets=warm[5],
                warm_scored=warm[6],
                warm_score_sum=warm[7],
            )

    # ------------------------------------------------------- stage plumbing
    def _beat(self, stage: str) -> None:
        self._monitor.beat(stage, 0)    # liveness reads only the beat's time

    def _put(self, q: queue.Queue, item, stage: str) -> bool:
        while not self._abort.is_set():
            self._beat(stage)
            try:
                q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _get(self, q: queue.Queue, stage: str):
        while not self._abort.is_set():
            self._beat(stage)
            try:
                return q.get(timeout=0.05)
            except queue.Empty:
                continue
        return None

    # --------------------------------------------------- stage 0: assembly
    def _assemble_loop(self) -> None:
        pending: collections.deque = collections.deque()
        while not self._abort.is_set():
            self._beat("assemble")
            draining = self._drain.is_set()
            try:
                req = self._ingest.get(timeout=0.02)
                self._classify_warm(req)
                pending.append(req)
            except queue.Empty:
                if draining and not pending:
                    self._put(self._waves, _EOS, "assemble")
                    return
                if not pending:
                    continue

            # Shed work that expired while queued -- in EVERY bucket, so an
            # expired request never waits for its bucket to reach the head
            # of the line before being declared dead.
            now = time.monotonic()
            if any(r.deadline is not None and r.deadline < now
                   for r in pending):
                _, dead = self._admission.select(list(pending), 0, now)
                dead_ids = {r.request_id for r in dead}
                pending = collections.deque(
                    r for r in pending if r.request_id not in dead_ids
                )
                for r in dead:
                    self._shed_request(r)
                if not pending:
                    continue

            # Fill the head-of-line wave: linger briefly for same-bucket
            # requests, then dispatch padded rather than stall.  The wave
            # width is the bucket's (possibly calibrated) batch.  Warm and
            # cold frames never share a wave (their programs differ), so
            # the warm classification joins the grouping key.
            key = self._cache.bucket_shape(pending[0].h, pending[0].w)
            warm = pending[0].warm
            width = self._cache.batch_for(*key)
            deadline = time.monotonic() + self.wave_linger
            # Only this thread builds waves: the next one gets this index.
            with span("stereo.assemble.linger", wave=self._waves_built):
                while (not draining
                       and sum(self._cache.bucket_shape(r.h, r.w) == key
                               and r.warm == warm for r in pending) < width):
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    try:
                        req = self._ingest.get(timeout=remaining)
                        self._classify_warm(req)
                        pending.append(req)
                    except queue.Empty:
                        break

            # Admission: deadline shedding + per-stream round-robin slots
            # over the head bucket's candidates.
            candidates = [
                r for r in pending
                if self._cache.bucket_shape(r.h, r.w) == key
                and r.warm == warm
            ]
            admitted, dead = self._admission.select(
                candidates, width, time.monotonic()
            )
            taken = {r.request_id for r in admitted}
            taken |= {r.request_id for r in dead}
            pending = collections.deque(
                r for r in pending if r.request_id not in taken
            )
            for r in dead:
                self._shed_request(r)
            if not admitted:
                continue
            backlog = self._ingest.qsize() + len(pending) + len(admitted)
            degraded = self._admission.update_pressure(backlog)
            wave = self._build_wave(key, admitted, width, degraded, warm)
            if not self._put(self._waves, wave, "assemble"):
                return

    def _classify_warm(self, req: _Request) -> None:
        """The warm/cold decision for one frame, pinned as it enters
        assembly: stamps ``req.warm`` / ``req.prior`` / ``req.thumb`` and
        advances the warm counters.  A no-op with ``warm_start=False`` --
        the cold path never touches warm state, locks, or thumbnails."""
        if not self.warm_start:
            return
        if req.deadline is not None and req.deadline < time.monotonic():
            # Already expired: admission sheds it this same assembly pass.
            # A doomed frame must not touch the stream's state or advance
            # its streak (its shed delivery still resets the state).
            return
        fault = (self.fault_plan.warm_kind(req.request_id)
                 if self.fault_plan is not None else None)
        req.thumb = frame_thumbnail(req.left)
        with self._wlock:
            state = self._warm_state.get(req.stream_id)
            if fault == "stale_state" and state is not None:
                # Poison the STORED seed in place.  The thumbnail still
                # matches, so classification goes warm on a corrupt prior
                # -- the silent-corruption scenario; only the post-hoc
                # disagreement check can catch it.
                state.disparity = _warmstart.corrupt_disparity(
                    state.disparity, self.params.disp_max
                )
            if fault == "scene_cut":
                # Force the detector's verdict without touching the frame:
                # the frame must come out bitwise-cold with a state reset.
                warm, reason = False, "scene_change"
            else:
                warm, reason = _warmstart.classify(
                    state, req.thumb, (req.h, req.w), req.seq,
                    threshold=self.scene_change_threshold,
                    refresh_interval=self.refresh_interval,
                )
            if warm:
                req.warm = True
                # Pin the prior NOW: a state reset later in flight (error
                # delivery, scene cut on a younger frame) must not
                # retroactively change an assembled wave.
                req.prior = state.disparity.copy()
                if fault == "corrupt_prior":
                    # In-flight copy only; the stream state stays intact.
                    req.prior = _warmstart.corrupt_disparity(
                        req.prior, self.params.disp_max
                    )
                state.streak += 1
                self._warm_frames += 1
            else:
                self._cold_frames += 1
                if reason == "scene_change":
                    self._scene_changes += 1
                elif reason == "refresh":
                    self._warm_refreshes += 1
                elif reason in ("stale_seq", "resolution"):
                    self._warm_resets += 1
                # Every cold reason except "no state" resets the chain, so
                # this frame's own delivery re-seeds it.
                if state is not None:
                    self._warm_state.pop(req.stream_id, None)

    def _shed_request(self, req: _Request) -> None:
        self._finish(req, None, error=(
            f"shed by admission control: deadline expired before compute "
            f"(frame {req.frame_id}, stream {req.stream_id})"
        ), shed=True)

    def _build_wave(self, key: tuple, reqs: list, width: int,
                    degraded: bool = False, warm: bool = False) -> _Wave:
        bh, bw = key
        pad = width - len(reqs)

        def fit(img: np.ndarray) -> np.ndarray:
            h, w = img.shape
            if (h, w) == (bh, bw):
                return img
            return np.pad(img, ((0, bh - h), (0, bw - w)), mode="edge")

        with self._slock:
            index = self._waves_built
            self._waves_built += 1
            self._wave_slots += width
            self._padded_slots += pad
            if degraded:
                self._degraded_waves += 1
        with span("stereo.assemble.build", wave=index) as sp:
            lefts = [fit(r.left) for r in reqs]
            rights = [fit(r.right) for r in reqs]
            if pad:                 # replicate a real frame into padded slots
                lefts += [lefts[0]] * pad
                rights += [rights[0]] * pad
            prior = None
            if warm:
                # Stack the pinned per-frame priors (padded slots replicate
                # a real one, like the frames above).  Warm requests KEEP
                # their host frames/priors: the emit stage needs them for
                # the post-hoc disagreement check and its cold re-run.
                priors = [fit(r.prior) for r in reqs]
                if pad:
                    priors += [priors[0]] * pad
                prior = jnp.asarray(np.stack(priors))
            else:
                for r in reqs:      # emit only needs ids/shape/timing: release
                    r.left = r.right = None  # host frames while waves queue
            wave = _Wave(
                key=key, requests=reqs, index=index, degraded=degraded,
                warm=warm, prior=prior,
                left=jnp.asarray(np.stack(lefts)),
                right=jnp.asarray(np.stack(rights)),
            )
        wave.t.update(wave=index, build_start=sp.start, build_end=sp.end)
        return wave

    # ------------------------------------------- stages 1+2: contained exec
    def _check_faults(self, stage: str, wave: _Wave) -> None:
        if self.fault_plan is not None:
            self.fault_plan.check(
                stage, wave.index,
                tuple(r.request_id for r in wave.requests),
            )

    def _exec_stage(self, wave: _Wave, stage: str) -> None:
        """Run one stage's program over one wave, blocking on the result so
        failures surface HERE -- in the stage that owns the retry -- rather
        than asynchronously at emit.  The stage's span covers dispatch to
        ready and gives the wave its ``<stage>_dispatch/_ready`` stamps."""
        self._check_faults(stage, wave)
        if stage == "support":
            wave.programs = self._cache.get(
                *wave.key, batch=int(wave.left.shape[0])
            )
            support = (wave.programs.support_warm if wave.warm
                       else wave.programs.support)
            with span("stereo.support.run", wave=wave.index) as sp:
                wave.mid = support(wave.left, wave.right)
                jax.block_until_ready(wave.mid)
            wave.left = wave.right = None
        else:
            prog = wave.programs
            if wave.warm:
                dense = (prog.dense_warm_degraded
                         if wave.degraded
                         and prog.dense_warm_degraded is not None
                         else prog.dense_warm)
                args = (*wave.mid, wave.prior)
            else:
                dense = (prog.dense_degraded
                         if wave.degraded and prog.dense_degraded is not None
                         else prog.dense)
                args = wave.mid
            with span("stereo.dense.run", wave=wave.index) as sp:
                wave.disp = dense(*args)
                jax.block_until_ready(wave.disp)
            wave.mid = None
            wave.prior = None
        wave.t[f"{stage}_dispatch"], wave.t[f"{stage}_ready"] = sp.start, sp.end

    def _retry_slot(self, wave: _Wave, stage: str, slot: int) -> _Wave:
        """The bounded retry: re-run ONE slot of a failed wave as a
        single-frame fallback wave (batch-1 program; a cold-path compile
        the first time a bucket needs it).  A warm wave's slot retries on
        the batch-1 WARM programs with its slice of the wave's pinned
        prior -- warm state survives the retry path."""
        req = wave.requests[slot]
        with self._slock:
            self._retried += 1
        prog = self._cache.get(*wave.key, batch=1)
        sub = _Wave(key=wave.key, requests=[req], left=None, right=None,
                    index=wave.index, degraded=wave.degraded, warm=wave.warm,
                    programs=prog, t=dict(wave.t))
        if self.fault_plan is not None:
            self.fault_plan.check(stage, wave.index, (req.request_id,))
        with span("stereo.retry", wave=wave.index, stage=stage) as sp:
            if stage == "support":
                support = prog.support_warm if wave.warm else prog.support
                sub.mid = support(wave.left[slot:slot + 1],
                                  wave.right[slot:slot + 1])
                jax.block_until_ready(sub.mid)
            else:
                mid = tuple(m[slot:slot + 1] for m in wave.mid)
                if wave.warm:
                    dense = (prog.dense_warm_degraded
                             if wave.degraded
                             and prog.dense_warm_degraded is not None
                             else prog.dense_warm)
                    sub.disp = dense(*mid, wave.prior[slot:slot + 1])
                else:
                    dense = (prog.dense_degraded
                             if wave.degraded
                             and prog.dense_degraded is not None
                             else prog.dense)
                    sub.disp = dense(*mid)
                jax.block_until_ready(sub.disp)
        sub.t[f"{stage}_dispatch"], sub.t[f"{stage}_ready"] = sp.start, sp.end
        return sub

    def _contain(self, wave: _Wave, stage: str, exc: Exception,
                 downstream: queue.Queue) -> bool:
        """Wave-scoped error containment: the failed wave is split into
        single-frame fallback waves and retried once per slot.  Slots that
        recover continue downstream; slots that fail again are quarantined
        (delivered as error frames).  Only repeated SYSTEMIC failure --
        ``max_wave_failures`` consecutive waves with no surviving slot --
        aborts the engine.  Returns False only when aborting mid-push."""
        survivors: list[_Wave] = []
        failures: list[tuple[_Request, Exception]] = []
        for slot, req in enumerate(wave.requests):
            try:
                survivors.append(self._retry_slot(wave, stage, slot))
            except Exception as retry_exc:     # noqa: BLE001 -- quarantine
                failures.append((req, retry_exc))
        for req, retry_exc in failures:
            self._finish(req, None, error=(
                f"{stage} stage failed after retry: {retry_exc!r} "
                f"(wave {wave.index}, first failure: {exc!r})"
            ))
        systemic = False
        with self._slock:
            if failures and not survivors:
                self._consec_wave_failures += 1
                systemic = (self._consec_wave_failures
                            >= self.max_wave_failures)
            else:
                self._consec_wave_failures = 0
        if systemic:
            raise RuntimeError(
                f"systemic failure: {self.max_wave_failures} consecutive "
                f"waves failed completely in the {stage} stage"
            ) from exc
        for sub in survivors:
            if not self._put(downstream, sub, stage):
                return False
        return True

    def _stage_loop(self, stage: str, upstream: queue.Queue,
                    downstream: queue.Queue) -> None:
        while True:
            wave = self._get(upstream, stage)
            if wave is None:
                return
            if wave is _EOS:
                self._put(downstream, _EOS, stage)
                return
            try:
                self._exec_stage(wave, stage)
            except Exception as e:             # noqa: BLE001 -- contained
                if not self._contain(wave, stage, e, downstream):
                    return
            else:
                with self._slock:
                    self._consec_wave_failures = 0
                if not self._put(downstream, wave, stage):
                    return

    def _support_loop(self) -> None:
        self._stage_loop("support", self._waves, self._mid)

    def _dense_loop(self) -> None:
        self._stage_loop("dense", self._mid, self._ready)

    # ------------------------------------------------------- stage 3: emit
    def _emit_loop(self) -> None:
        while True:
            wave = self._get(self._ready, "emit")
            if wave is None:
                return
            if wave is _EOS:
                self._done.set()
                return
            try:
                self._check_faults("emit", wave)
                with span("stereo.emit.readback", wave=wave.index) as sp:
                    disp = np.asarray(wave.disp)   # device -> host sync
            except Exception as e:             # noqa: BLE001 -- contain: the
                # wave's device buffers are gone, so there is no retry here;
                # its frames fail terminally but the engine stays up.
                for req in wave.requests:
                    self._finish(req, None, error=(
                        f"emit stage failed: {e!r} (wave {wave.index})"
                    ))
                with self._slock:
                    self._consec_wave_failures += 1
                    systemic = (self._consec_wave_failures
                                >= self.max_wave_failures)
                if systemic:
                    raise RuntimeError(
                        f"systemic failure: {self.max_wave_failures} "
                        f"consecutive waves failed at emit"
                    ) from e
                continue
            with self._slock:
                self._consec_wave_failures = 0
            wave.t.update(emit_start=sp.start, readback_end=sp.end)
            with span("stereo.emit.deliver", wave=wave.index):
                for slot, req in enumerate(wave.requests):
                    out = np.ascontiguousarray(disp[slot, : req.h, : req.w])
                    error = None
                    if wave.warm:
                        out, error = self._posthoc_check(req, out, wave.key)
                        req.left = req.right = req.prior = None
                    req.stamps = wave.t
                    self._finish(req, out, error=error)
            wave.disp = None

    def _posthoc_check(self, req: _Request, out: np.ndarray,
                       key: tuple) -> tuple:
        """The warm self-check at emit: score the result against the very
        prior that seeded it; past ``rerun_threshold * num_disp`` the frame
        is retroactively re-run COLD on the batch-1 fallback programs
        (bitwise equal to the cold search).  Returns ``(out, error)``."""
        score = prior_disagreement(out, req.prior, self.params.num_disp)
        limit = self.rerun_threshold * self.params.num_disp
        with self._wlock:
            self._warm_scored += 1
            self._warm_score_sum += score / limit
            if score > limit:
                self._warm_reruns += 1
        if score <= limit:
            return out, None
        try:
            with span("stereo.warm.rerun", request=req.request_id):
                return self._run_cold_single(req, key), None
        except Exception as e:             # noqa: BLE001 -- contained: the
            # re-run failing fails only this frame, like any compute fault
            return None, (
                f"warm post-hoc cold re-run failed: {e!r} "
                f"(disagreement {score:.1f} levels, limit {limit:.1f})"
            )

    def _run_cold_single(self, req: _Request, key: tuple) -> np.ndarray:
        """One frame through the batch-1 COLD wave programs, from its host
        frames (warm requests keep them until emit for exactly this)."""
        bh, bw = key

        def fit(img: np.ndarray) -> np.ndarray:
            h, w = img.shape
            if (h, w) == (bh, bw):
                return img
            return np.pad(img, ((0, bh - h), (0, bw - w)), mode="edge")

        prog = self._cache.get(*key, batch=1)
        dl, dr, sup = prog.support(jnp.asarray(fit(req.left)[None]),
                                   jnp.asarray(fit(req.right)[None]))
        disp = prog.dense(dl, dr, sup)
        return np.ascontiguousarray(np.asarray(disp)[0, : req.h, : req.w])

    # ------------------------------------------------------------ delivery
    def _finish(self, req: _Request, out: Optional[np.ndarray],
                error: Optional[str] = None, shed: bool = False) -> None:
        """Terminal delivery for one request -- success, compute failure,
        or admission shed.  Honors the in_order reordering buffer: every
        terminal state advances the stream's sequence, so a failed or shed
        frame never blocks the frames behind it."""
        req.t_finished = time.monotonic()
        if not self.in_order:
            self._deliver(req, out, error, shed)
            return
        with self._olock:
            # Per-stream reordering buffer: hold this frame until every
            # earlier submission of the same stream has been delivered,
            # then flush the now-consecutive run.  Latency is measured
            # at delivery, so held frames honestly include hold time.
            sid = req.stream_id
            self._reorder.setdefault(sid, {})[req.seq] = (req, out, error, shed)
            pending = self._reorder[sid]
            while True:
                nxt = self._next_emit[sid]
                if nxt in self._lost_seqs[sid]:
                    # known-dead seq (dropped by an aborted stop):
                    # skip it so survivors behind it still deliver
                    self._lost_seqs[sid].discard(nxt)
                    self._next_emit[sid] = nxt + 1
                elif nxt in pending:
                    r, o, err, sh = pending.pop(nxt)
                    self._next_emit[sid] = nxt + 1
                    self._deliver(r, o, err, sh)
                else:
                    break

    def _deliver(self, req: _Request, out: Optional[np.ndarray],
                 error: Optional[str] = None, shed: bool = False) -> None:
        now = time.monotonic()
        lat = now - req.t_submit
        if self.warm_start:
            # Warm state transitions ride delivery -- the ONLY writer of
            # per-stream state, so a frame can seed its successor only
            # after it was actually delivered intact and in sequence.
            with self._wlock:
                state = self._warm_state.get(req.stream_id)
                if error is not None:
                    # Quarantined (compute fault after retry) or shed
                    # frame: whatever state exists is now suspect -- the
                    # next frame must re-seed cold.
                    if state is not None:
                        self._warm_state.pop(req.stream_id, None)
                        self._warm_resets += 1
                elif state is None or req.seq == state.seq + 1:
                    self._warm_state[req.stream_id] = WarmState.from_delivery(
                        out, req.thumb, req.seq,
                        streak=state.streak if state is not None else 0,
                    )
                else:
                    # Out-of-sequence delivery: the temporal chain is
                    # broken (a frame between this one and the stored
                    # seed is still in flight, or this frame arrived
                    # late).  Reset rather than store a gapped seed.
                    self._warm_state.pop(req.stream_id, None)
                    self._warm_resets += 1
        with self._slock:
            self._inflight.pop(req.request_id, None)
            if error is None:
                self._completed += 1
                self._latencies.append(lat)
                self._lat_sum += lat
                self._lat_max = max(self._lat_max, lat)
            elif shed:
                self._shed += 1
            else:
                self._failed += 1
            self._t_last_emit = now
        timing = None
        if error is None and req.stamps is not None:
            timing = FrameTiming(
                submit=req.t_submit, enqueued=req.t_enqueued, **req.stamps,
                finished=req.t_finished, delivered=now,
            )
        self._out.put(CompletedFrame(
            request_id=req.request_id, stream_id=req.stream_id,
            frame_id=req.frame_id, disparity=out, latency_s=lat,
            error=error, timing=timing,
        ))
