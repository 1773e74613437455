"""Spans, the per-frame timeline and the compile counter of the stereo service.

* :class:`span` -- one unit of the service's work.  It enters a
  ``jax.profiler.TraceAnnotation`` (so a profiler trace shows it on the
  same clock as the device operations, with the wave index as metadata)
  and stamps ``time.monotonic()`` at both ends, the clock of
  :attr:`CompletedFrame.latency_s <repro.serving.stereo_service.CompletedFrame>`.
  With the profiler off a span costs one annotation enter/exit and two
  clock reads.

* :class:`FrameTiming` -- a delivered frame's consecutive boundary stamps.
  The parts between them partition ``[submit, delivered]``, so they sum to
  the frame's ``latency_s``; :data:`PART_KIND` says which parts are host
  work, a device program (dispatch to ready) or a wait.

* :class:`CompileCounter` -- XLA compiles run by a service's own stage
  threads.  A process-wide ``jax.monitoring`` listener on the backend
  compile event credits each compile to the counter and stage bound to
  the thread that ran it.  That event wraps the persistent-cache lookup,
  so a cache load counts as one compile too.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# part name -> kind, in timeline order; the part ends at the stamp of the
# same position in FrameTiming.STAMPS[1:].
PART_KIND = {
    "submit": "host",          # the request record, up to the ingest put
    "ingest_wait": "wait",     # blocked put (backpressure), ingest queue
                               # and the wave linger
    "build": "host",           # pad, stack, upload
    "support_queue": "wait",
    "support_run": "program",  # dispatch -> ready (includes device wait)
    "dense_queue": "wait",
    "dense_run": "program",
    "emit_queue": "wait",
    "readback": "host",        # device -> host copy of the wave
    "deliver": "host",         # slicing and delivery up to this frame's own
    "hold": "wait",            # in_order reordering buffer
}


class span:
    """``with span("stereo.dense.run", wave=3) as s:`` -- a profiler
    annotation plus ``s.start`` / ``s.end`` on ``time.monotonic()``."""

    __slots__ = ("_ann", "start", "end")

    def __init__(self, name: str, **metadata):
        self._ann = jax.profiler.TraceAnnotation(name, **metadata)
        self.start = self.end = None

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self.start = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.monotonic()
        self._ann.__exit__(*exc)


@dataclasses.dataclass(frozen=True)
class FrameTiming:
    """One delivered frame's timeline (``time.monotonic()`` seconds).

    Program stamps are its wave's; a slot recovered by the contained retry
    carries its single-frame sub-wave's stamps for the retried stage (the
    failed attempt then lies in the wait before it).
    """

    wave: int
    submit: float
    enqueued: float
    build_start: float
    build_end: float
    support_dispatch: float
    support_ready: float
    dense_dispatch: float
    dense_ready: float
    emit_start: float
    readback_end: float
    finished: float
    delivered: float

    STAMPS = ("submit", "enqueued", "build_start", "build_end",
              "support_dispatch", "support_ready", "dense_dispatch",
              "dense_ready", "emit_start", "readback_end", "finished",
              "delivered")

    def parts(self) -> dict:
        """Part name (:data:`PART_KIND`) -> seconds; sums to the latency."""
        t = [getattr(self, s) for s in self.STAMPS]
        return {name: t[i + 1] - t[i] for i, name in enumerate(PART_KIND)}


_local = threading.local()
_listener_lock = threading.Lock()
_listening = False


def _on_duration(event: str, duration: float, **_) -> None:
    if event == COMPILE_EVENT:
        sink = getattr(_local, "sink", None)
        if sink is not None:
            sink[0]._add(sink[1])


class CompileCounter:
    """Compiles per stage, counted in the threads :meth:`bind` marked."""

    def __init__(self):
        global _listening
        with _listener_lock:
            if not _listening:
                jax.monitoring.register_event_duration_secs_listener(_on_duration)
                _listening = True
        self._lock = threading.Lock()
        self._by_stage: collections.Counter = collections.Counter()

    def bind(self, stage: str) -> None:
        """Credit compiles the calling thread runs from now on to ``stage``."""
        _local.sink = (self, stage)

    def _add(self, stage: str) -> None:
        with self._lock:
            self._by_stage[stage] += 1

    def snapshot(self) -> tuple:
        """``((stage, compiles), ...)`` sorted by stage."""
        with self._lock:
            return tuple(sorted(self._by_stage.items()))
