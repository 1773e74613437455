"""The closed-loop load generator every traffic mix runs through.

A traffic file (``traffic/<name>.json``) gives:

* ``streams`` and ``in_flight_per_stream``: each camera stream keeps that
  many frames submitted and not yet delivered; a stream submits its next
  frame the moment one of its frames comes back (a closed loop);
* ``pool``: how many seeded pairs the frames are drawn from, round-robin
  over all streams in submission order;
* ``service``: the ``StereoService`` settings of the deployment
  (``batch``, ``depth``, ``wave_linger``);
* ``prime_rounds``: rounds of every in-flight slot run through the served
  path during set-up, so the window starts warm.

Frames are submitted as the uint8 arrays a camera gives; casting and
uploading them is part of the timed path.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Optional

import numpy as np


def service_kwargs(traffic: dict) -> dict:
    if traffic.get("loop") != "closed":
        raise ValueError(f"this generator runs closed loops, not {traffic.get('loop')!r}")
    allowed = {"batch", "depth", "wave_linger"}
    extra = set(traffic["service"]) - allowed
    if extra:
        raise ValueError(f"unknown service settings {sorted(extra)}")
    return dict(traffic["service"])


def frame_pool(seed: int, n: int, h: int, w: int, d_max: float,
               n_objects: int) -> tuple[np.ndarray, np.ndarray]:
    """``n`` seeded (left, right) uint8 pairs of (h, w), as two (n, h, w)
    arrays: ``repro.data.stereo.synthetic_stereo_pair`` scenes, each with a
    seed drawn from ``seed``, so the same seed gives the same pool."""
    from repro.data.stereo import synthetic_stereo_pair

    seeds = np.random.SeedSequence(int(seed) % 2**64).generate_state(n, np.uint64)
    pairs = [synthetic_stereo_pair(h, w, d_max, n_objects, seed=int(s))[:2] for s in seeds]
    return np.stack([p[0] for p in pairs]), np.stack([p[1] for p in pairs])


def percentile(values, q: float) -> float:
    """The ``q``-quantile (0..1) by nearest rank; 0.0 if empty."""
    xs = sorted(values)
    if not xs:
        return 0.0
    return xs[min(len(xs) - 1, int(q * len(xs)))]


@dataclasses.dataclass
class Record:
    frame_id: int
    stream: int
    pool_index: int
    t_done: Optional[float] = None
    frame: object = None                  # the CompletedFrame delivered
    duplicates: int = 0


@dataclasses.dataclass
class Window:
    seconds: float
    t_start: float
    t_end: float
    records: list                          # every frame submitted in the window
    strays: int                            # deliveries matching no submission

    def delivered_in_window(self) -> int:
        return sum(1 for r in self.records
                   if r.frame is not None and r.frame.ok and r.t_done <= self.t_end)

    def failed(self) -> int:
        return sum(1 for r in self.records if r.frame is None or not r.frame.ok)

    def latencies_ms(self) -> list:
        """Per frame submitted in the window; a failed or lost frame is inf."""
        return [r.frame.latency_s * 1e3 if r.frame is not None and r.frame.ok
                else float("inf") for r in self.records]


class _Loop:
    def __init__(self, svc, pool_l, pool_r, traffic, first_id: int):
        self.svc, self.pool_l, self.pool_r = svc, pool_l, pool_r
        self.n_pool = len(pool_l)
        self.traffic = traffic
        self.ids = itertools.count(first_id)
        self.records: dict = {}
        self.strays = 0

    def submit(self, stream: int) -> None:
        fid = next(self.ids)
        idx = fid % self.n_pool
        self.records[fid] = Record(fid, stream, idx)
        self.svc.submit(fid, self.pool_l[idx], self.pool_r[idx], stream_id=stream)

    def fill(self) -> None:
        for s in range(self.traffic["streams"]):
            for _ in range(self.traffic["in_flight_per_stream"]):
                self.submit(s)

    def take(self, frame) -> Optional[Record]:
        rec = self.records.get(frame.frame_id)
        if rec is None or rec.stream != frame.stream_id:
            self.strays += 1
            return None
        if rec.frame is not None:
            rec.duplicates += 1
            return None
        rec.frame, rec.t_done = frame, time.monotonic()
        return rec

    def outstanding(self) -> int:
        return sum(1 for r in self.records.values() if r.frame is None)


def prime(svc, pool_l, pool_r, traffic: dict) -> None:
    """Run ``prime_rounds`` rounds of every slot, then wait until all are back."""
    loop = _Loop(svc, pool_l, pool_r, traffic, first_id=-10**9)
    loop.fill()
    left = traffic["prime_rounds"] * len(loop.records) - len(loop.records)
    deadline = time.monotonic() + 600.0
    while loop.outstanding() and time.monotonic() < deadline:
        for frame in svc.collect(1, timeout=1.0):
            rec = loop.take(frame)
            if rec is not None and left > 0:
                left -= 1
                loop.submit(rec.stream)
    if loop.outstanding():
        raise RuntimeError(f"{loop.outstanding()} priming frames never came back")


def closed_loop(svc, pool_l, pool_r, traffic: dict, seconds: float,
                wait_after_close: float) -> Window:
    """The measured window: ``seconds`` of closed-loop load, then a wait of
    up to ``wait_after_close`` seconds for the frames still in flight."""
    loop = _Loop(svc, pool_l, pool_r, traffic, first_id=0)
    t_start = time.monotonic()
    t_end = t_start + seconds
    loop.fill()
    while True:
        now = time.monotonic()
        if now >= t_end:
            break
        for frame in svc.collect(1, timeout=t_end - now):
            rec = loop.take(frame)
            if rec is not None and time.monotonic() < t_end:
                loop.submit(rec.stream)
    deadline = t_end + wait_after_close
    while loop.outstanding() and time.monotonic() < deadline:
        for frame in svc.collect(1, timeout=min(1.0, deadline - time.monotonic())):
            loop.take(frame)
    return Window(seconds, t_start, t_end, list(loop.records.values()), loop.strays)
