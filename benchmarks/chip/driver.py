"""The closed-loop load generator every traffic mix runs through.

A traffic file (``traffic/<name>.json``) gives:

* ``streams`` and ``in_flight_per_stream``: each camera stream keeps that
  many frames submitted and not yet delivered; a stream submits its next
  frame the moment one of its frames comes back (a closed loop);
* ``source``: where the frames come from, ``"pairs"`` (the default) or
  ``"sequence"``:

  - ``pairs``: ``pool`` seeded independent pairs, dealt round-robin over
    all streams in submission order (frame id modulo ``pool``);
  - ``sequence``: ``sequences`` seeded videos of ``frames`` frames each, a
    pan of ``motion`` px a frame with a scene cut half way, so the wrap
    from the last frame to the first is a cut too.  Stream ``s`` plays
    video ``s mod sequences`` in order and in a cycle, from frame
    ``s * frames // streams``, so the cuts are staggered over the fleet;
    its place carries on from priming into the window;

* ``service``: the ``StereoService`` settings of the deployment
  (``batch``, ``depth``, ``wave_linger``, and for video ``warm_start``,
  ``warm_band``);
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
    allowed = {"batch", "depth", "wave_linger", "warm_start", "warm_band"}
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


def sequence_pool(seed: int, sequences: int, frames: int, h: int, w: int, d_max: float,
                  n_objects: int, motion: int) -> tuple[np.ndarray, np.ndarray]:
    """``sequences`` seeded videos of ``frames`` (left, right) uint8 frames of
    (h, w), as two (sequences * frames, h, w) arrays, video ``i``'s frame
    ``t`` at ``i * frames + t``: ``repro.data.stereo.synthetic_stereo_sequence``
    pans of ``motion`` px a frame, cut at ``frames // 2``, each with a seed
    drawn from ``seed`` as in :func:`frame_pool`."""
    from repro.data.stereo import synthetic_stereo_sequence

    seeds = np.random.SeedSequence(int(seed) % 2**64).generate_state(sequences, np.uint64)
    left = np.empty((sequences * frames, h, w), np.uint8)
    right = np.empty_like(left)
    for i, s in enumerate(seeds):
        video = synthetic_stereo_sequence(frames, h, w, d_max, n_objects, motion,
                                          cut_at=frames // 2, seed=int(s))
        for t, (l, r, _) in enumerate(video):
            left[i * frames + t], right[i * frames + t] = l, r
    return left, right


class Pairs:
    """Independent pairs: the frame with id ``i`` is pair ``i mod pool``,
    whatever its stream."""

    def __init__(self, left: np.ndarray, right: np.ndarray):
        self.left, self.right = left, right

    def place(self, frame_id: int, stream: int) -> tuple:
        """(pool index, video, frame of the video) of the next frame."""
        return frame_id % len(self.left), None, None


class Sequences:
    """Videos played in order: stream ``s`` plays video ``s mod sequences``
    in a cycle, from frame ``s * frames // streams``."""

    def __init__(self, left: np.ndarray, right: np.ndarray, streams: int, frames: int):
        self.left, self.right, self.frames = left, right, frames
        self.sequences = len(left) // frames
        self.position = [s * frames // streams for s in range(streams)]

    def place(self, frame_id: int, stream: int) -> tuple:
        q, t = stream % self.sequences, self.position[stream]
        self.position[stream] = (t + 1) % self.frames
        return q * self.frames + t, q, t


def frame_source(traffic: dict, seed: int, h: int, w: int, scene: dict):
    """The frames a run submits, made from ``seed``, as the traffic's
    ``source`` says."""
    kind = traffic.get("source", "pairs")
    if kind == "pairs":
        return Pairs(*frame_pool(seed, traffic["pool"], h, w, scene["d_max"],
                                 scene["n_objects"]))
    if kind == "sequence":
        left, right = sequence_pool(seed, traffic["sequences"], traffic["frames"], h, w,
                                    scene["d_max"], scene["n_objects"], traffic["motion"])
        return Sequences(left, right, traffic["streams"], traffic["frames"])
    raise ValueError(f"unknown frame source {kind!r}")


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
    sequence: Optional[int] = None        # the video and its frame, where the
    index: Optional[int] = None           # source plays videos


@dataclasses.dataclass
class Window:
    seconds: float
    t_start: float
    t_end: float
    records: list                          # every frame submitted in the window
    strays: int                            # deliveries matching no submission
    primed: dict = dataclasses.field(default_factory=dict)   # stream -> its last
                                                             # priming Record

    def delivered_in_window(self) -> int:
        return sum(1 for r in self.records
                   if r.frame is not None and r.frame.ok and r.t_done <= self.t_end)

    def failed(self) -> int:
        return sum(1 for r in self.records if r.frame is None or not r.frame.ok)

    def latencies_ms(self) -> list:
        """Per frame submitted in the window; a failed or lost frame is inf."""
        return [r.frame.latency_s * 1e3 if r.frame is not None and r.frame.ok
                else float("inf") for r in self.records]

    def predecessors(self) -> dict:
        """frame id -> the Record its stream submitted just before it (a
        stream's first window frame follows its last priming frame)."""
        before = dict(self.primed)
        out = {}
        for r in sorted(self.records, key=lambda r: r.frame_id):
            out[r.frame_id] = before.get(r.stream)
            before[r.stream] = r
        return out


class _Loop:
    def __init__(self, svc, source, traffic, first_id: int):
        self.svc, self.source = svc, source
        self.traffic = traffic
        self.ids = itertools.count(first_id)
        self.records: dict = {}
        self.strays = 0

    def submit(self, stream: int) -> None:
        fid = next(self.ids)
        idx, seq, t = self.source.place(fid, stream)
        self.records[fid] = Record(fid, stream, idx, sequence=seq, index=t)
        self.svc.submit(fid, self.source.left[idx], self.source.right[idx], stream_id=stream)

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


def prime(svc, source, traffic: dict) -> dict:
    """Run ``prime_rounds`` rounds of every slot, then wait until all are
    back; returns each stream's last priming Record."""
    loop = _Loop(svc, source, traffic, first_id=-10**9)
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
    return {r.stream: r for r in sorted(loop.records.values(), key=lambda r: r.frame_id)}


def closed_loop(svc, source, traffic: dict, seconds: float,
                wait_after_close: float, primed: Optional[dict] = None) -> Window:
    """The measured window: ``seconds`` of closed-loop load, then a wait of
    up to ``wait_after_close`` seconds for the frames still in flight.
    ``primed``: what :func:`prime` returned."""
    loop = _Loop(svc, source, traffic, first_id=0)
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
    return Window(seconds, t_start, t_end, list(loop.records.values()), loop.strays,
                  dict(primed or {}))
