"""Shared arithmetic of the service metrics: the per-frame timelines that
``StereoService`` attaches to its delivered frames (``CompletedFrame.timing``,
``time.monotonic()`` stamps), read over the whole measured window.

A frame's latency splits into host work, program runs and waits.  A program
run is dispatch to ready; its device start is the later of its dispatch and
the ready stamp of the run that became ready just before it (the chip runs
one program at a time), and the part before that start is device wait.
Ready order, not dispatch order: a program whose inputs are still being
uploaded lets one dispatched after it run first.  Where the program
attaches no timeline, :func:`summary` returns None.
"""
from __future__ import annotations

# (part, first stamp, last stamp, kind), in timeline order
PARTS = (
    ("submit", "submit", "enqueued", "host"),
    ("ingest_wait", "enqueued", "build_start", "wait"),
    ("build", "build_start", "build_end", "host"),
    ("support_queue", "build_end", "support_dispatch", "wait"),
    ("support_run", "support_dispatch", "support_ready", "program"),
    ("dense_queue", "support_ready", "dense_dispatch", "wait"),
    ("dense_run", "dense_dispatch", "dense_ready", "program"),
    ("emit_queue", "dense_ready", "emit_start", "wait"),
    ("readback", "emit_start", "readback_end", "host"),
    ("deliver", "readback_end", "finished", "host"),
    ("hold", "finished", "delivered", "wait"),
)
STAGES = ("support", "dense")


def _runs(timings) -> list:
    """Every program run once, in dispatch order: [dispatch, ready, wave,
    stage, device start]."""
    seen = {}
    for t in timings:
        for stage in STAGES:
            d, r = getattr(t, f"{stage}_dispatch"), getattr(t, f"{stage}_ready")
            seen[(d, r, stage)] = t.wave
    runs = sorted([d, r, w, stage, d] for (d, r, stage), w in seen.items())
    by_ready = sorted(runs, key=lambda run: run[1])
    for prev, run in zip(by_ready, by_ready[1:]):
        run[4] = max(run[0], prev[1])
    return runs


def summary(window) -> dict | None:
    """Per frame delivered without error by the window's end (of those
    submitted in it): the mean of each part and of each kind, in ms; the
    program runs of every frame the window submitted; None where no
    delivered frame carries a timeline."""
    timed = [r.frame for r in window.records
             if r.frame is not None and getattr(r.frame, "timing", None) is not None]
    frames = [f for f in timed if f.ok and f.timing.delivered <= window.t_end]
    if not frames:
        return None
    runs = _runs(f.timing for f in timed)
    start = {(run[0], run[1], run[3]): run[4] for run in runs}
    sums = {name: 0.0 for name, *_ in PARTS}
    sums.update(support_device_wait=0.0, dense_device_wait=0.0)
    for f in frames:
        t = f.timing
        for name, a, b, _ in PARTS:
            sums[name] += getattr(t, b) - getattr(t, a)
        for stage in STAGES:
            d, r = getattr(t, f"{stage}_dispatch"), getattr(t, f"{stage}_ready")
            wait = start[(d, r, stage)] - d
            sums[f"{stage}_device_wait"] += wait
            sums[f"{stage}_run"] -= wait
    n = len(frames)
    parts = {k: v / n * 1e3 for k, v in sums.items()}
    kinds = {"host": 0.0, "program": 0.0, "wait": 0.0}
    for name, _, _, kind in PARTS:
        kinds[kind] += parts[name]
    kinds["wait"] += parts["support_device_wait"] + parts["dense_device_wait"]
    return {
        "frames": n,
        "latency_ms": sum(f.latency_s for f in frames) / n * 1e3,
        "parts": parts,
        "kinds": kinds,
        "runs": runs,
        "timings": [f.timing for f in timed],
    }
