"""The reduction from a device trace to the per-layer metrics' inputs."""
import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import traces

DATA = Path(__file__).resolve().parent / "data"


def _trace():
    # device ops (ns): [0,10] and [5,15] overlap, [30,40] alone
    return {
        "window": [0.0, 50.0],
        "devices": [{
            "plane": "/device:TPU:0",
            "modules": [["jit_support_wave(11)", 0.0, 15.0],
                        ["jit_dense_wave(22)", 28.0, 14.0],
                        ["jit_dense_wave(22)", 45.0, 10.0]],   # runs past the window
            "ops": [["%a = f32[4]{0} fusion(f32[4] %x)", 0.0, 10.0],
                    ["%b = s32[4]{0} fusion(f32[4] %y)", 5.0, 10.0],
                    ["%a = f32[4]{0} fusion(f32[4] %x)", 30.0, 10.0],
                    ["%a = f32[4]{0} fusion(f32[4] %x)", 46.0, 8.0]],
        }],
        "host": [["Transpose::ExecuteChunk", 14.0, 12.0],
                 ["long wait", 0.0, 1000.0],
                 ["H2D Dispatch", 41.0, 2.0]],
    }


def test_busy_programs_and_breakdown():
    r = traces.reduce(_trace())
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(50e-9)
    # union [0,15] + [30,40] + [46,50] (clipped at the window's end)
    assert r["busy_s"] == pytest.approx(29e-9)
    assert r["programs"]["support_wave"] == {"count": 1, "device_s": pytest.approx(15e-9)}
    # the execution that runs past the window's end is not counted
    assert r["programs"]["dense_wave"] == {"count": 1, "device_s": pytest.approx(10e-9)}
    assert r["device_ops"][0] == ["support_wave:a fusion f32[4]", pytest.approx(10e-9)]
    assert ["dense_wave:a fusion f32[4]", pytest.approx(10e-9)] in r["device_ops"]


def test_idle_gaps_named_by_the_host_event_that_overlaps_most():
    r = traces.reduce(_trace())
    # gaps: [15,30] (15 ns), [40,46] (6 ns); "long wait" is over ten times
    # longer than either and names neither
    assert r["idle_gaps"] == [["Transpose::ExecuteChunk", pytest.approx(15e-9)],
                              ["H2D Dispatch", pytest.approx(6e-9)]]


def test_no_device_plane_reads_nothing():
    t = _trace()
    t["devices"] = []
    r = traces.reduce(t)
    assert r["devices"] == 0 and r["busy_s"] == 0.0 and r["programs"] == {}


def test_merge_clips_and_joins():
    assert traces.merge([(5, 8), (0, 3), (2, 4), (9, 20)], 1, 12) == [[1, 4], [5, 8], [9, 12]]


def _recorded():
    with gzip.open(DATA / "tsukuba_fleet8_slice.json.gz", "rt") as f:
        return json.load(f)


def test_recorded_trace_busy_matches_a_brute_force_timeline():
    """On 200 ms of a real TPU v5e trace: the busy time equals a 1-us
    timeline painted op by op, and the program attribution adds up."""
    t = _recorded()
    r = traces.reduce(t)
    lo, hi = t["window"]
    paint = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for _, s, d in t["devices"][0]["ops"]:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            paint[int((a - lo) // 1000): int(np.ceil((b - lo) / 1000))] = True
    assert r["busy_s"] == pytest.approx(paint.sum() * 1e-6, rel=0.01)
    progs = r["programs"]
    assert set(progs) == {"support_wave", "dense_wave"}
    assert sum(p["device_s"] for p in progs.values()) <= r["busy_s"] + 1e-12
    assert 0 < r["busy_s"] < r["window_s"]
    assert len(r["device_ops"]) == traces.TOP and len(r["idle_gaps"]) == traces.TOP
    assert all(n.startswith(("support_wave:", "dense_wave:")) for n, _ in r["device_ops"])
