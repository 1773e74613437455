"""StereoService's spans, per-frame timeline and compile counter.

* every delivered frame's :class:`~repro.serving.tracing.FrameTiming` is
  ordered and its parts sum to ``latency_s``;
* wave-mates share the wave index and the program stamps; a slot recovered
  by the contained retry carries its sub-wave's stamps;
* a profiler trace holds one ``stereo.support.run`` / ``stereo.dense.run``
  / ``stereo.emit.readback`` annotation per wave, with the wave index;
* ``compiles_after_warmup`` stays 0 over steady waves and rises on a new
  bucket shape.
"""
import tempfile
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from repro.configs.elas_stereo import SYNTH
from repro.data.stereo import synthetic_stereo_pair
from repro.serving import FaultPlan, FaultSpec, StereoService
from repro.serving.tracing import PART_KIND, FrameTiming, span

P = SYNTH.params
H, W = 40, 64
PROGRAM_STAMPS = ("build_start", "build_end", "support_dispatch",
                  "support_ready", "dense_dispatch", "dense_ready",
                  "emit_start", "readback_end")


def _frames(n, h=H, w=W):
    return [synthetic_stereo_pair(height=h, width=w, d_max=24, seed=s)[:2]
            for s in range(n)]


def _serve(svc, frames):
    with svc:
        for i, (left, right) in enumerate(frames):
            svc.submit(i, left, right, stream_id=i % 2)
        done = svc.collect(len(frames), timeout=300, strict=True)
    return sorted(done, key=lambda c: c.frame_id)


def _assert_timeline(c):
    t = c.timing
    assert isinstance(t, FrameTiming)
    stamps = [getattr(t, s) for s in FrameTiming.STAMPS]
    assert stamps == sorted(stamps), f"frame {c.frame_id}: {t}"
    parts = t.parts()
    assert list(parts) == list(PART_KIND)
    assert sum(parts.values()) == pytest.approx(c.latency_s, abs=1e-6)
    assert t.delivered - t.submit == pytest.approx(c.latency_s, abs=1e-6)


@pytest.mark.parametrize("batch,in_order", [(1, False), (4, False), (4, True)])
def test_timeline_is_ordered_and_sums_to_latency(batch, in_order):
    svc = StereoService(P, batch=batch, wave_linger=0.5, in_order=in_order)
    svc.warmup([(H, W)])
    done = _serve(svc, _frames(8))
    assert all(c.ok for c in done)
    for c in done:
        _assert_timeline(c)
    assert len({c.timing.wave for c in done}) == 8 // batch


def test_wave_mates_share_the_wave_and_its_program_stamps():
    svc = StereoService(P, batch=4, wave_linger=0.5)
    svc.warmup([(H, W)])
    done = _serve(svc, _frames(8))
    by_wave = {}
    for c in done:
        by_wave.setdefault(c.timing.wave, []).append(c.timing)
    assert sorted(len(v) for v in by_wave.values()) == [4, 4]
    for timings in by_wave.values():
        for s in PROGRAM_STAMPS:
            assert len({getattr(t, s) for t in timings}) == 1, s
    # the second wave's programs ran after the first's were dispatched
    first, second = (by_wave[k][0] for k in sorted(by_wave))
    assert second.support_dispatch >= first.support_dispatch
    assert second.dense_dispatch >= first.dense_ready


def test_retried_frame_carries_its_sub_waves_stamps():
    plan = FaultPlan([FaultSpec(stage="dense", wave=0, times=1)])
    svc = StereoService(P, batch=2, wave_linger=0.5, fault_plan=plan)
    svc.warmup([(H, W)])
    done = _serve(svc, _frames(2))
    assert all(c.ok for c in done) and svc.stats().retried == 2
    a, b = (c.timing for c in done)
    assert a.wave == b.wave == 0
    # the wave's own support run, then one single-frame dense run per slot
    assert a.support_dispatch == b.support_dispatch
    assert a.dense_dispatch != b.dense_dispatch
    for c in done:
        _assert_timeline(c)
        assert c.timing.dense_dispatch > c.timing.support_ready


def test_profiler_trace_holds_one_span_per_wave_with_its_index():
    svc = StereoService(P, batch=2, wave_linger=0.5)
    svc.warmup([(H, W)])
    import jax

    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            done = _serve(svc, _frames(6))
        finally:
            jax.profiler.stop_trace()
        (path,) = Path(d).rglob("*.xplane.pb")
        data = ProfileData.from_file(str(path))
    waves = sorted({c.timing.wave for c in done})
    assert len(waves) == 3
    seen = {}
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("stereo."):
                    stats = dict(e.stats)
                    seen.setdefault(e.name, []).append(stats.get("wave"))
    for name in ("stereo.support.run", "stereo.dense.run",
                 "stereo.emit.readback", "stereo.assemble.build"):
        assert sorted(seen.get(name, [])) == waves, name
    assert len(seen["stereo.submit"]) == 6


def test_compiles_after_warmup_zero_when_steady_and_counted_on_a_new_shape():
    svc = StereoService(P, batch=1)
    svc.warmup([(H, W)])
    with svc:
        for i, (left, right) in enumerate(_frames(3)):
            svc.submit(i, left, right)
        svc.collect(3, timeout=300, strict=True)
        steady = svc.stats()
        left, right = _frames(1, h=H + 8)[0]
        svc.submit(3, left, right)
        svc.collect(1, timeout=300, strict=True)
        after = svc.stats()
    assert steady.compiles_after_warmup == 0 and steady.compiles_by_stage == ()
    assert after.compiles_after_warmup > 0
    by_stage = dict(after.compiles_by_stage)
    assert by_stage.get("support", 0) > 0 and by_stage.get("dense", 0) > 0
    assert sum(by_stage.values()) == after.compiles_after_warmup


def test_span_stamps_the_monotonic_clock_around_its_body():
    import time

    t0 = time.monotonic()
    with span("stereo.test", wave=1) as s:
        t1 = time.monotonic()
    assert t0 <= s.start <= t1 <= s.end <= time.monotonic()
