"""Fault containment, admission control, and liveness for StereoService.

Every test here is marked ``faults`` (CI runs them as their own job with a
hard timeout): they prove the engine's failure model with the deterministic
:mod:`repro.serving.faults` injection harness --

* a wave-level fault fails only its own frames (containment),
* one bounded retry recovers transients bitwise-exactly,
* a poison frame is quarantined while its wave-mates recover,
* only repeated systemic failure aborts the engine,
* expired work is shed pre-compute and degraded mode engages/clears on
  backlog pressure,
* the non-degraded path stays bitwise identical to the fused single-frame
  program (conformance is never traded for robustness),
* ``collect(strict=True)`` / ``stop(drain=True)`` fail fast with context,
* stage heartbeats expose per-stage liveness.
"""
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.elas_stereo import SYNTH
from repro.core.pipeline import ielas_disparity
from repro.data.stereo import synthetic_stereo_pair
from repro.runtime.fault_tolerance import HeartbeatMonitor
from repro.serving import (
    AdmissionController, FaultInjected, FaultPlan, FaultSpec, StereoService,
)

pytestmark = pytest.mark.faults

P = SYNTH.params


def _frames(n, h=40, w=64, seed0=0):
    return [
        synthetic_stereo_pair(height=h, width=w, d_max=24, seed=seed0 + s)[:2]
        for s in range(n)
    ]


def _direct(left, right):
    return np.asarray(
        ielas_disparity(jnp.asarray(left, jnp.float32),
                        jnp.asarray(right, jnp.float32), P)
    )


# ---------------------------------------------------------------------------
# harness units (no service, no compiles)
# ---------------------------------------------------------------------------
class TestFaultPlan:
    def test_spec_validation(self):
        with pytest.raises(ValueError):
            FaultSpec(stage="nope")
        with pytest.raises(ValueError):
            FaultSpec(stage="dense", kind="explode")
        with pytest.raises(ValueError):
            FaultSpec(stage="dense", times=0)

    def test_matching_is_an_and_of_conditions(self):
        plan = FaultPlan([FaultSpec(stage="dense", wave=3, request_id=7,
                                    times=None)])
        plan.check("support", 3, (7,))       # wrong stage: no fire
        plan.check("dense", 2, (7,))         # wrong wave: no fire
        plan.check("dense", 3, (5, 6))       # request not riding: no fire
        assert plan.fired(0) == 0
        with pytest.raises(FaultInjected):
            plan.check("dense", 3, (6, 7))
        assert plan.fired(0) == 1

    def test_times_bounds_firings(self):
        plan = FaultPlan([FaultSpec(stage="support", times=2)])
        for _ in range(2):
            with pytest.raises(FaultInjected):
                plan.check("support", 0, (0,))
        plan.check("support", 0, (0,))       # spec exhausted: quiet now
        assert plan.fired(0) == 2

    def test_delay_kind_sleeps_instead_of_raising(self):
        plan = FaultPlan([FaultSpec(stage="dense", kind="delay",
                                    delay_s=0.05, times=1)])
        t0 = time.monotonic()
        plan.check("dense", 0, (0,))         # no raise
        assert time.monotonic() - t0 >= 0.05
        t0 = time.monotonic()
        plan.check("dense", 1, (1,))         # exhausted: no sleep either
        assert time.monotonic() - t0 < 0.05


class _R:
    """Minimal request stand-in for AdmissionController tests."""

    def __init__(self, rid, sid, deadline=None):
        self.request_id = rid
        self.stream_id = sid
        self.deadline = deadline


class TestAdmissionController:
    def test_watermark_validation(self):
        with pytest.raises(ValueError):
            AdmissionController(degrade_watermark=0)
        with pytest.raises(ValueError):
            AdmissionController(degrade_watermark=4, clear_watermark=4)

    def test_expired_work_is_shed(self):
        ctl = AdmissionController()
        reqs = [_R(0, 0, deadline=5.0), _R(1, 0), _R(2, 0, deadline=20.0)]
        admitted, dead = ctl.select(reqs, width=4, now=10.0)
        assert [r.request_id for r in dead] == [0]
        assert [r.request_id for r in admitted] == [1, 2]
        c = ctl.counters()
        assert c["shed"] == c["expired"] == 1
        assert c["shed_by_stream"] == ((0, 1),)

    def test_round_robin_grants_one_slot_per_stream(self):
        ctl = AdmissionController()
        # stream 0 floods with 4 requests; streams 1 and 2 have one each
        reqs = ([_R(i, 0) for i in range(4)]
                + [_R(10, 1), _R(11, 2)])
        admitted, _ = ctl.select(reqs, width=3, now=0.0)
        # one slot per stream before stream 0 gets a second
        assert sorted(r.stream_id for r in admitted) == [0, 1, 2]
        # stream 0's own submission order is preserved
        assert [r.request_id for r in admitted if r.stream_id == 0] == [0]

    def test_rotation_resumes_after_last_served_stream(self):
        ctl = AdmissionController()
        ctl.select([_R(0, 0), _R(1, 1)], width=2, now=0.0)   # last served: 1
        admitted, _ = ctl.select(
            [_R(2, 0), _R(3, 1), _R(4, 2)], width=1, now=0.0
        )
        assert admitted[0].stream_id == 2, "rotation should pass streams 0, 1"

    def test_degraded_hysteresis(self):
        ctl = AdmissionController(degrade_watermark=8, clear_watermark=2)
        assert ctl.update_pressure(7) is False
        assert ctl.update_pressure(8) is True          # engage at watermark
        assert ctl.update_pressure(5) is True          # hysteresis: hold
        assert ctl.update_pressure(2) is False         # clear at low mark
        assert ctl.counters()["degraded_transitions"] == 1

    def test_disabled_without_watermark(self):
        ctl = AdmissionController()
        assert ctl.update_pressure(10_000) is False


class TestHeartbeatMonitor:
    def test_liveness_with_fake_clock(self):
        t = [0.0]
        mon = HeartbeatMonitor(["support", "dense"], timeout=10.0,
                               clock=lambda: t[0])
        assert mon.is_alive("support")       # registration counts as a beat
        t[0] = 5.0
        mon.beat("support", 1)
        t[0] = 12.0
        assert mon.is_alive("support")       # beaten at t=5, within 10
        assert not mon.is_alive("dense")     # silent since t=0
        assert mon.dead_hosts() == ["dense"]
        assert not mon.is_alive("never-registered")

    def test_beat_auto_registers_unknown_host(self):
        t = [0.0]
        mon = HeartbeatMonitor([], timeout=10.0, clock=lambda: t[0])
        mon.beat("late-stage", 0)
        assert mon.is_alive("late-stage")

    def test_straggler_uses_per_step_time(self):
        t = [0.0]
        mon = HeartbeatMonitor(["a", "b", "c"], timeout=1e9,
                               clock=lambda: t[0])
        for host, dt in (("a", 1.0), ("b", 1.0), ("c", 10.0)):
            t[0] = 100.0
            mon.beat(host, 0)
            t[0] = 100.0 + dt
            mon.beat(host, 1)
        assert mon.stragglers() == ["c"]


# ---------------------------------------------------------------------------
# containment in the live engine
# ---------------------------------------------------------------------------
class TestContainment:
    def test_transient_fault_retries_and_recovers_bitwise(self):
        """Wave 0's batched support attempt fails once; the single-frame
        retries recover every slot BITWISE-identically to the fused
        program, and nothing is delivered as failed."""
        frames = _frames(4)
        plan = FaultPlan([FaultSpec(stage="support", wave=0, times=1)])
        svc = StereoService(P, batch=2, wave_linger=0.05, fault_plan=plan)
        svc.warmup([(40, 64)])
        with svc:
            for i, (l, r) in enumerate(frames):
                svc.submit(i, l, r)
            done = svc.collect(4, timeout=300)
        st = svc.stats()
        assert len(done) == 4 and all(c.ok for c in done)
        assert plan.fired(0) == 1
        assert st.retried == 2               # both slots of the failed wave
        assert st.failed_frames == 0
        assert st.completed == 4 and st.pending == 0
        for c in done:
            np.testing.assert_array_equal(
                c.disparity, _direct(*frames[c.frame_id])
            )

    def test_persistent_wave_fault_is_isolated(self):
        """A fault pinned to wave 0 (batched attempt AND retries) fails
        only wave 0's frames; the next wave is untouched and the engine
        stays up."""
        frames = _frames(4)
        plan = FaultPlan([FaultSpec(stage="dense", wave=0, times=None)])
        svc = StereoService(P, batch=2, wave_linger=0.05, fault_plan=plan)
        svc.warmup([(40, 64)])
        with svc:
            for i, (l, r) in enumerate(frames):
                svc.submit(i, l, r)
            done = svc.collect(4, timeout=300)
        st = svc.stats()
        assert len(done) == 4
        failed = sorted(c.frame_id for c in done if not c.ok)
        assert len(failed) == 2, "exactly one wave's frames should fail"
        for c in done:
            if c.ok:
                assert c.disparity is not None
            else:
                assert c.disparity is None
                assert "dense stage failed after retry" in c.error
        assert st.failed_frames == 2 and st.completed == 2
        assert st.pending == 0

    def test_poison_frame_quarantined_wave_mates_recover(self):
        """A request-pinned fault re-fires on the frame's retry wave: that
        one frame fails terminally while its wave-mate recovers bitwise."""
        frames = _frames(2)
        plan = FaultPlan([FaultSpec(stage="dense", request_id=1,
                                    times=None)])
        svc = StereoService(P, batch=2, wave_linger=0.05, fault_plan=plan)
        svc.warmup([(40, 64)])
        with svc:
            for i, (l, r) in enumerate(frames):
                svc.submit(i, l, r)
            done = svc.collect(2, timeout=300)
        st = svc.stats()
        by_id = {c.frame_id: c for c in done}
        assert not by_id[1].ok and by_id[1].disparity is None
        assert by_id[0].ok
        np.testing.assert_array_equal(by_id[0].disparity, _direct(*frames[0]))
        assert st.failed_frames == 1 and st.completed == 1
        assert st.retried == 2               # both slots were retried

    def test_retry_programs_do_not_evict_hot_path(self):
        """The batch-1 fallback program the retry compiles must live
        ALONGSIDE the hot batch-2 program: traffic after the fault stays
        zero-recompile."""
        frames = _frames(6)
        plan = FaultPlan([FaultSpec(stage="support", wave=0, times=1)])
        svc = StereoService(P, batch=2, wave_linger=0.05, fault_plan=plan)
        svc.warmup([(40, 64)])
        with svc:
            for i, (l, r) in enumerate(frames[:2]):
                svc.submit(i, l, r)
            svc.collect(2, timeout=300)
            misses_after_fault = svc.stats().cache_misses
            for i, (l, r) in enumerate(frames[2:], start=2):
                svc.submit(i, l, r)
            done = svc.collect(4, timeout=300)
        st = svc.stats()
        assert len(done) == 4 and all(c.ok for c in done)
        assert misses_after_fault == 1, "retry compiles exactly one batch-1"
        assert st.cache_misses == misses_after_fault, (
            "post-fault traffic must not recompile the hot program"
        )
        assert st.programs_cached == 2       # batch-2 hot + batch-1 fallback

    def test_systemic_failure_aborts_engine(self):
        """Every attempt failing (batched and retry, every wave) is
        systemic: after max_wave_failures consecutive dead waves the
        engine aborts, stop() re-raises, and submit() refuses."""
        frames = _frames(6)
        plan = FaultPlan([FaultSpec(stage="support", times=None)])
        svc = StereoService(P, batch=2, wave_linger=0.05, fault_plan=plan,
                            max_wave_failures=2).start()
        svc.warmup([(40, 64)])
        for i, (l, r) in enumerate(frames):
            try:
                svc.submit(i, l, r)
            except RuntimeError:
                break           # engine already aborted mid-submission: fine
        with pytest.raises(RuntimeError, match="worker failed"):
            svc.stop(drain=True, timeout=60)
        assert isinstance(svc._error, RuntimeError)
        assert "systemic" in str(svc._error)
        with pytest.raises(RuntimeError):
            svc.submit(99, *frames[0])

    def test_isolated_failures_never_count_as_systemic(self):
        """Waves that fail but RECOVER by retry reset the consecutive
        counter: many transient faults in a row never abort the engine."""
        frames = _frames(6)
        plan = FaultPlan([
            FaultSpec(stage="support", wave=w, times=1) for w in range(3)
        ])
        svc = StereoService(P, batch=2, wave_linger=0.05, fault_plan=plan,
                            max_wave_failures=2)
        svc.warmup([(40, 64)])
        with svc:
            for i, (l, r) in enumerate(frames):
                svc.submit(i, l, r)
            done = svc.collect(6, timeout=300)
        assert len(done) == 6 and all(c.ok for c in done)
        assert svc.stats().retried == 6

    def test_in_order_failed_frame_does_not_block_stream(self):
        """With in_order=True a quarantined frame delivers its sequence
        slot as an error frame, so later frames of the stream still come
        out, in order."""
        frames = _frames(4)
        plan = FaultPlan([FaultSpec(stage="dense", request_id=1,
                                    times=None)])
        svc = StereoService(P, batch=2, wave_linger=0.05, in_order=True,
                            fault_plan=plan)
        svc.warmup([(40, 64)])
        with svc:
            for i, (l, r) in enumerate(frames):
                svc.submit(i, l, r)
            done = svc.collect(4, timeout=300)
        order = [c.frame_id for c in done]
        assert order == [0, 1, 2, 3], f"stream order must hold: {order}"
        assert [c.ok for c in done] == [True, False, True, True]


# ---------------------------------------------------------------------------
# admission control in the live engine
# ---------------------------------------------------------------------------
class TestAdmissionInEngine:
    def test_expired_requests_shed_without_compute(self):
        frames = _frames(4)
        svc = StereoService(P, batch=2, wave_linger=0.05)
        svc.warmup([(40, 64)])
        with svc:
            past = time.monotonic() - 1.0
            for i, (l, r) in enumerate(frames):
                svc.submit(i, l, r, deadline=past if i % 2 else None)
            done = svc.collect(4, timeout=300)
        st = svc.stats()
        assert len(done) == 4
        shed = sorted(c.frame_id for c in done if not c.ok)
        assert shed == [1, 3]
        for c in done:
            if not c.ok:
                assert "shed by admission control" in c.error
        assert st.shed == 2 and st.expired == 2
        assert st.failed_frames == 0         # shed is not a compute failure
        assert st.completed == 2 and st.pending == 0

    def test_degraded_mode_engages_and_clears(self):
        """Backlog past the watermark switches waves to the narrowed-band
        dense program; once pressure drains, the mode clears."""
        frames = _frames(2)
        plan = FaultPlan([FaultSpec(stage="dense", kind="delay",
                                    delay_s=0.1, times=None)])
        svc = StereoService(P, batch=1, fault_plan=plan,
                            degrade_watermark=3, clear_watermark=1)
        svc.warmup([(40, 64)])
        with svc:
            for i in range(10):
                svc.submit(i, *frames[i % 2])
            done = svc.collect(10, timeout=300)
        st = svc.stats()
        assert len(done) == 10 and all(c.ok for c in done)
        assert st.degraded_waves > 0, "pressure should engage degraded mode"
        assert st.degraded_waves < st.waves, "early waves ran full quality"
        assert st.degraded is False, "mode must clear once pressure drains"

    def test_non_degraded_path_stays_bitwise_exact(self):
        """A watermark-enabled service that never overloads runs zero
        degraded waves and its output is bitwise identical to the fused
        single-frame program: robustness costs nothing at low load."""
        frames = _frames(3)
        svc = StereoService(P, batch=1, degrade_watermark=50)
        svc.warmup([(40, 64)])
        with svc:
            for i, (l, r) in enumerate(frames):
                svc.submit(i, l, r)
                svc.collect(0, timeout=0.05)     # keep the backlog at ~1
            done = svc.collect(3, timeout=300)
        st = svc.stats()
        assert len(done) == 3
        assert st.degraded_waves == 0
        for c in done:
            np.testing.assert_array_equal(
                c.disparity, _direct(*frames[c.frame_id])
            )


# ---------------------------------------------------------------------------
# fail-fast lifecycle + liveness
# ---------------------------------------------------------------------------
class TestFailFast:
    def test_stop_drain_detects_dead_pipeline_promptly(self):
        """stop(drain=True, timeout=120) on an aborted engine must raise
        within seconds, not sleep out the timeout."""
        frames = _frames(2)
        plan = FaultPlan([FaultSpec(stage="support", times=None)])
        svc = StereoService(P, batch=2, wave_linger=0.05, fault_plan=plan,
                            max_wave_failures=1).start()
        svc.warmup([(40, 64)])
        for i, (l, r) in enumerate(frames):
            svc.submit(i, l, r)
        deadline = time.monotonic() + 30.0   # wait for the abort to land
        while svc._error is None and time.monotonic() < deadline:
            time.sleep(0.05)
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="worker failed"):
            svc.stop(drain=True, timeout=120.0)
        assert time.monotonic() - t0 < 10.0, (
            "stop() slept toward its 120s timeout on a dead pipeline"
        )

    def test_collect_total_deadline_and_strict(self):
        """collect()'s timeout is a TOTAL deadline; strict=True raises a
        TimeoutError naming the outstanding frame ids and attaching the
        partial results."""
        frames = _frames(1)
        svc = StereoService(P, batch=1)
        svc.warmup([(40, 64)])
        with svc:
            svc.submit(7, *frames[0])
            done = svc.collect(1, timeout=300)
            assert len(done) == 1
            t0 = time.monotonic()
            out = svc.collect(5, timeout=0.3)       # nothing else coming
            assert time.monotonic() - t0 < 5.0, "timeout must be total"
            assert out == []
            svc.submit(8, *frames[0], deadline=None)
            with pytest.raises(TimeoutError) as ei:
                # ask for more than will ever arrive
                svc.collect(3, timeout=2.0, strict=True)
        msg = str(ei.value)
        assert "got" in msg and "outstanding frame ids" in msg
        assert len(ei.value.partial) <= 2

    def test_stage_liveness_reported_while_running(self):
        frames = _frames(1)
        svc = StereoService(P, batch=1)
        svc.warmup([(40, 64)])
        with svc:
            svc.submit(0, *frames[0])
            svc.collect(1, timeout=300)
            st = svc.stats()
        assert dict(st.stage_liveness) == {
            "assemble": True, "support": True, "dense": True, "emit": True,
        }

    def test_stats_before_start_has_no_liveness(self):
        svc = StereoService(P, batch=1)
        st = svc.stats()
        assert st.stage_liveness == ()
