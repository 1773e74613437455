"""A run with the timed path broken underneath comes out not correct.

Each fault is planted in the served path, where the answer is produced,
and the rest of a run (set-up, the closed-loop window, the reference,
the check) runs as ``run.py`` makes it, on the CPU at a small size and
without the harness's look for a chip.  The warm faults break a
warm-start video run, whose reference module is ``warm_stub.py``.
"""
import dataclasses
import time

import jax.numpy as jnp
import pytest

from benchmarks.chip import harness
from benchmarks.chip.tests.small import small_cell, small_video_cell
from repro.serving import stereo_service

LOST_FRAME = 3


def _wave_fault(monkeypatch, fault, program="dense"):
    """Wrap every ``program`` wave program's output in ``fault``."""
    build = stereo_service.FrameProgramCache._build

    def broken(self, key, batch):
        progs = build(self, key, batch)
        run = getattr(progs, program)
        if run is None:             # a service without that program
            return progs
        return dataclasses.replace(progs, **{program: lambda *a: fault(run(*a))})

    monkeypatch.setattr(stereo_service.FrameProgramCache, "_build", broken)


def _delivery_fault(monkeypatch, fault):
    finish = stereo_service.StereoService._finish

    def broken(self, req, out, error=None, shed=False):
        if req.frame_id == LOST_FRAME:
            if fault == "lost":
                return
            req.stream_id += 1
        finish(self, req, out, error=error, shed=shed)

    monkeypatch.setattr(stereo_service.StereoService, "_finish", broken)


def _prior_fault(monkeypatch, pick):
    """Seed each warm frame from ``pick(service, request)``, where that gives
    a prior, in place of its own stream's frame just before it."""
    classify = stereo_service.StereoService._classify_warm

    def broken(self, req):
        classify(self, req)
        prior = pick(self, req) if req.warm else None
        if prior is not None:
            req.prior = prior

    monkeypatch.setattr(stereo_service.StereoService, "_classify_warm", broken)


def _other_stream(svc, req):
    with svc._wlock:
        others = [st.disparity for sid, st in sorted(svc._warm_state.items())
                  if sid != req.stream_id]
    return others[0].copy() if others else None


def _two_back(monkeypatch):
    """Seed each warm frame from its stream's frame two before it."""
    deliver = stereo_service.StereoService._deliver

    def keep_two(self, req, out, error=None, shed=False):
        if error is None:
            kept = self.__dict__.setdefault("_two_delivered", {})
            kept[req.stream_id] = (kept.get(req.stream_id, ()) + (out,))[-2:]
        deliver(self, req, out, error, shed)

    def pick(svc, req):
        kept = svc.__dict__.get("_two_delivered", {}).get(req.stream_id, ())
        return kept[0].copy() if len(kept) == 2 else None

    monkeypatch.setattr(stereo_service.StereoService, "_deliver", keep_two)
    _prior_fault(monkeypatch, pick)


# fault -> (how it is planted, the number that has to catch it)
FAULTS = {
    # an answer altered where it is produced
    "answer_altered": (lambda m: _wave_fault(m, lambda d: d.at[0].add(1.0)),
                       "worst_frame_mismatch"),
    # half of each wave left out, its slots given the other half's answers
    "half_wave_left_out": (lambda m: _wave_fault(
        m, lambda d: jnp.concatenate([d[: d.shape[0] // 2]] * 2)), "worst_frame_mismatch"),
    # the wave's slots handed back in the wrong order
    "slots_swapped": (lambda m: _wave_fault(m, lambda d: jnp.roll(d, 1, axis=0)),
                      "worst_frame_mismatch"),
    # a frame that never comes back
    "frame_lost": (lambda m: _delivery_fault(m, "lost"), "undelivered"),
    # a frame delivered under another stream
    "frame_misrouted": (lambda m: _delivery_fault(m, "misrouted"), "misdelivered"),
}


def _rerun_skipped(monkeypatch):
    """Deliver every warm frame warm, the post-hoc check's re-run skipped."""
    monkeypatch.setattr(stereo_service.StereoService, "_posthoc_check",
                        lambda self, req, out, key: (out, None))


# the same, for a warm-start video run
WARM_FAULTS = {
    # a warm frame seeded from another stream's last frame
    "prior_from_another_stream": (lambda m: _prior_fault(m, _other_stream),
                                  "worst_frame_mismatch"),
    # a warm frame seeded from its stream's frame two before it
    "prior_from_two_frames_back": (_two_back, "worst_frame_mismatch"),
    # a warm wave's answer altered where it is produced
    "warm_answer_altered": (lambda m: _wave_fault(m, lambda d: d.at[0].add(1.0), "dense_warm"),
                            "worst_frame_mismatch"),
    # a warm frame past the re-run bound delivered warm, not re-run cold
    "rerun_skipped": (_rerun_skipped, "worst_frame_mismatch"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_run_is_not_correct(fault, monkeypatch):
    plant, caught_by = FAULTS[fault]
    plant(monkeypatch)
    cell = small_cell("tsukuba.fleet8", 60, 80, 31, 24.0)
    res = harness.run_cell(cell, 2**31 + 5, 2.0, False, t_process=time.monotonic(),
                           device=None, wait_after_close=3.0)
    assert res["correct"] is False, res["check"]
    number = res["check"][caught_by]
    assert number["value"] > number["limit"], res["check"]


def test_the_same_run_unbroken_is_correct():
    cell = small_cell("tsukuba.fleet8", 60, 80, 31, 24.0)
    res = harness.run_cell(cell, 2**31 + 5, 2.0, False, t_process=time.monotonic(),
                           device=None, wait_after_close=3.0)
    assert res["correct"] is True, res["check"]


@pytest.mark.parametrize("fault", sorted(WARM_FAULTS))
def test_a_broken_warm_video_run_is_not_correct(fault, monkeypatch):
    plant, caught_by = WARM_FAULTS[fault]
    plant(monkeypatch)
    cell = small_video_cell(monkeypatch)
    res = harness.run_cell(cell, 2**31 + 5, 2.0, False, t_process=time.monotonic(),
                           device=None, wait_after_close=3.0)
    assert res["correct"] is False, res["check"]
    number = res["check"][caught_by]
    assert number["value"] > number["limit"], res["check"]
