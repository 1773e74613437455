"""A run with the timed path broken underneath comes out not correct.

Each fault is planted in the served path, where the answer is produced,
and the rest of a run (set-up, the closed-loop window, the reference,
the check) runs as ``run.py`` makes it, on the CPU at a small size and
without the harness's look for a chip.
"""
import dataclasses
import time

import jax.numpy as jnp
import pytest

from benchmarks.chip import harness
from benchmarks.chip.tests.small import small_cell
from repro.serving import stereo_service

LOST_FRAME = 3


def _wave_fault(monkeypatch, fault):
    """Wrap every dense wave program's output in ``fault``."""
    build = stereo_service.FrameProgramCache._build

    def broken(self, key, batch):
        progs = build(self, key, batch)
        dense = progs.dense
        return dataclasses.replace(progs, dense=lambda *a: fault(dense(*a)))

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
