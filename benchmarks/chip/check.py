"""The comparison that decides ``correct``.

Every frame submitted in the window is an answer that is due.  Each one
that came back is compared, pixel for pixel, with the plain reference's
disparity for the pair it was made from; the numbers compared are:

* ``worst_frame_mismatch``: over the frames delivered, the largest share
  of a frame's pixels whose disparity differs from the reference's
  (a frame of the wrong shape counts 1.0);
* ``failed_frames``: frames delivered with an error;
* ``undelivered``: frames that never came back, within the wait past the
  window's close;
* ``misdelivered``: deliveries that match no submission (an unknown frame
  id, another stream's id) or repeat one.

Each has its limit in the configuration file's ``check`` (the last three
are exact, limit 0).

A warm-start service may give a frame one of two answers: the cold one of
its pair, or, where the same stream's frame just before it was delivered
ok, the warm one seeded by that frame's delivered disparity.  The service
states when each is right: it writes a stream's warm state only on
in-sequence delivery, seeds a warm frame only from its immediate
predecessor, and re-runs a warm frame cold where the frame's
:func:`disagreement` with its prior is over ``rerun_threshold * num_disp``.
So the warm answer is right only where the warm reference's own
disagreement is within that bound (:func:`warm_stands`); anywhere else the
cold one is.  A frame's mismatch is the least over its right answers; the
numbers compared are the same four.
"""
from __future__ import annotations

import dataclasses

import numpy as np

EXACT = ("failed_frames", "undelivered", "misdelivered")


def mismatch(got: np.ndarray, want: np.ndarray) -> float:
    if got is None or got.shape != want.shape:
        return 1.0
    return float(np.count_nonzero(got != want)) / want.size


def disagreement(disp: np.ndarray, prior: np.ndarray, num_disp: int,
                 invalid: float, stride: int = 4) -> float:
    """How far a warm answer lies from the prior it was seeded by, in
    disparity levels, as the service states its post-hoc check: the mean,
    over every ``stride``-th row and column where the prior is valid, of
    ``|disp - prior|``, an invalid answer pixel counting ``num_disp``;
    ``num_disp`` where the prior is invalid everywhere."""
    d = np.asarray(disp)[::stride, ::stride]
    m = np.asarray(prior)[::stride, ::stride]
    care = m != invalid
    if not care.any():
        return float(num_disp)
    gap = np.where(d == invalid, float(num_disp), np.abs(d - m))
    return float(gap[care].mean())


def warm_stands(answer: np.ndarray, prior: np.ndarray, num_disp: int, invalid: float,
                rerun_threshold: float) -> bool:
    """Whether a warm answer is one the service may deliver: one it does
    not re-run cold."""
    return disagreement(answer, prior, num_disp, invalid) <= rerun_threshold * num_disp


def compare(records, cold, warm, limits: dict, strays: int = 0) -> tuple:
    """(numbers, modes): ``numbers`` name -> (value, limit) for every number
    compared; ``modes`` frame id -> ``"cold"`` or ``"warm"``, the answer
    each frame delivered ok was judged by.  ``cold(r)``: the cold answer
    to ``r``'s pair; ``warm(r)``: its warm answer where that is a right
    one, else None, asked for only where the cold one lies beyond the
    limit."""
    limit = limits["worst_frame_mismatch"]
    worst = 0.0
    failed = undelivered = 0
    misdelivered = strays
    modes = {}
    for r in records:
        if r.frame is None:
            undelivered += 1
            continue
        misdelivered += r.duplicates
        if not r.frame.ok:
            failed += 1
            continue
        value, mode = mismatch(r.frame.disparity, cold(r)), "cold"
        if value > limit:
            want = warm(r)
            if want is not None:
                value_warm = mismatch(r.frame.disparity, want)
                if value_warm < value:
                    value, mode = value_warm, "warm"
        modes[r.frame_id] = mode
        worst = max(worst, value)
    numbers = {
        "worst_frame_mismatch": (worst, limit),
        "failed_frames": (failed, 0),
        "undelivered": (undelivered, 0),
        "misdelivered": (misdelivered, 0),
    }
    return numbers, modes


def substituted(records, outputs: dict) -> list:
    """``records`` with ``outputs`` (frame id -> disparity) in place of
    every frame the program delivered that has one: how the control is put
    in the program's place."""
    return [dataclasses.replace(r, frame=dataclasses.replace(
        r.frame, disparity=outputs[r.frame_id]))
            if r.frame is not None and r.frame_id in outputs else r
            for r in records]


def correct(numbers: dict) -> bool:
    return all(limit is not None and value <= limit
               for value, limit in numbers.values())
