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
"""
from __future__ import annotations

import dataclasses

import numpy as np

EXACT = ("failed_frames", "undelivered", "misdelivered")


def mismatch(got: np.ndarray, want: np.ndarray) -> float:
    if got is None or got.shape != want.shape:
        return 1.0
    return float(np.count_nonzero(got != want)) / want.size


def compare(records, references: dict, limits: dict, strays: int = 0) -> dict:
    """name -> (value, limit) for every number compared."""
    worst = 0.0
    failed = undelivered = 0
    misdelivered = strays
    for r in records:
        if r.frame is None:
            undelivered += 1
            continue
        misdelivered += r.duplicates
        if not r.frame.ok:
            failed += 1
            continue
        worst = max(worst, mismatch(r.frame.disparity, references[r.pool_index]))
    return {
        "worst_frame_mismatch": (worst, limits["worst_frame_mismatch"]),
        "failed_frames": (failed, 0),
        "undelivered": (undelivered, 0),
        "misdelivered": (misdelivered, 0),
    }


def substituted(records, outputs: dict) -> list:
    """``records`` with ``outputs`` (pool index -> disparity) in place of
    every frame the program delivered: how the control is put in the
    program's place."""
    return [dataclasses.replace(r, frame=dataclasses.replace(
        r.frame, disparity=outputs[r.pool_index])) if r.frame is not None else r
            for r in records]


def correct(numbers: dict) -> bool:
    return all(limit is not None and value <= limit
               for value, limit in numbers.values())
