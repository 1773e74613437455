"""The output check passes the program and fails the control.

The control is the plain reference computed in bfloat16, the precision
below the float32 the configurations state, put in the program's place.
Here at 100x130 with 64 disparities on the CPU; the readings at the
cells' own sizes on the chip, and the limits set from them, are in
PERF.md.
"""
import time

import pytest

from benchmarks.chip import check, harness
from benchmarks.chip.tests.small import small_cell


@pytest.mark.parametrize("workload", ["tsukuba.fleet8", "kitti.stream1"])
def test_program_passes_and_the_bfloat16_control_fails(workload):
    cell = small_cell(workload, 100, 130, 63, 50.0)
    keep: dict = {}
    res = harness.run_cell(cell, 2**31 + 17, 2.0, False, t_process=time.monotonic(),
                           device=None, wait_after_close=30.0, keep=keep)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    limit = cell.config["check"]["worst_frame_mismatch"]
    control = harness.reference_outputs(cell.config, *keep["pool"],
                                        sorted(keep["references"]), "bfloat16")
    window = keep["window"]
    numbers = check.compare(check.substituted(window.records, control), keep["references"],
                            cell.config["check"], window.strays)
    assert numbers["worst_frame_mismatch"][0] > limit
    assert check.correct(numbers) is False
