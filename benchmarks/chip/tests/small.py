"""A benchmark cell cut to a size the CPU runs in seconds, for the tests."""
import copy

from benchmarks.chip import harness


def small_cell(workload: str, height: int, width: int, disp_max: int, d_max: float):
    cell = harness.load_cell(workload)
    cfg = copy.deepcopy(cell.config)
    cfg["height"], cfg["width"] = height, width
    cfg["params"]["disp_max"] = disp_max
    cfg["scene"]["d_max"] = d_max
    cell.config = cfg
    return cell
