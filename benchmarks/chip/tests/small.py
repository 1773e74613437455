"""A benchmark cell cut to a size the CPU runs in seconds, for the tests."""
import copy

from benchmarks.chip import harness

# A warm-start video mix: 4 cameras, 1 frame each in flight, over 2 videos
# of 8 frames, waves of 2.
VIDEO = {"loop": "closed", "source": "sequence", "streams": 4, "in_flight_per_stream": 1,
         "sequences": 2, "frames": 8, "motion": 2, "prime_rounds": 2,
         "service": {"batch": 2, "depth": 2, "wave_linger": 0.05,
                     "warm_start": True, "warm_band": 8}}


def small_cell(workload: str, height: int, width: int, disp_max: int, d_max: float):
    cell = harness.load_cell(workload)
    cfg = copy.deepcopy(cell.config)
    cfg["height"], cfg["width"] = height, width
    cfg["params"]["disp_max"] = disp_max
    cfg["scene"]["d_max"] = d_max
    cell.config = cfg
    return cell


def small_video_cell(monkeypatch):
    """``kitti.fleet8`` cut to 100x130 with 64 disparities, playing
    :data:`VIDEO`, with the stand-in warm reference of ``warm_stub.py`` as
    its reference module.  At this size the service keeps some warm frames
    and re-runs others cold."""
    from benchmarks.chip.tests import warm_stub

    cell = small_cell("kitti.fleet8", 100, 130, 63, 50.0)
    cell.traffic = copy.deepcopy(VIDEO)
    monkeypatch.setattr(harness, "reference_module", lambda config: warm_stub)
    return cell
