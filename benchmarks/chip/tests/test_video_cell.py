"""The warm-start KITTI video cell, ``kitti-video.fleet8``: it loads, its check
against the plain video reference (``reference/ielas_video.py``) passes an
unbroken run and catches the warm faults of ``test_faults.py``, its bfloat16
control fails on the warm frames alone, and its four metrics read a fixed
context.  On the CPU at 100x130 with 64 disparities, playing
``small.VIDEO``."""
import copy
import dataclasses
import time
import types

import pytest

from benchmarks.chip import check, driver, harness, warm_work
from benchmarks.chip.tests.small import VIDEO, small_cell
from benchmarks.chip.tests.test_faults import WARM_FAULTS
from repro.serving import stereo_service

CELL = "kitti-video.fleet8"
SEED = 2**31 + 29
# A re-run bound, as a share of num_disp, that some warm frames of the
# small run lie past (at the service's 0.15 none does here).
TIGHT_BOUND = 0.06
METRICS = ("warm_share", "warm_score_pct", "warm_dense_stage_ms", "warm_dense_roofline_pct")


def small_kitti_video_cell():
    cell = small_cell(CELL, 100, 130, 63, 50.0)
    cell.traffic = copy.deepcopy(VIDEO)
    return cell


def test_the_cell_loads_with_its_reference_traffic_and_metrics():
    cell = harness.load_cell(CELL)
    assert cell.workload["chips"] == 1
    assert cell.config["reference"] == "ielas_video"
    assert cell.config["params"]["redun_order"] == "sequential"
    assert (cell.config["height"], cell.config["width"]) == (375, 1242)
    assert cell.traffic["source"] == "sequence" and cell.traffic["in_flight_per_stream"] == 1
    assert cell.traffic["service"]["warm_start"] is True
    assert {m["name"] for m in cell.end_to_end} == {"frames_per_s", "setup_s"}
    assert [m["name"] for m in cell.per_layer] == list(METRICS)
    mod = harness.reference_module(cell.config)
    assert hasattr(mod, "warm_disparity")
    # the rest of the configuration is elas-kitti's
    kitti = harness.load_cell("kitti.fleet8").config
    assert {k: v for k, v in cell.config["params"].items() if k != "redun_order"} == kitti["params"]


@pytest.fixture(scope="module")
def video_run():
    cell = small_kitti_video_cell()
    keep: dict = {}
    res = harness.run_cell(cell, SEED, 2.0, False, t_process=time.monotonic(),
                           device=None, wait_after_close=30.0, keep=keep)
    return cell, res, keep


def test_an_unbroken_run_is_correct_and_mostly_warm(video_run):
    cell, res, keep = video_run
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    modes = list(keep["modes"].values())
    assert modes.count("warm") > modes.count("cold") > 0


def test_the_bfloat16_control_fails_on_the_warm_frames_alone(video_run):
    cell, _, keep = video_run
    limit = cell.config["check"]["worst_frame_mismatch"]
    assert check.correct(harness.control_numbers(cell, keep)) is False
    warm = {fid for fid, mode in keep["modes"].items() if mode == "warm"}
    window = keep["window"]
    only_warm = dataclasses.replace(
        window, records=[r for r in window.records if r.frame_id in warm])
    numbers = harness.control_numbers(cell, dict(keep, window=only_warm))
    assert numbers["worst_frame_mismatch"][0] > limit
    assert check.correct(numbers) is False


def _tight_bound(monkeypatch) -> list:
    """Serve with :data:`TIGHT_BOUND` as the re-run bound; returns a list
    that receives the service's last counters."""
    kwargs, stats, seen = driver.service_kwargs, stereo_service.StereoService.stats, []
    monkeypatch.setattr(driver, "service_kwargs",
                        lambda tr: dict(kwargs(tr), rerun_threshold=TIGHT_BOUND))
    monkeypatch.setattr(stereo_service.StereoService, "stats",
                        lambda self: seen.append(stats(self)) or seen[-1])
    return seen


def test_an_unbroken_run_past_a_tight_bound_reruns_and_is_correct(monkeypatch):
    seen = _tight_bound(monkeypatch)
    res = harness.run_cell(small_kitti_video_cell(), SEED, 2.0, False,
                           t_process=time.monotonic(), device=None, wait_after_close=30.0)
    assert res["correct"], res["check"]
    assert 0 < seen[-1].warm_reruns < seen[-1].warm_frames


@pytest.mark.parametrize("fault", sorted(WARM_FAULTS))
def test_a_broken_warm_run_is_not_correct(fault, monkeypatch):
    plant, caught_by = WARM_FAULTS[fault]
    plant(monkeypatch)
    if fault == "rerun_skipped":
        # a skipped re-run shows only where some warm frame lies past the bound
        _tight_bound(monkeypatch)
    res = harness.run_cell(small_kitti_video_cell(), SEED, 2.0, False,
                           t_process=time.monotonic(), device=None, wait_after_close=30.0)
    assert res["correct"] is False, res["check"]
    number = res["check"][caught_by]
    assert number["value"] > number["limit"], res["check"]


def _ctx(stats, programs=None, device_kind="TPU v5 lite"):
    return {"cell": harness.load_cell(CELL), "stats": stats, "device_kind": device_kind,
            "trace": None if programs is None else {"programs": programs},
            "log": lambda msg: None}


def test_each_metric_reads_a_fixed_context():
    stats = types.SimpleNamespace(warm_frames=90, cold_frames=10, warm_reruns=5,
                                  warm_scored=90, warm_score_sum=27.0)
    prog = {"dense_warm_wave": {"count": 10, "device_s": 0.4}}
    read = {m: harness.load_module(harness.HERE / "metrics" / f"{m}.py").read for m in METRICS}
    assert read["warm_share"](_ctx(stats)) == pytest.approx(85.0)
    assert read["warm_score_pct"](_ctx(stats)) == pytest.approx(30.0)
    assert read["warm_dense_stage_ms"](_ctx(stats, prog)) == pytest.approx(10.0)  # 0.4 s / 40 slots
    # 375x1242, band 8: 17 candidates; bytes 2*465750*(16 + 4 + 4)
    assert warm_work.warm_dense_ops(375, 1242, 8) == 2 * 465750 * 17 * 16 * 2
    assert warm_work.warm_dense_bytes(375, 1242) == 22_356_000
    least, bound = warm_work.warm_dense_least_seconds(375, 1242, 8, "TPU v5 lite")
    assert bound == "memory" and least == pytest.approx(22_356_000 / 819e9)
    assert read["warm_dense_roofline_pct"](_ctx(stats, prog)) == pytest.approx(
        100.0 * least / 10e-3)
    # nothing to read: no frames, no counters (a service without them), no trace
    empty = types.SimpleNamespace(warm_frames=0, cold_frames=0, warm_reruns=0)
    assert read["warm_share"](_ctx(empty)) is None
    assert read["warm_score_pct"](_ctx(empty)) is None
    assert read["warm_dense_stage_ms"](_ctx(stats)) is None
    assert read["warm_dense_roofline_pct"](_ctx(stats, prog, device_kind=None)) is None
