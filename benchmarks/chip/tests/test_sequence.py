"""The video frame source, and the check's two right answers for a warm-start
video run: the source from the seed, the paired pool as it was, cuts the
service's detector sees, stream positions that carry on from priming, the
check's score of a warm answer against its prior, and an unbroken warm run
judged correct through both references.  On the CPU at a small size, with
``warm_stub.py`` as the reference module."""
import hashlib
import inspect
import json
import time
import types
from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import check, driver, harness
from benchmarks.chip.tests.small import VIDEO, small_video_cell
from repro.data.stereo import synthetic_stereo_sequence
from repro.serving import StereoService
from repro.serving.warmstart import frame_thumbnail, prior_disagreement, scene_change_score

SEED = 2**31 + 7
CONFIGS = Path(__file__).resolve().parents[1] / "configs"
# sha256 of driver.frame_pool(2**31 + 5, 16, 60, 80, 24.0, 4), left then
# right, as the benchmark made it before videos were added
PAIRS_SHA256 = "8a1d94ea620dbcea74ad0ba25dc27ce00a6ff8ef519bd013dbb1e90c6c484ef4"
STAT_METRIC = "warm_reruns_from_stats"


def test_the_paired_pool_and_its_order_are_what_they_were():
    src = driver.frame_source({"pool": 16}, 2**31 + 5, 60, 80, {"d_max": 24.0, "n_objects": 4})
    assert isinstance(src, driver.Pairs)
    assert hashlib.sha256(src.left.tobytes() + src.right.tobytes()).hexdigest() == PAIRS_SHA256
    assert [src.place(i, i % 3) for i in range(-5, 40)] == [(i % 16, None, None)
                                                            for i in range(-5, 40)]


def test_the_sequence_source_is_made_from_the_seed():
    args = (2, 6, 40, 64, 24.0, 4, 2)
    left, right = driver.sequence_pool(SEED, *args)
    again = driver.sequence_pool(SEED, *args)
    other = driver.sequence_pool(SEED + 1, *args)
    assert left.shape == right.shape == (12, 40, 64) and left.dtype == np.uint8
    np.testing.assert_array_equal(left, again[0])
    np.testing.assert_array_equal(right, again[1])
    assert not np.array_equal(left, other[0])
    # video 1 is the generator's pan on the second seed drawn, cut half way
    seed = np.random.SeedSequence(SEED).generate_state(2, np.uint64)[1]
    video = synthetic_stereo_sequence(6, 40, 64, 24.0, 4, 2, cut_at=3, seed=int(seed))
    for t, (l, r, _) in enumerate(video):
        np.testing.assert_array_equal(left[6 + t], l)
        np.testing.assert_array_equal(right[6 + t], r)


def _scene(name):
    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    return cfg["height"], cfg["width"], cfg["scene"]["d_max"]


@pytest.mark.parametrize("h,w,d_max,frames", [
    (60, 80, 24.0, VIDEO["frames"]),
    (*_scene("elas-kitti"), 30),
    (*_scene("elas-tsukuba"), 30),
])
def test_the_cut_and_the_wrap_trip_the_scene_change_detector(h, w, d_max, frames):
    threshold = inspect.signature(StereoService).parameters["scene_change_threshold"].default
    left, _ = driver.sequence_pool(SEED, 1, frames, h, w, d_max, 4, 2)
    thumbs = [frame_thumbnail(f) for f in left]
    # score[t]: frame t against the frame before it in the cycle (t = 0: the wrap)
    score = [scene_change_score(thumbs[t], thumbs[t - 1]) for t in range(frames)]
    cuts = {0, frames // 2}
    assert min(score[t] for t in cuts) > threshold, score
    assert max(score[t] for t in range(frames) if t not in cuts) < threshold, score


def test_streams_start_staggered_and_play_their_video_in_a_cycle():
    src = driver.Sequences(np.zeros((12, 1, 1)), np.zeros((12, 1, 1)), streams=4, frames=6)
    assert src.position == [0, 1, 3, 4]
    places = [src.place(i, s) for i, s in enumerate([0, 1, 2, 3] * 3)]
    assert places[:4] == [(0, 0, 0), (7, 1, 1), (3, 0, 3), (10, 1, 4)]
    assert places[-1] == (6 + 0, 1, 0)              # stream 3: 4, 5, then the wrap
    assert src.position == [3, 4, 0, 1]


@pytest.fixture(scope="module")
def video_run():
    """One warm-start video run; a stand-in metric reads the service's
    counters from the ``stats`` the metrics are given."""
    with pytest.MonkeyPatch.context() as mp:
        cell = small_video_cell(mp)
        cell.end_to_end = cell.end_to_end + [{"name": STAT_METRIC, "unit": "frames"}]
        load = harness.load_module

        def load_module(path):
            if path.stem == STAT_METRIC:
                return types.SimpleNamespace(read=lambda ctx: ctx["stats"].warm_reruns)
            return load(path)

        mp.setattr(harness, "load_module", load_module)
        keep: dict = {}
        res = harness.run_cell(cell, SEED, 2.0, False, t_process=time.monotonic(),
                               device=None, wait_after_close=30.0, keep=keep)
        mp.setattr(harness, "load_module", load)
        yield cell, res, keep


def test_an_unbroken_warm_run_is_correct_through_the_warm_reference(video_run):
    cell, res, keep = video_run
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    modes = list(keep["modes"].values())
    assert modes.count("warm") > 0 and modes.count("cold") > 0
    # the counters reach the metrics; the service re-ran warm frames past
    # its bound cold, and those were judged by their cold answers
    assert res["metrics"][STAT_METRIC]["value"] > 0
    # without the warm answers the same frames fail
    refs = keep["references"]
    numbers, _ = check.compare(keep["window"].records, refs.cold, lambda r: None,
                               cell.config["check"])
    assert not check.correct(numbers)


def test_the_check_scores_a_warm_answer_as_the_service_states():
    rng = np.random.default_rng(SEED)
    for invalid_share in (0.0, 0.3, 1.0):
        prior = rng.uniform(0, 63, (37, 53)).astype(np.float32)
        disp = (prior + rng.normal(0, 3, prior.shape)).astype(np.float32)
        prior[rng.random(prior.shape) < invalid_share] = -1.0
        disp[rng.random(disp.shape) < 0.2] = -1.0
        assert check.disagreement(disp, prior, 64, -1.0) == pytest.approx(
            prior_disagreement(disp, prior, 64), rel=1e-6)
    prior = np.full((8, 8), 10.0, np.float32)
    assert check.warm_stands(prior + 9.5, prior, 64, -1.0, 0.15)
    assert not check.warm_stands(prior + 9.7, prior, 64, -1.0, 0.15)


def test_stream_positions_carry_on_from_priming_into_the_window(video_run):
    _, _, keep = video_run
    window, frames = keep["window"], VIDEO["frames"]
    assert sorted(window.primed) == list(range(VIDEO["streams"]))
    before = window.predecessors()
    for r in window.records:
        prev = before[r.frame_id]
        assert prev.stream == r.stream and prev.sequence == r.sequence
        assert r.sequence == r.stream % VIDEO["sequences"]
        assert r.index == (prev.index + 1) % frames
    firsts = {r.stream: r for r in sorted(window.records, key=lambda r: -r.frame_id)}
    for s, r in firsts.items():
        assert before[r.frame_id] is window.primed[s]
