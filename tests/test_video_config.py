"""The warm-start video configuration: libelas's redundancy order, and the
served warm path against the benchmark's plain video reference.

* ``redun_order="sequential"``'s closed form equals libelas's nested loops
  (``Elas::removeRedundantSupportPoints``, copied plainly in the video
  reference) on seeded grids; ``"parallel"``, the default, gives what it gave before the option;
  other values are refused.
* ``StereoService`` with ``warm_start`` and the sequential order, on a small
  ``synthetic_stereo_sequence`` driven one frame at a time, delivers each
  frame as ``benchmarks/chip/reference/ielas_video.py`` computes it: cold
  frames as its ``disparity``, warm frames as its ``warm_disparity``
  seeded by the frame delivered before; a warm frame forced past the
  re-run bound comes back cold, and nothing compiles after ``warmup()``.
"""
import dataclasses
import hashlib
import importlib.util
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.filtering import filter_support, remove_redundant
from repro.core.params import ElasParams
from repro.data.stereo import synthetic_stereo_sequence
from repro.serving import StereoService
from repro.serving.faults import FaultPlan, FaultSpec
from repro.serving.warmstart import corrupt_disparity, prior_disagreement

REFERENCE = Path(__file__).resolve().parents[1] / "benchmarks/chip/reference/ielas_video.py"


def _reference():
    name = "bench.reference.ielas_video"      # the benchmark harness's name for it
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, REFERENCE)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def _grid(seed, shape, invalid_share, levels=5):
    rng = np.random.default_rng(seed)
    g = rng.integers(0, levels, shape).astype(np.float32)
    g[rng.random(shape) < invalid_share] = -1.0
    return g


@pytest.mark.parametrize("invalid_share", [0.0, 0.25, 0.5])
@pytest.mark.parametrize("threshold", [0, 1, 2])
@pytest.mark.parametrize("shape", [(1, 17), (17, 1), (6, 9), (15, 26)])
def test_sequential_order_equals_libelas_loops(shape, threshold, invalid_share):
    p = ElasParams(redun_order="sequential", redun_threshold=threshold)
    for seed in range(4):
        g = _grid(seed, shape, invalid_share)
        got = np.asarray(remove_redundant(jnp.asarray(g), p))
        want = _reference().libelas_redundancy(g, 1, threshold)
        np.testing.assert_array_equal(got, want)


def test_sequential_order_thins_a_plane_to_every_other_node():
    g = np.full((6, 8), 7.0, np.float32)
    got = np.asarray(remove_redundant(jnp.asarray(g), ElasParams(redun_order="sequential")))
    # the column pass keeps rows 0, 2, 4, 5; the row pass then columns 0, 2, 4, 6, 7
    keep = np.zeros_like(g, bool)
    keep[np.ix_([0, 2, 4, 5], [0, 2, 4, 6, 7])] = True
    np.testing.assert_array_equal(got >= 0, keep)
    # the parallel order keeps only the four corners
    par = np.asarray(remove_redundant(jnp.asarray(g), ElasParams()))
    assert (par >= 0).sum() == 4


# sha256 of remove_redundant's and filter_support's outputs under the
# parallel order, as they were before the order became an option
PARALLEL_PINS = {
    (0, 1): ("170b5c5be80a071bfdfbeb983b8e8ee3a7a8dc557dfde2ed7397844306d6a761",
             "f1d7b58af14341c96c5da50997a3713e2ff4f5792b06f402f837913509cfca60"),
    (0, 2): ("2a0a22f445dda2bbbcf54defae51be2547bd238b46169bbb586bc0d79c93622e",
             "a08c0b7e07d18e30e26e682639eb8c4c2cd72afdf797a8a36ff0ed9d2e7cb9d8"),
    (1, 1): ("e7e955ed68afd0ac505eecc9272de7522014fd49fe130c14d31a5c8d20590f50",
             "e7e955ed68afd0ac505eecc9272de7522014fd49fe130c14d31a5c8d20590f50"),
    (1, 2): ("e4ea4440b798613f05bea1b548048cf4ff3eb0bdd05c04ca59b614d92c765acb",
             "e4ea4440b798613f05bea1b548048cf4ff3eb0bdd05c04ca59b614d92c765acb"),
    (2, 1): ("2c8d4b2e975721a82cdd9967d4c5d7e38f191df408ee8ea66511753e21afe596",
             "52bd10f5e2249bd25707f8b664a86612dd7122a7a35a22cb1d7551a7c7fde26d"),
    (2, 2): ("675cad209eceee71a351e9268d31b39a0624fe25a530c4e44f13a9e662b7aee1",
             "bb98ec86031854821cc97a734c546f1d20d4d66cebf93bb514d5e04cc3ae8f13"),
}


@pytest.mark.parametrize("seed,max_dist", sorted(PARALLEL_PINS))
def test_parallel_order_is_unchanged(seed, max_dist):
    rng = np.random.default_rng(seed)
    g = rng.integers(0, 6, (15, 26)).astype(np.float32)
    g[rng.random(g.shape) < 0.3] = -1
    p = ElasParams(redun_max_dist=max_dist, redun_threshold=1)
    assert p.redun_order == "parallel"
    got = [np.asarray(f(jnp.asarray(g), p)).tobytes() for f in (remove_redundant, filter_support)]
    assert tuple(hashlib.sha256(b).hexdigest() for b in got) == PARALLEL_PINS[(seed, max_dist)]


@pytest.mark.parametrize("kwargs", [
    {"redun_order": "sequential", "redun_max_dist": 2},
    {"redun_order": "sequential", "redun_max_dist": 5},
    {"redun_order": "libelas"},
    {"redun_order": ""},
])
def test_bad_redundancy_settings_are_refused(kwargs):
    with pytest.raises(ValueError):
        ElasParams(**kwargs)


# ---------------------------------------------------------------- service
H, W, D_MAX = 100, 130, 50.0
PARAMS = dict(disp_max=63, redun_order="sequential")
WARM_BAND = 8
FORCED_RERUN = 2            # request id whose warm prior is corrupted


@pytest.fixture(scope="module")
def video_run():
    """One stream of a cut video, one frame at a time, through a warm-start
    service whose frame ``FORCED_RERUN`` is seeded by a corrupted prior."""
    frames = synthetic_stereo_sequence(8, H, W, D_MAX, 4, 2, cut_at=4, seed=3)
    p = ElasParams(**PARAMS)
    plan = FaultPlan([FaultSpec(stage="warm", kind="corrupt_prior",
                                request_id=FORCED_RERUN)])
    svc = StereoService(p, batch=1, depth=2, warm_start=True, warm_band=WARM_BAND,
                        fault_plan=plan)
    svc.warmup([(H, W)])
    out = []
    with svc:
        for t, (left, right, _) in enumerate(frames):
            svc.submit(t, left, right)
            out += svc.collect(1, timeout=600)
    return frames, p, svc, svc.stats(), out


def test_served_video_equals_the_plain_video_reference(video_run):
    frames, p, svc, st, out = video_run
    ref = _reference()
    rp = ref.Params(**dataclasses.asdict(p))
    assert [c.frame_id for c in out] == list(range(len(frames))) and all(c.ok for c in out)
    warm_only = []
    for t, (c, (left, right, _)) in enumerate(zip(out, frames)):
        cold = np.asarray(ref.disparity(jnp.asarray(left, jnp.float32),
                                        jnp.asarray(right, jnp.float32), rp))
        if np.array_equal(c.disparity, cold):
            continue
        assert t > 0, "a stream's first frame is cold"
        warm = np.asarray(ref.warm_disparity(
            jnp.asarray(left, jnp.float32), jnp.asarray(right, jnp.float32),
            jnp.asarray(out[t - 1].disparity), rp, WARM_BAND))
        np.testing.assert_array_equal(c.disparity, warm, err_msg=f"frame {t}")
        warm_only.append(t)
    assert st.warm_reruns < st.warm_frames
    assert len(warm_only) == st.warm_frames - st.warm_reruns
    assert FORCED_RERUN not in warm_only and 0 not in warm_only and 4 not in warm_only


def test_a_rerun_after_warmup_compiles_nothing(video_run):
    _, _, _, st, _ = video_run
    assert st.warm_reruns >= 1
    assert st.compiles_after_warmup == 0, st.compiles_by_stage


def test_the_warm_score_counters_add_up(video_run):
    frames, p, svc, st, out = video_run
    ref = _reference()
    rp = ref.Params(**dataclasses.asdict(p))
    limit = svc.rerun_threshold * p.num_disp
    assert st.warm_frames + st.cold_frames == st.submitted == len(frames)
    assert st.warm_scored == st.warm_frames
    # each warm frame's score: its warm answer against the prior it was
    # seeded by (for the forced one, the corrupted prior)
    scores = []
    for t, (left, right, _) in enumerate(frames):
        if t in (0, 4):                    # the stream's start and the cut
            continue
        prior = out[t - 1].disparity
        if t == FORCED_RERUN:
            prior = corrupt_disparity(prior, p.disp_max)
        warm = np.asarray(ref.warm_disparity(
            jnp.asarray(left, jnp.float32), jnp.asarray(right, jnp.float32),
            jnp.asarray(prior), rp, WARM_BAND))
        scores.append(prior_disagreement(warm, prior, p.num_disp) / limit)
    assert len(scores) == st.warm_scored
    assert st.warm_score_sum == pytest.approx(sum(scores), rel=1e-9)
    assert sum(s > 1.0 for s in scores) == st.warm_reruns
