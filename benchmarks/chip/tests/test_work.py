"""The dense matching's work counts, against hand-computed KITTI and Tsukuba."""
import json
from pathlib import Path

import pytest

from benchmarks.chip import work

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def _cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_kitti_counts():
    c = _cfg("elas-kitti")
    h, w, p = c["height"], c["width"], c["params"]
    assert work.num_candidates(p) == 25                    # 20 + 2*2 + 1
    # 2 views x 375*1242 px x 25 candidates x 16 bytes x 2 ops
    assert work.dense_ops(h, w, p) == 2 * 465750 * 25 * 16 * 2 == 745_200_000
    # descriptors 2*465750*16 + grid vectors 2*(18*62)*20*4 + maps 2*465750*4
    assert work.dense_bytes(h, w, p) == 14_904_000 + 178_560 + 3_726_000
    least, bound = work.dense_least_seconds(h, w, p, "TPU v5 lite")
    assert bound == "memory"
    assert least == pytest.approx(18_808_560 / 819e9)


def test_tsukuba_counts():
    c = _cfg("elas-tsukuba")
    h, w, p = c["height"], c["width"], c["params"]
    assert work.dense_ops(h, w, p) == 2 * 307200 * 25 * 16 * 2 == 491_520_000
    # 2*307200*16 + 2*(24*32)*20*4 + 2*307200*4
    assert work.dense_bytes(h, w, p) == 9_830_400 + 122_880 + 2_457_600
    least, bound = work.dense_least_seconds(h, w, p, "TPU v5 lite")
    assert bound == "memory"
    assert least == pytest.approx(12_410_880 / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("TPU v99")
