"""Work the warm dense scan needs, counted from shapes and the service's warm band.

The count is the algorithm's, as ``work.py`` counts the cold scan's: a
warm frame matches each pixel of both views over the ``2 * warm_band + 1``
disparities of the band around its prior, whatever a kernel sweeps or masks.

* Operations: per pixel and view, one 16-byte descriptor SAD per band
  disparity, 2 operations a byte (``work.OPS_PER_DESC_BYTE``).
* Bytes: the two descriptor images (16 int8 per pixel), the two views'
  float32 priors and the two float32 disparity maps, each read or written
  once.
"""
from __future__ import annotations

from benchmarks.chip import work


def band_candidates(warm_band: int) -> int:
    return 2 * warm_band + 1


def warm_dense_ops(height: int, width: int, warm_band: int) -> int:
    """Operations of one warm frame's dense matching, both views."""
    return (2 * height * width * band_candidates(warm_band)
            * work.DESC_BYTES * work.OPS_PER_DESC_BYTE)


def warm_dense_bytes(height: int, width: int) -> int:
    """Bytes one warm frame's dense matching has to move, both views."""
    pixels = height * width
    return 2 * pixels * work.DESC_BYTES + 2 * pixels * work.F32 + 2 * pixels * work.F32


def warm_dense_least_seconds(height: int, width: int, warm_band: int,
                             device_kind: str) -> tuple[float, str]:
    """(least seconds one warm frame's dense matching needs, the bound that binds)."""
    pk = work.peaks(device_kind)
    t_ops = warm_dense_ops(height, width, warm_band) / pk["int8_ops_per_s"]
    t_bytes = warm_dense_bytes(height, width) / pk["hbm_bytes_per_s"]
    return (t_bytes, "memory") if t_bytes >= t_ops else (t_ops, "compute")
