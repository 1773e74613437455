"""Work the dense matching stage needs, counted from shapes and the configuration.

The count is the algorithm's, not an implementation's: it does not depend
on how many disparity steps a kernel sweeps or masks.

* Operations: for every pixel of both views, one 16-byte descriptor SAD
  per candidate disparity, at most ``num_candidates = grid_vector_k + 2 *
  plane_radius + 1`` of them.  A SAD byte is an absolute difference and an
  accumulate: 2 operations, counted like the multiply-accumulate of the
  int8 peak it is rated against.
* Bytes: the two descriptor images (16 int8 per pixel), the two views'
  grid vectors (``grid_vector_k`` float32 per grid cell) and the two
  float32 disparity maps, each read or written once.
"""
from __future__ import annotations

import json
from pathlib import Path

DESC_BYTES = 16          # int8 descriptor bytes per pixel
OPS_PER_DESC_BYTE = 2    # |a - b| and the accumulate
F32 = 4

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def num_candidates(params: dict) -> int:
    return params["grid_vector_k"] + 2 * params["plane_radius"] + 1


def dense_ops(height: int, width: int, params: dict) -> int:
    """Operations of one frame's dense matching, both views."""
    return 2 * height * width * num_candidates(params) * DESC_BYTES * OPS_PER_DESC_BYTE


def dense_bytes(height: int, width: int, params: dict) -> int:
    """Bytes one frame's dense matching has to move, both views."""
    cells = (height // params["grid_size"]) * (width // params["grid_size"])
    descriptors = 2 * height * width * DESC_BYTES
    grid_vectors = 2 * cells * params["grid_vector_k"] * F32
    disparities = 2 * height * width * F32
    return descriptors + grid_vectors + disparities


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip; an unknown device is an error."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def dense_least_seconds(height: int, width: int, params: dict,
                        device_kind: str) -> tuple[float, str]:
    """(least seconds one frame's dense matching needs, the bound that binds)."""
    pk = peaks(device_kind)
    t_ops = dense_ops(height, width, params) / pk["int8_ops_per_s"]
    t_bytes = dense_bytes(height, width, params) / pk["hbm_bytes_per_s"]
    return (t_bytes, "memory") if t_bytes >= t_ops else (t_ops, "compute")
