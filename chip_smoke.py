#!/usr/bin/env python3
"""Chip smoke test: the served iELAS stereo path on one TPU, checked.

    python chip_smoke.py

Runs in ONE process on one chip and fails (non-zero exit, no result line)
unless every phase passes:

1. device    -- JAX's default backend is ``tpu`` and the kernel registry
                resolves to ``pallas_tpu`` (``IELAS_BACKEND`` must be unset);
2. kernels   -- ``ielas_disparity`` on seeded New Tsukuba-sized frames
                (480x640, D=64) and one KITTI-sized frame (375x1242,
                D=128) with ``backend="pallas_tpu"`` agrees with the plain
                XLA reference ``backend="ref"``;
3. served    -- ``StereoService`` (batch 4) serves 2 streams x 8 frames,
                all ok, no retry, no compile after warm-up, each output
                equal to the single-frame ``pallas_tpu`` program;
4. warm      -- a warm-start video stream takes the warm path and stays
                within 0.10 bad-pixel rate of the cold result.

The last line of stdout is ``{"ok": true, "device": {...}}``.  Timings
printed on the way are set-up costs of this run (compilation included),
not throughput measurements.
"""
from __future__ import annotations

import functools
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.elas_stereo import KITTI, TSUKUBA  # noqa: E402
from repro.core import pipeline  # noqa: E402
from repro.data.stereo import synthetic_stereo_pair, synthetic_stereo_sequence  # noqa: E402
from repro.kernels.registry import get_backend, resolve_dispatch  # noqa: E402
from repro.launch.compile_cache import place_compile_cache  # noqa: E402
from repro.serving import StereoService  # noqa: E402

KERNEL = "pallas_tpu"
SERVED_STREAMS, SERVED_FRAMES, SERVED_BATCH = 2, 8, 4
# pallas_tpu vs ref: identical integer math; only Mosaic's log/exp may
# round differently from XLA's, so allow a sliver of pixels to move.
MAX_DIFF_FRACTION = 0.005
MAX_BAD_PIXEL_GAP = 0.01
WARM_MARGIN = 0.10


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def log(msg: str) -> None:
    print(msg, flush=True)


def device_phase(kernel: str) -> None:
    dev = jax.devices()[0]
    log(f"device: platform={dev.platform} device_kind={dev.device_kind} "
        f"count={len(jax.devices())} default_backend={jax.default_backend()}")
    backend, tile = resolve_dispatch(None, None)
    log(f"dispatch: backend={backend} tile={tile} "
        f"gather={get_backend(backend).tiling.default_gather}")
    require(backend == kernel, f"resolved backend {backend!r} != {kernel!r}")


def frame_program(p, backend: str, h: int, w: int):
    """Compile ``ielas_disparity`` for one shape; returns (program, seconds)."""
    fn = jax.jit(functools.partial(pipeline.ielas_disparity, p=p, backend=backend))
    spec = jax.ShapeDtypeStruct((h, w), jnp.float32)
    t0 = time.perf_counter()
    compiled = fn.lower(spec, spec).compile()
    return compiled, time.perf_counter() - t0


def check_map(disp, h: int, w: int, p, what: str) -> None:
    require(disp.shape == (h, w) and disp.dtype == np.float32,
            f"{what}: got {disp.shape} {disp.dtype}")
    require(bool(np.isfinite(disp).all()), f"{what}: non-finite values")
    valid = disp != p.invalid
    require(bool(valid.any()), f"{what}: no valid pixel")
    require(bool(((disp[valid] >= p.disp_min) & (disp[valid] <= p.disp_max)).all()),
            f"{what}: disparity outside [{p.disp_min}, {p.disp_max}]")


def kernel_phase(cfg, d_max: float, seeds, kernel: str):
    """pallas_tpu vs ref on seeded frames; returns the kernel program."""
    p, h, w = cfg.params, cfg.height, cfg.width
    progs = {}
    for backend in (kernel, "ref"):
        progs[backend], secs = frame_program(p, backend, h, w)
        log(f"compile: {cfg.name} ielas_disparity backend={backend} "
            f"{h}x{w} D={p.num_disp}: {secs:.2f} s")
    for seed in seeds:
        il, ir, gt = synthetic_stereo_pair(height=h, width=w, d_max=d_max, seed=seed)
        left, right = jnp.asarray(il, jnp.float32), jnp.asarray(ir, jnp.float32)
        out = {b: np.asarray(progs[b](left, right)) for b in progs}
        for b, disp in out.items():
            check_map(disp, h, w, p, f"{cfg.name} seed={seed} {b}")
        diff = int(np.sum(out[kernel] != out["ref"]))
        bpr = {b: float(pipeline.bad_pixel_rate(out[b], gt)) for b in out}
        log(f"kernels: {cfg.name} seed={seed} {kernel} vs ref: "
            f"{diff}/{h * w} pixels differ; bad-pixel rate "
            f"{kernel}={bpr[kernel]!r} ref={bpr['ref']!r}")
        require(diff <= MAX_DIFF_FRACTION * h * w,
                f"{cfg.name} seed={seed}: {diff} pixels differ from ref")
        require(abs(bpr[kernel] - bpr["ref"]) <= MAX_BAD_PIXEL_GAP,
                f"{cfg.name} seed={seed}: bad-pixel rates {bpr}")
    return progs[kernel]


def served_phase(cfg, d_max: float, single, kernel: str):
    p, h, w = cfg.params, cfg.height, cfg.width
    pairs = {
        (s, f): synthetic_stereo_pair(height=h, width=w, d_max=d_max,
                                      seed=1000 + s * SERVED_FRAMES + f)[:2]
        for s in range(SERVED_STREAMS) for f in range(SERVED_FRAMES)
    }
    with StereoService(p, batch=SERVED_BATCH, depth=2, max_pending=64) as svc:
        t0 = time.perf_counter()
        svc.warmup([(h, w)])
        log(f"compile: {cfg.name} service warm-up batch={SERVED_BATCH}: "
            f"{time.perf_counter() - t0:.2f} s")
        t0 = time.perf_counter()
        for f in range(SERVED_FRAMES):
            for s in range(SERVED_STREAMS):
                svc.submit(f, *pairs[s, f], stream_id=s)
        done = svc.collect(len(pairs), timeout=600.0, strict=True)
        wall = time.perf_counter() - t0
        st = svc.stats()
    n_ok = sum(c.ok for c in done)
    log(f"served: {cfg.name} {n_ok}/{len(pairs)} ok in {wall:.2f} s; "
        f"backend={st.backend} tile={st.tile} waves={st.waves} "
        f"occupancy={st.wave_occupancy!r} cache={st.cache_hits}h/"
        f"{st.cache_misses}m retried={st.retried} "
        f"failed_frames={st.failed_frames}")
    for c in done:
        require(c.ok, f"served stream={c.stream_id} frame={c.frame_id}: {c.error}")
    require(len(done) == len(pairs), f"{len(done)}/{len(pairs)} frames delivered")
    require(st.backend == kernel, f"service ran backend {st.backend!r}")
    require(st.failed_frames == 0 and st.retried == 0,
            f"failed_frames={st.failed_frames} retried={st.retried}")
    require(st.cache_misses == 0, f"{st.cache_misses} compiles after warm-up")
    differing = 0
    for c in done:
        left, right = (jnp.asarray(x, jnp.float32) for x in pairs[c.stream_id, c.frame_id])
        differing += int(np.sum(c.disparity != np.asarray(single(left, right))))
    log(f"served: {differing} pixels differ from the single-frame {kernel} program")
    require(differing == 0, "served output differs from the single-frame program")


def warm_phase(cfg, d_max: float, single):
    p, h, w = cfg.params, cfg.height, cfg.width
    seq = synthetic_stereo_sequence(8, height=h, width=w, d_max=d_max,
                                    motion=2, cut_at=4, seed=3)
    gaps = []
    with StereoService(p, batch=1, depth=2, warm_start=True, warm_band=8) as svc:
        t0 = time.perf_counter()
        svc.warmup([(h, w)])
        log(f"compile: {cfg.name} warm service warm-up: "
            f"{time.perf_counter() - t0:.2f} s")
        for i, (il, ir, gt) in enumerate(seq):
            # one frame at a time: frame t+1 is seeded by delivered frame t
            svc.submit(i, il, ir)
            got = svc.collect(1, timeout=300.0, strict=True)[0]
            require(got.ok, f"warm frame {i}: {got.error}")
            check_map(got.disparity, h, w, p, f"warm frame {i}")
            cold = np.asarray(single(jnp.asarray(il, jnp.float32),
                                     jnp.asarray(ir, jnp.float32)))
            gaps.append(float(pipeline.bad_pixel_rate(got.disparity, gt))
                        - float(pipeline.bad_pixel_rate(cold, gt)))
        st = svc.stats()
    log(f"warm: warm_frames={st.warm_frames} cold_frames={st.cold_frames} "
        f"scene_changes={st.scene_changes} warm_reruns={st.warm_reruns} "
        f"warm_resets={st.warm_resets} worst bad-pixel gap warm-cold="
        f"{max(gaps)!r}")
    require(st.warm_frames > 0, "no frame took the warm path")
    require(max(gaps) <= WARM_MARGIN, f"warm bad-pixel gaps {gaps}")


def main() -> int:
    require("IELAS_BACKEND" not in os.environ,
            "IELAS_BACKEND is set; the smoke test checks the default dispatch")
    require(jax.default_backend() == "tpu",
            f"JAX found no TPU (default backend {jax.default_backend()!r})")
    t_start = time.perf_counter()
    log(f"compile cache: {place_compile_cache()}")
    device_phase(KERNEL)
    single = kernel_phase(TSUKUBA, 60.0, (0, 1), KERNEL)
    kernel_phase(KITTI, 120.0, (0,), KERNEL)
    served_phase(TSUKUBA, 60.0, single, KERNEL)
    warm_phase(TSUKUBA, 60.0, single)
    log(f"total: {time.perf_counter() - t_start:.2f} s")

    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
