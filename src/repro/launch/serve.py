"""Serving launchers: LM generation and the continuous-batching stereo service.

  PYTHONPATH=src python -m repro.launch.serve lm --arch yi-9b --reduced \\
      --requests 4 --prompt-len 16 --max-new 24
  PYTHONPATH=src python -m repro.launch.serve stereo --frames 8 --batch 4 \\
      --height 120 --width 160
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.configs.elas_stereo import SYNTH
from repro.data.stereo import synthetic_stereo_pair
from repro.models.model import LMModel
from repro.serving.engine import ServeEngine
from repro.launch.compile_cache import place_compile_cache
from repro.serving.stereo_service import StereoService
from repro.serving.tracing import PART_KIND


def serve_lm(args) -> int:
    cfg = get_config(args.arch, reduced=args.reduced)
    if cfg.frontend != "none":
        raise SystemExit(f"{args.arch} has a stub frontend; LM serving demo "
                         "uses token archs")
    model = LMModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, batch=args.batch,
                         max_len=args.prompt_len + args.max_new + 1)
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(0, cfg.vocab_size, size=rng.integers(4, args.prompt_len + 1))
        for _ in range(args.requests)
    ]
    t0 = time.monotonic()
    outs = engine.generate(prompts, max_new_tokens=args.max_new)
    dt = time.monotonic() - t0
    tokens = sum(len(o) for o in outs)
    print(f"{args.requests} requests, {tokens} tokens in {dt:.2f}s "
          f"({tokens/dt:.1f} tok/s)")
    for i, o in enumerate(outs[:4]):
        print(f"  req{i}: {o[:12]}{'...' if len(o) > 12 else ''}")
    return 0


def serve_stereo(args) -> int:
    p = SYNTH.params
    svc = StereoService(p, batch=args.batch, depth=2,
                        max_pending=max(64, args.frames)).start()
    svc.warmup([(args.height, args.width)])
    frames = [
        synthetic_stereo_pair(height=args.height, width=args.width,
                              d_max=40, seed=s)[:2]
        for s in range(args.frames)
    ]
    # submit everything up front so waves fill to `batch` (a serial
    # submit-then-wait loop would dispatch padded single-frame waves)
    t0 = time.monotonic()
    for i, (l, r) in enumerate(frames):
        svc.submit(i, l, r)
    done = svc.collect(args.frames, timeout=600.0)
    wall = time.monotonic() - t0
    st = svc.stats()
    svc.stop()
    ok = sum(c.ok for c in done)
    dev = jax.devices()[0]
    print(f"{ok}/{args.frames} frames ok in {wall:.2f}s -> "
          f"{len(done) / wall:.1f} fps ({args.height}x{args.width}, "
          f"batch={args.batch}, platform={dev.platform}, "
          f"device_kind={dev.device_kind}, backend={st.backend})")
    print(f"waves={st.waves} occupancy={st.wave_occupancy:.2f} "
          f"cache={st.cache_hits}h/{st.cache_misses}m "
          f"p95={st.latency_p95_ms:.0f}ms "
          f"compiles_after_warmup={st.compiles_after_warmup}")
    parts = [c.timing.parts() for c in done if c.timing is not None]
    if parts:
        print("latency parts, mean ms: " + " ".join(
            f"{k}={sum(p[k] for p in parts) / len(parts) * 1e3:.2f}"
            for k in PART_KIND))
    for c in done:
        if not c.ok:
            print(f"frame {c.frame_id} failed: {c.error}", file=sys.stderr)
    return 0 if ok == args.frames else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="mode", required=True)

    lm = sub.add_parser("lm")
    lm.add_argument("--arch", choices=ARCH_IDS, default="yi-9b")
    lm.add_argument("--reduced", action="store_true", default=True)
    lm.add_argument("--requests", type=int, default=4)
    lm.add_argument("--batch", type=int, default=2)
    lm.add_argument("--prompt-len", type=int, default=16)
    lm.add_argument("--max-new", type=int, default=16)

    st = sub.add_parser("stereo")
    st.add_argument("--frames", type=int, default=8)
    st.add_argument("--batch", type=int, default=1)
    st.add_argument("--height", type=int, default=120)
    st.add_argument("--width", type=int, default=160)

    args = ap.parse_args(argv)
    place_compile_cache()
    return serve_lm(args) if args.mode == "lm" else serve_stereo(args)


if __name__ == "__main__":
    raise SystemExit(main())
