"""Benchmark harness: one module per paper table.  Prints
``name,us_per_call,derived`` CSV rows (benchmarks.common.row).

Usage
-----
::

  PYTHONPATH=src python -m benchmarks.run                 # all tables
  PYTHONPATH=src python -m benchmarks.run table4 table5   # a subset

CI smoke mode
-------------
The ``bench-smoke`` CI job runs a tiny QVGA configuration and gates on
dense-stage throughput::

  PYTHONPATH=src python -m benchmarks.run --smoke \
      --json bench-smoke.json \
      --check benchmarks/baseline_ci.json --tolerance 0.30

Flags:

``--smoke``
    preset: table4 + table5 only, QVGA (240x320), a small frame budget --
    finishes in a couple of minutes on a CI runner.
``--height/--width/--frames``
    override the smoke resolution / per-path frame budget.
``--json PATH``
    also write the collected rows as JSON (``{"meta": ..., "rows": ...}``;
    uploaded as the CI artifact).
``--check BASELINE [--tolerance T]``
    compare fps-bearing rows against a checked-in baseline JSON
    (``benchmarks/baseline_ci.json``); exit non-zero if any regresses by
    more than ``T`` (default 0.30, i.e. >30% slower fails).  The baseline
    pins the per-stage breakdown: ``table4/support_stage`` (the streaming
    row-block-tiled support search), ``table4/dense_stage`` (the
    gather-free streaming dense stage) and ``table4/interp_stage`` (the
    paper's regularized interpolation) -- the stages the streaming/tiling
    work optimises -- plus ``table5/video_warm`` (the temporal
    warm-start live-camera scenario: fps with the band-only warm scan,
    self-validation overhead included).

Row-by-row diffing of two artifacts (per-stage speedup table)::

  PYTHONPATH=src python -m benchmarks.compare A.json B.json

(the CI bench-smoke job prints it against the checked-in baseline after
the regression gate).

Regenerating the baseline after an intentional perf change::

  PYTHONPATH=src python -m benchmarks.run --smoke --json /tmp/b.json
  # review, then copy the gated rows into benchmarks/baseline_ci.json
"""
from __future__ import annotations

import argparse
import sys

from benchmarks import common


def _parse_args(argv: list[str]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(
        prog="benchmarks.run", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("tables", nargs="*",
                    help="subset to run (table1..table5, lm); default all")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke preset: table4+table5 at QVGA, tiny budget")
    ap.add_argument("--height", type=int, default=None)
    ap.add_argument("--width", type=int, default=None)
    ap.add_argument("--frames", type=int, default=None,
                    help="frame budget per measured path")
    ap.add_argument("--backend", default=None,
                    help="kernel backend name; default: device-aware probe "
                         "(repro.kernels.registry.default_backend)")
    ap.add_argument("--json", dest="json_path", default=None,
                    help="write collected rows as JSON to this path")
    ap.add_argument("--check", dest="baseline", default=None,
                    help="baseline JSON to gate against (fps rows)")
    ap.add_argument("--tolerance", type=float, default=0.30,
                    help="allowed fractional fps regression (default 0.30)")
    return ap.parse_args(argv)


def main(argv: list[str] | None = None) -> int:
    args = _parse_args(sys.argv[1:] if argv is None else argv)
    which = set(args.tables)
    if args.smoke and not which:
        which = {"table4", "table5"}

    def want(name: str) -> bool:
        return not which or name in which

    height = args.height or (240 if args.smoke else None)
    width = args.width or (320 if args.smoke else None)
    frames = args.frames or (3 if args.smoke else None)
    if bool(height) != bool(width):
        print("--height and --width must be given together", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import place_compile_cache

    place_compile_cache()
    # Resolve the device-aware dispatch once, so the CSV header and the
    # JSON meta state which backend/tile/gather this run actually used.
    from repro.kernels.registry import get_backend, resolve_dispatch

    backend, default_tile = resolve_dispatch(args.backend, None)
    cap = get_backend(backend).tiling
    gather = cap.default_gather
    precision = cap.default_precision
    print(f"# dispatch: backend={backend} default_tile={default_tile} "
          f"gather={gather} precision={precision}", flush=True)

    lines: list[str] = []
    print("name,us_per_call,derived")
    if want("table1"):
        from benchmarks import table1_interp_error
        lines += table1_interp_error.run() or []
    if want("table2"):
        from benchmarks import table2_memory
        lines += table2_memory.run() or []
    if want("table3"):
        from benchmarks import table3_accuracy
        lines += table3_accuracy.run() or []
    if want("table4"):
        from benchmarks import table4_throughput
        kw = {"backend": backend}
        if height:
            kw.update(height=height, width=width)
        if frames:
            kw.update(frames=frames)
        lines += table4_throughput.run(**kw) or []
    if want("table5"):
        from benchmarks import table5_multistream
        kw = {}
        if height:
            kw.update(height=height, width=width)
        if frames:
            kw.update(frames_per_stream=frames)
        if args.smoke:
            kw.update(streams=2, reps=1)
        lines += table5_multistream.run(**kw) or []
        vkw = {}
        if height:
            vkw.update(height=height, width=width)
        if args.smoke:
            vkw.update(frames=12)     # cut at frame 6: recovery in-window
        lines += table5_multistream.run_video(**vkw) or []
    if want("lm"):
        from benchmarks import lm_steps
        lines += lm_steps.run() or []

    records = common.rows_to_records(lines)
    if args.json_path:
        meta = {"smoke": args.smoke, "height": height, "width": width,
                "frames": frames, "backend": backend, "gather": gather,
                "precision": precision, "default_tile": repr(default_tile)}
        common.write_json(args.json_path, records, meta=meta)
        print(f"# wrote {len(records)} rows to {args.json_path}", flush=True)

    if args.baseline:
        failures = common.check_against_baseline(
            records, common.load_baseline(args.baseline), args.tolerance
        )
        if failures:
            for f in failures:
                print(f"BENCH REGRESSION: {f}", file=sys.stderr, flush=True)
            return 1
        print(f"# bench gate passed (tolerance {args.tolerance:.0%})",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
