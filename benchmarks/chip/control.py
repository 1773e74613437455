#!/usr/bin/env python3
"""Readings that set the output check's limit: the program's, and the control's.

    python3 benchmarks/chip/control.py --workload <name> --seeds 1,2,3 --seconds <s> [--fault <name>]

In one process, for each seed: one run of the cell as ``run.py`` makes it
(a window of ``--seconds``), whose check numbers are the program's
readings; then the control, the plain reference computed in bfloat16
where the configuration states float32, put in the program's place for
every frame of that window (on a warm-start video cell, in the mode,
cold or warm, the program's frame was judged in) and judged by the same
check.  A sound limit
lies above every program reading and below every control reading, so the
program's runs come out correct and the control's not.  With ``--fault``
the program runs with one of the faults of ``tests/test_faults.py``
planted in its timed path, and its runs have to come out not correct.
The benchmark's own runs never run this.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parents[2]
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != Path(__file__).parent.resolve()]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from benchmarks.chip import check, harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args()
    import jax

    cell = harness.load_cell(args.workload)
    device = harness.require_device(cell.workload["chips"])
    from repro.launch.compile_cache import place_compile_cache

    harness.log(f"compile cache: {place_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if args.fault is not None:
        import pytest

        from benchmarks.chip.tests.test_faults import FAULTS, WARM_FAULTS

        {**FAULTS, **WARM_FAULTS}[args.fault][0](pytest.MonkeyPatch())
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        keep: dict = {}
        res = harness.run_cell(cell, seed, args.seconds, False, t_process=time.monotonic(),
                               device=device, keep=keep)
        row = {"seed": seed, "frames": res["attempted"], "failed": res["failed"],
               "program": {k: v["value"] for k, v in res["check"].items()},
               "program_correct": res["correct"]}
        if args.fault is None:
            numbers = harness.control_numbers(cell, keep)
            for name, (value, limit) in numbers.items():
                print(f"control check: {name}={value!r} limit={limit!r}", flush=True)
            row["control"] = {k: v for k, (v, _) in numbers.items()}
            row["control_correct"] = check.correct(numbers)
        rows.append(row)
        print("reading: " + json.dumps(row), flush=True)
    summary = {"workload": args.workload, "fault": args.fault,
               "limit": cell.config["check"]["worst_frame_mismatch"],
               "program_max": max(r["program"]["worst_frame_mismatch"] for r in rows),
               "program_correct": [r["program_correct"] for r in rows]}
    if args.fault is None:
        summary["control_min"] = min(r["control"]["worst_frame_mismatch"] for r in rows)
        summary["control_correct"] = [r["control_correct"] for r in rows]
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
