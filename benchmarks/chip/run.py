#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this machine holds.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  The last line
of standard output is the result object; earlier lines give the set-up
phases, the service's counters and the window's latency median and count;
the last lines of standard error give each number the output check
compared, beside its limit.  Without an accelerator, or with fewer chips
than the cell asks for, it exits non-zero and prints no result.
"""
import time

T_PROCESS = time.monotonic()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parents[2]
# This file's own directory must not shadow other modules.
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != Path(__file__).parent.resolve()]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from benchmarks.chip import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_PROCESS))
