"""Mean milliseconds a frame delivered in the window spent waiting: in the
ingest queue and the wave linger, in the stage queues, in the in-order
hold, and on the device behind another program (``_timeline.py``).  With
``host_path_ms`` and the program runs it adds up to the mean latency; the
log line gives every part and the host's estimate of each program's run."""
from benchmarks.chip.metrics._timeline import STAGES, summary


def read(ctx):
    s = summary(ctx["window"])
    if s is None:
        return None
    per_run = []
    for stage in STAGES:
        runs = [r for r in s["runs"] if r[3] == stage]
        ms = sum(r[1] - r[4] for r in runs) / len(runs) * 1e3
        per_run.append(f"{stage}_run_ms_per_wave={ms!r} over {len(runs)} runs")
    ctx["log"](
        f"timeline: {s['frames']} frames, mean ms: "
        + " ".join(f"{k}={v!r}" for k, v in s["parts"].items())
        + " | " + " ".join(f"{k}={v!r}" for k, v in s["kinds"].items())
        + f" sum={sum(s['kinds'].values())!r} latency={s['latency_ms']!r} | "
        + " ".join(per_run))
    return s["kinds"]["wait"]
