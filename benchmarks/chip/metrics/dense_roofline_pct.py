"""The least time the dense matching's own work needs on this chip
(``work.py``: algorithmic operations over the int8 peak, bytes over HBM
bandwidth, whichever is larger), as a share of the measured dense stage
time per frame.  The denominator is the whole dense wave program, so the
share reads the same work whether a Pallas kernel, XLA ops or a fusion of
both does it."""
from benchmarks.chip import work
from benchmarks.chip.metrics._stage import stage_ms


def read(ctx):
    ms = stage_ms(ctx, "dense_wave")
    if ms is None or ctx["device_kind"] is None:
        return None
    cfg = ctx["cell"].config
    least, bound = work.dense_least_seconds(cfg["height"], cfg["width"],
                                            cfg["params"], ctx["device_kind"])
    ctx["log"](f"dense roofline: least {least!r} s per frame, {bound}-bound")
    return 100.0 * least / (ms * 1e-3)
