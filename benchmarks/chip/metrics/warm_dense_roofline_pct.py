"""The least time the warm dense scan's own work needs on this chip
(``warm_work.py``: the band's operations over the int8 peak, bytes over HBM
bandwidth, whichever is larger), as a share of ``warm_dense_stage_ms``, the
whole warm dense wave program per frame slot."""
from benchmarks.chip import warm_work
from benchmarks.chip.metrics._stage import stage_ms


def read(ctx):
    ms = stage_ms(ctx, "dense_warm_wave")
    if ms is None or ctx["device_kind"] is None:
        return None
    cfg, band = ctx["cell"].config, ctx["cell"].traffic["service"]["warm_band"]
    least, bound = warm_work.warm_dense_least_seconds(cfg["height"], cfg["width"], band,
                                                      ctx["device_kind"])
    ctx["log"](f"warm dense roofline: least {least!r} s per frame, {bound}-bound")
    return 100.0 * least / (ms * 1e-3)
