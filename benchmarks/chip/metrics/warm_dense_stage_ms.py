"""Device ms per frame slot of the warm dense wave program (warm priors from
the previous disparity, the band-only scan of both views, post-processing)."""
from benchmarks.chip.metrics._stage import stage_ms


def read(ctx):
    return stage_ms(ctx, "dense_warm_wave")
