"""Device ms per frame slot of the dense wave program (priors, grid
vectors, dense matching of both views, post-processing)."""
from benchmarks.chip.metrics._stage import stage_ms


def read(ctx):
    return stage_ms(ctx, "dense_wave")
