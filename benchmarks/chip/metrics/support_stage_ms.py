"""Device ms per frame slot of the support wave program (descriptors, the
support search, filtering and the iELAS interpolation)."""
from benchmarks.chip.metrics._stage import stage_ms


def read(ctx):
    return stage_ms(ctx, "support_wave")
