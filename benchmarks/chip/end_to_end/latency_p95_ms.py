"""95th percentile of submit-to-delivery latency over every frame submitted
in the window (``CompletedFrame.latency_s``); a failed or lost frame counts
as beyond any latency (reported as 1e12 ms if it reaches the percentile)."""
import math

from benchmarks.chip.driver import percentile


def read(ctx):
    value = percentile(ctx["window"].latencies_ms(), 0.95)
    return value if math.isfinite(value) else 1e12
