"""Mean milliseconds of host work per frame delivered in the window: the
request record in ``submit`` up to the ingest put, the wave's pad, stack and
upload, its read-back, and slicing and delivery up to the frame's own
(``_timeline.py``)."""
from benchmarks.chip.metrics._timeline import summary


def read(ctx):
    s = summary(ctx["window"])
    return None if s is None else s["kinds"]["host"]
