"""Share of the whole window in which no wave program was between dispatch
and ready, on either stage thread (``_timeline.py``).  The log line names
the three longest such intervals by the runs around them and by the part of
the next wave's timeline that covers them."""
from benchmarks.chip.metrics._timeline import PARTS, summary


def _part_at(t, when) -> str:
    if when < t.submit:
        return "nothing (not yet submitted)"
    for name, a, b, _ in PARTS:
        if getattr(t, a) <= when < getattr(t, b):
            return name
    return "delivered"


def read(ctx):
    w = ctx["window"]
    s = summary(w)
    if s is None:
        return None
    lo, hi = w.t_start, w.t_end
    gaps, t, before = [], lo, "window start"
    for d, r, wave, stage, _ in s["runs"]:
        if d > t and t < hi:
            gaps.append((t, min(d, hi), before, f"wave {wave} {stage} dispatch", wave))
        if r > t:
            t, before = r, f"wave {wave} {stage} ready"
    if t < hi:
        gaps.append((t, hi, before, "window end", None))
    idle = sum(g1 - g0 for g0, g1, *_ in gaps)
    last_in = {}                # wave -> the timeline of its last frame in
    for tm in s["timings"]:
        if tm.wave not in last_in or tm.enqueued > last_in[tm.wave].enqueued:
            last_in[tm.wave] = tm
    named = []
    for g0, g1, before, after, wave in sorted(gaps, key=lambda g: g[0] - g[1])[:3]:
        text = f"{before} -> {after}: {(g1 - g0) * 1e3!r} ms"
        tm = last_in.get(wave)
        if tm is not None:
            text += (f"; wave {wave}'s last frame in {_part_at(tm, (g0 + g1) / 2)},"
                     f" built {(tm.build_end - g0) * 1e3:+.3f} ms")
        named.append(text)
    ctx["log"]("pipeline idle: longest " + " | ".join(named))
    return 100.0 * idle / (hi - lo)
