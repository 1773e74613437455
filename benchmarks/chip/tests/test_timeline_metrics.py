"""The service metrics read from the frames' timelines, on a hand-made
window whose answers are worked out by hand below."""
import gzip
import json
import re
from pathlib import Path

import pytest

from benchmarks.chip import driver
from benchmarks.chip.metrics import host_path_ms, pipeline_idle_share, queue_wait_ms
from repro.serving import CompletedFrame
from repro.serving.tracing import FrameTiming

MS = 1e-3
DATA = Path(__file__).resolve().parent / "data"


def _timing(wave, submit, enqueued, build, support, dense, emit, finished, delivered):
    """Times in ms after 10 s; ``build``, ``support``, ``dense``, ``emit``
    are (start, end) pairs."""
    ms = [submit, enqueued, *build, *support, *dense, *emit, finished, delivered]
    stamps = dict(zip(FrameTiming.STAMPS, (10.0 + v * MS for v in ms)))
    return FrameTiming(wave=wave, **stamps)


def _record(fid, timing):
    frame = CompletedFrame(request_id=fid, stream_id=0, frame_id=fid, disparity=None,
                           latency_s=timing.delivered - timing.submit, timing=timing)
    return driver.Record(fid, 0, 0, t_done=timing.delivered, frame=frame)


def _window():
    # wave 0 (frames a, b): support [7, 17], dense [18, 58]; b is held 1 ms
    w0 = dict(build=(4, 6), support=(7, 17), dense=(18, 58), emit=(60, 62))
    a = _timing(0, 0, 1, finished=63, delivered=63, **w0)
    b = _timing(0, 2, 3, finished=64, delivered=65, **w0)
    # wave 1 (frame c): support dispatched at 17.5, before dense 0, but run
    # after it (ready 58): 40.5 ms of device wait and a 10 ms run, while
    # dense 0 keeps its 40 ms
    c = _timing(1, 10, 11, build=(12, 14), support=(17.5, 68), dense=(70, 110),
                emit=(111, 112), finished=113, delivered=113)
    # wave 2 (frame e): delivered after the window's end, so in no mean;
    # its runs still count for the idle share
    e = _timing(2, 150, 151, build=(152, 155), support=(160, 170), dense=(171, 210),
                emit=(212, 214), finished=215, delivered=215)
    records = [_record(i, t) for i, t in enumerate((a, b, c, e))]
    records.append(driver.Record(4, 0, 0))           # lost: no frame
    return driver.Window(seconds=0.2, t_start=10.0, t_end=10.2, records=records, strays=0)


def _ctx(window):
    lines = []
    return {"window": window, "log": lines.append, "trace": None}, lines


def test_parts_split_into_wait_host_and_programs_that_add_up_to_latency():
    ctx, lines = _ctx(_window())
    # waits: a 3+1+1+2 = 7, b 1+1+1+2+1 (hold) = 6, c 1+3.5+40.5 (device)+2+1 = 48
    assert queue_wait_ms.read(ctx) == pytest.approx((7 + 6 + 48) / 3)
    # host: a 1+2+2+1 = 6, b 1+2+2+2 = 7, c 1+2+1+1 = 5
    assert host_path_ms.read(ctx) == pytest.approx(6.0)
    (line,) = lines
    logged = {k: float(v) for k, v in re.findall(r"(\w+)=([-\d.e]+)", line)}
    assert logged["support_device_wait"] == pytest.approx(40.5 / 3)
    assert logged["dense_device_wait"] == pytest.approx(0.0)
    assert logged["hold"] == pytest.approx(1 / 3)
    # programs 50 ms a frame; the parts sum to the mean latency (63, 63, 103)
    assert logged["program"] == pytest.approx(50.0)
    assert logged["sum"] == pytest.approx(229 / 3)
    assert logged["latency"] == pytest.approx(229 / 3)
    # host estimate per run: support 10 in each wave; dense 40, 40 and 39
    assert logged["support_run_ms_per_wave"] == pytest.approx(10.0)
    assert logged["dense_run_ms_per_wave"] == pytest.approx(119 / 3)


def test_pipeline_idle_share_and_its_longest_gaps():
    ctx, lines = _ctx(_window())
    # union of runs in [0, 200] ms leaves 7 + 0.5 + 2 + 50 + 1 = 60.5 ms idle
    assert pipeline_idle_share.read(ctx) == pytest.approx(100 * 60.5 / 200)
    (line,) = lines
    first, second, third = line.split("longest ")[1].split(" | ")
    gap_ms = [float(re.search(r": ([\d.e-]+) ms", g).group(1)) for g in (first, second, third)]
    assert gap_ms == pytest.approx([50.0, 7.0, 2.0])
    assert first.startswith("wave 1 dense ready -> wave 2 support dispatch:")
    assert "wave 2's last frame in nothing (not yet submitted)" in first
    assert second.startswith("window start -> wave 0 support dispatch:")
    assert "last frame in ingest_wait, built +6.000 ms" in second
    assert third.startswith("wave 1 support ready -> wave 1 dense dispatch:")
    assert "last frame in dense_queue, built -54.000 ms" in third


def test_a_program_without_timelines_reads_nothing():
    window = _window()
    for r in window.records:
        if r.frame is not None:
            r.frame = CompletedFrame(r.frame.request_id, 0, r.frame_id, None,
                                     r.frame.latency_s)
    ctx, lines = _ctx(window)
    assert queue_wait_ms.read(ctx) is None
    assert host_path_ms.read(ctx) is None
    assert pipeline_idle_share.read(ctx) is None
    assert lines == []


def _recorded_spans():
    """400 ms of a traced ``kitti.fleet8`` run on a TPU v5e: the device's
    program executions and the host events, ``stereo.*`` spans with their
    wave index as a fourth element (per-chunk ``Transpose`` events and
    device operations left out)."""
    with gzip.open(DATA / "kitti_fleet8_spans_slice.json.gz", "rt") as f:
        return json.load(f)


def test_recorded_program_runs_lie_inside_their_spans_on_one_shifted_clock():
    """The clock check.  Each execution pairs with the span of its program
    it overlaps most; one constant shift of the device's clock puts every
    execution inside its span: dispatch - start <= shift <= ready - end."""
    t = _recorded_spans()
    span_of = {"jit_support_wave": "stereo.support.run", "jit_dense_wave": "stereo.dense.run"}
    lower, upper, pairs = -float("inf"), float("inf"), []
    for name, start, dur in t["devices"][0]["modules"]:
        wanted = span_of[name.split("(")[0]]
        spans = [e for e in t["host"] if e[0] == wanted
                 and e[1] < start + dur and start < e[1] + e[2]]
        if not spans:            # its span was open when the trace stopped
            continue
        span = max(spans, key=lambda e: min(e[1] + e[2], start + dur) - max(e[1], start))
        pairs.append(span[3])
        lower = max(lower, span[1] - start)
        upper = min(upper, span[1] + span[2] - (start + dur))
    # four waves' programs in order; each span holds its wave index
    assert pairs == [18, 19, 20, 19, 20, 21]
    assert lower <= upper
    # the device's clock runs 1.7-2.8 ms behind the host plane's here
    assert 1.0e6 < lower <= upper < 3.0e6
