"""One run of one benchmark cell: set-up, the measured window, the check.

Everything a cell is made of is found by name, as data:

* the cell: an entry of ``workloads`` in ``BENCHMARK.json``;
* its configuration: the ``file`` its ``configs`` entry names, with the
  plain reference module that file names under ``reference/``;
* its traffic: ``traffic/<traffic>.json``, read by :mod:`driver`;
* each end-to-end metric: ``end_to_end/<name>.py``, each per-layer metric:
  ``metrics/<name>.py``, a ``read(ctx)`` that returns a number or None.

A later change adds a cell, a mix or a metric by adding such files and
entries; nothing here names one.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
WAIT_AFTER_CLOSE_S = 60.0


def log(msg: str) -> None:
    print(msg, flush=True)


def load_module(path: Path):
    """The module in ``path``, loaded once under a name made from its path."""
    name = "bench." + ".".join(path.relative_to(HERE).with_suffix("").parts)
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


@dataclasses.dataclass
class Cell:
    """A cell with everything it names loaded."""

    workload: dict
    config: dict
    traffic: dict
    end_to_end: list          # BENCHMARK.json entries this cell reports
    per_layer: list


def load_cell(name: str, spec_path: Path = ROOT / "BENCHMARK.json") -> Cell:
    spec = json.loads(spec_path.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    wl = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{wl['traffic']}.json").read_text())

    def applies(metric: dict) -> bool:
        return "workloads" not in metric or name in metric["workloads"]

    e2e = [m for m in spec["end_to_end"] if applies(m)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if applies(m) and m["moves"] in reported]
    return Cell(wl, config, traffic, e2e, per_layer)


def require_device(chips: int) -> dict:
    """The accelerator JAX found; exits without a result if it is not one."""
    import jax

    devs = jax.devices()
    d = devs[0]
    desc = f"platform={d.platform} device_kind={d.device_kind} count={len(devs)}"
    if d.platform == "cpu" or len(devs) < chips:
        print(f"benchmark: needs {chips} accelerator chip(s); JAX found {desc}",
              file=sys.stderr, flush=True)
        raise SystemExit(3)
    log(f"device: {desc}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def memory_peak_bytes(chips: int) -> int:
    import jax

    peaks = [(dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for dev in jax.devices()[:chips]]
    return int(max(peaks))


class TraceWindow:
    """Profiles ``length`` seconds starting ``lead`` seconds into the window,
    from a thread of its own so the load loop never waits on the profiler."""

    def __init__(self, lead: float, length: float):
        self.lead, self.length = lead, length
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.error = None
        self._thread = threading.Thread(target=self._run, name="bench-trace")

    def start(self) -> None:
        self._thread.start()

    def _run(self) -> None:
        import jax

        try:
            time.sleep(self.lead)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            from benchmarks.chip.traces import WINDOW_ANNOTATION

            with jax.profiler.TraceAnnotation(WINDOW_ANNOTATION):
                time.sleep(self.length)
            jax.profiler.stop_trace()
        except Exception as e:          # noqa: BLE001 -- reported by read()
            self.error = e

    def read(self) -> dict:
        from benchmarks.chip import traces

        self._thread.join()
        try:
            if self.error is not None:
                raise RuntimeError(f"profiler failed: {self.error!r}")
            found = sorted(Path(self.dir).rglob("*.xplane.pb"))
            if not found:
                raise RuntimeError("the profiler wrote no trace")
            return traces.reduce(traces.load(str(found[-1])))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def reference_outputs(config: dict, pool_l, pool_r, indices,
                      ftype: str = "float32") -> dict:
    """pool index -> the plain reference's (H, W) float32 disparity, its
    real-valued arithmetic done in ``ftype``."""
    import jax.numpy as jnp
    import numpy as np

    ref_mod = load_module(HERE / "reference" / f"{config['reference']}.py")
    p = ref_mod.params_from(config)
    return {i: np.asarray(ref_mod.disparity(jnp.asarray(pool_l[i], jnp.float32),
                                            jnp.asarray(pool_r[i], jnp.float32), p, ftype))
            for i in indices}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_process: float, device: dict | None,
             wait_after_close: float = WAIT_AFTER_CLOSE_S,
             keep: dict | None = None) -> dict:
    """One run; returns the result object.  ``device`` None runs wherever
    JAX runs (the harness's own tests) and reports the platform it found.
    ``keep``, where given, receives the frame pool and reference outputs."""
    import jax

    from benchmarks.chip import check, driver
    from repro.core.params import ElasParams
    from repro.serving import StereoService

    cfg, tr = cell.config, cell.traffic
    chips = cell.workload["chips"]
    h, w = cfg["height"], cfg["width"]
    phases = {"start": time.monotonic() - t_process}

    t = time.monotonic()
    pool_l, pool_r = driver.frame_pool(seed, tr["pool"], h, w,
                                       cfg["scene"]["d_max"], cfg["scene"]["n_objects"])
    phases["pool"] = time.monotonic() - t

    t = time.monotonic()
    svc = StereoService(ElasParams(**cfg["params"]), **driver.service_kwargs(tr))
    svc.start()
    svc.warmup([(h, w)])
    phases["compile_and_warmup"] = time.monotonic() - t

    t = time.monotonic()
    driver.prime(svc, pool_l, pool_r, tr)
    phases["prime"] = time.monotonic() - t
    setup_s = time.monotonic() - t_process
    log("setup: " + " ".join(f"{k}={v!r}" for k, v in phases.items())
        + f" total={setup_s!r}")

    tw = None
    if trace:
        tw = TraceWindow(lead=min(2.0, seconds / 4), length=min(4.0, seconds / 2))
        tw.start()
    window = driver.closed_loop(svc, pool_l, pool_r, tr, seconds, wait_after_close)
    traced = tw.read() if tw is not None else None
    svc.stop(drain=False)
    stats = svc.stats()
    log(f"service: {stats}")
    peak = memory_peak_bytes(chips) if device is not None else 0
    del svc
    gc.collect()

    lat = window.latencies_ms()
    log(f"window: seconds={window.seconds!r} submitted={len(window.records)} "
        f"delivered_in_window={window.delivered_in_window()} "
        f"latency_p50_ms={driver.percentile(lat, 0.5)!r} "
        f"latency_p95_ms={driver.percentile(lat, 0.95)!r} latency_count={len(lat)}")

    t = time.monotonic()
    used = sorted({r.pool_index for r in window.records})
    refs = reference_outputs(cfg, pool_l, pool_r, used)
    log(f"reference: {len(refs)} frames in {time.monotonic() - t!r} s")
    if keep is not None:
        keep.update(pool=(pool_l, pool_r), references=refs, window=window)
    numbers = check.compare(window.records, refs, cfg["check"], window.strays)

    ctx = {"cell": cell, "window": window, "setup_s": setup_s, "trace": traced,
           "device_kind": (device or {}).get("kind"), "log": log}
    metrics = {}
    if trace:
        wanted = cell.per_layer
        folder = "metrics"
    else:
        wanted = cell.end_to_end
        folder = "end_to_end"
    for m in wanted:
        value = load_module(HERE / folder / f"{m['name']}.py").read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = dict(device) if device is not None else {
        "platform": jax.devices()[0].platform, "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}
    dev["memory_peak_bytes"] = peak
    result = {
        "correct": check.correct(numbers),
        "attempted": len(window.records),
        "failed": window.failed(),
        "metrics": metrics,
        "device": dev,
    }
    if traced is not None:
        dev["busy_s"] = traced["busy_s"]
        dev["window_s"] = traced["window_s"]
        result["breakdown"] = {"device_ops": traced["device_ops"],
                               "idle_gaps": traced["idle_gaps"]}
    for name, (value, limit) in numbers.items():
        print(f"check: {name}={value!r} limit={limit!r}", file=sys.stderr, flush=True)
    result["check"] = {k: {"value": v, "limit": lim} for k, (v, lim) in numbers.items()}
    return result


def main(argv, t_process: float) -> int:
    """``t_process``: ``time.monotonic()`` as the process started."""
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    device = require_device(cell.workload["chips"])
    from repro.launch.compile_cache import place_compile_cache
    import jax

    log(f"compile cache: {place_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_process=t_process, device=device)
    print(json.dumps(result), flush=True)
    return 0
