"""One run of one benchmark cell: set-up, the measured window, the check.

Everything a cell is made of is found by name, as data:

* the cell: an entry of ``workloads`` in ``BENCHMARK.json``;
* its configuration: the ``file`` its ``configs`` entry names, with the
  plain reference module that file names under ``reference/``: a
  ``disparity(left, right, p, ftype)``, and for warm-start video a
  ``warm_disparity(left, right, prior, p, warm_band, ftype)``;
* its traffic: ``traffic/<traffic>.json``, read by :mod:`driver`: pairs or
  videos, and the service's settings;
* each end-to-end metric: ``end_to_end/<name>.py``, each per-layer metric:
  ``metrics/<name>.py``, a ``read(ctx)`` that returns a number or None.

A later change adds a cell, a mix or a metric by adding such files and
entries; nothing here names one.
"""
from __future__ import annotations

import dataclasses
import gc
import hashlib
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


def reference_module(config: dict):
    return load_module(HERE / "reference" / f"{config['reference']}.py")


class References:
    """The plain reference's answers to the frames of a window, computed on
    demand, each once: a cold answer by pool index, a warm one by pool
    index and a digest of its prior.  ``priors``: frame id -> the delivered
    disparity of the frame its stream submitted just before it, the prior
    a warm answer to it starts from.  ``warm``: the service's
    ``(warm_band, rerun_threshold)`` where it runs warm start; without it,
    or where the reference module has no ``warm_disparity``, no frame has a
    warm answer."""

    def __init__(self, config: dict, source, priors: dict, warm, ftype: str = "float32"):
        self.config, self.source, self.warm_settings, self.ftype = config, source, warm, ftype
        self.mod = reference_module(config)
        self.p = self.mod.params_from(config)
        self.priors = priors if warm and hasattr(self.mod, "warm_disparity") else {}
        self._answers: dict = {}
        self.count = {"cold": 0, "warm": 0}
        self.seconds = {"cold": 0.0, "warm": 0.0}
        self.warm_over_bound = 0

    @staticmethod
    def priors_of(window) -> dict:
        return {fid: pred.frame.disparity for fid, pred in window.predecessors().items()
                if pred is not None and pred.frame is not None and pred.frame.ok}

    def with_ftype(self, ftype: str) -> "References":
        """The same frames and priors, computed in ``ftype``."""
        return References(self.config, self.source, self.priors, self.warm_settings, ftype)

    def _answer(self, mode: str, key: tuple, compute):
        import numpy as np

        if key not in self._answers:
            t = time.monotonic()
            self._answers[key] = np.asarray(compute())
            self.seconds[mode] += time.monotonic() - t
            self.count[mode] += 1
        return self._answers[key]

    def _pair(self, r):
        import jax.numpy as jnp

        return (jnp.asarray(self.source.left[r.pool_index], jnp.float32),
                jnp.asarray(self.source.right[r.pool_index], jnp.float32))

    def cold(self, r):
        return self._answer("cold", ("cold", r.pool_index), lambda: self.mod.disparity(
            *self._pair(r), self.p, self.ftype))

    def warm_answer(self, r):
        """The warm reference seeded by ``r``'s prior, or None without one."""
        import jax.numpy as jnp

        prior = self.priors.get(r.frame_id)
        if prior is None:
            return None
        digest = hashlib.blake2b(prior.tobytes(), digest_size=16).digest()
        return self._answer("warm", ("warm", r.pool_index, digest), lambda: self.mod.warm_disparity(
            *self._pair(r), jnp.asarray(prior), self.p, self.warm_settings[0], self.ftype))

    def warm(self, r):
        """The warm answer where the service may deliver it (see
        :func:`check.warm_stands`), else None."""
        from benchmarks.chip import check

        want = self.warm_answer(r)
        if want is None:
            return None
        if not check.warm_stands(want, self.priors[r.frame_id], self.p.num_disp,
                                 self.p.invalid, self.warm_settings[1]):
            self.warm_over_bound += 1
            return None
        return want

    def answers(self, records, modes: dict) -> dict:
        """frame id -> the answer in the mode ``modes`` gives it."""
        return {r.frame_id: (self.cold(r) if modes[r.frame_id] == "cold" else self.warm_answer(r))
                for r in records if r.frame_id in modes}

    def summary(self) -> str:
        return (" ".join(f"{m}={self.count[m]} {m}_s={self.seconds[m]!r}" for m in self.count)
                + f" warm_over_bound={self.warm_over_bound}")


def control_numbers(cell: Cell, keep: dict, ftype: str = "bfloat16") -> dict:
    """The check's numbers with the plain reference computed in ``ftype``
    put in the program's place, each frame in the mode (cold or warm) the
    program's own check judged it by."""
    from benchmarks.chip import check

    window, refs = keep["window"], keep["references"]
    outputs = refs.with_ftype(ftype).answers(window.records, keep["modes"])
    numbers, _ = check.compare(check.substituted(window.records, outputs), refs.cold,
                               refs.warm, cell.config["check"], window.strays)
    return numbers


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_process: float, device: dict | None,
             wait_after_close: float = WAIT_AFTER_CLOSE_S,
             keep: dict | None = None) -> dict:
    """One run; returns the result object.  ``device`` None runs wherever
    JAX runs (the harness's own tests) and reports the platform it found.
    ``keep``, where given, receives the window, the references and the
    mode each frame was judged in."""
    import jax

    from benchmarks.chip import check, driver
    from repro.core.params import ElasParams
    from repro.serving import StereoService

    cfg, tr = cell.config, cell.traffic
    chips = cell.workload["chips"]
    h, w = cfg["height"], cfg["width"]
    phases = {"start": time.monotonic() - t_process}

    t = time.monotonic()
    source = driver.frame_source(tr, seed, h, w, cfg["scene"])
    phases["pool"] = time.monotonic() - t

    t = time.monotonic()
    svc = StereoService(ElasParams(**cfg["params"]), **driver.service_kwargs(tr))
    svc.start()
    svc.warmup([(h, w)])
    phases["compile_and_warmup"] = time.monotonic() - t

    t = time.monotonic()
    primed = driver.prime(svc, source, tr)
    phases["prime"] = time.monotonic() - t
    setup_s = time.monotonic() - t_process
    log("setup: " + " ".join(f"{k}={v!r}" for k, v in phases.items())
        + f" total={setup_s!r}")

    tw = None
    if trace:
        tw = TraceWindow(lead=min(2.0, seconds / 4), length=min(4.0, seconds / 2))
        tw.start()
    window = driver.closed_loop(svc, source, tr, seconds, wait_after_close, primed)
    traced = tw.read() if tw is not None else None
    svc.stop(drain=False)
    stats = svc.stats()
    warm = (svc.warm_band, svc.rerun_threshold) if svc.warm_start else None
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
    refs = References(cfg, source, References.priors_of(window), warm)
    numbers, modes = check.compare(window.records, refs.cold, refs.warm, cfg["check"],
                                   window.strays)
    warm_only = sum(1 for m in modes.values() if m == "warm")
    log(f"reference: {refs.summary()} matched_only_warm={warm_only} "
        f"in {time.monotonic() - t!r} s")
    if keep is not None:
        keep.update(references=refs, window=window, modes=modes)

    ctx = {"cell": cell, "window": window, "setup_s": setup_s, "trace": traced,
           "stats": stats, "device_kind": (device or {}).get("kind"), "log": log}
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
