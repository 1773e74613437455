import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this script
  1. builds the production mesh ((16,16) single pod / (2,16,16) multi-pod),
  2. resolves per-arch sharding rules (repro.launch.mesh.make_rules),
  3. lowers the train/prefill/decode step with ShapeDtypeStruct inputs
     (no allocation anywhere -- params, optimizer state, caches and batch
     are all abstract),
  4. compiles, and records memory_analysis() / cost_analysis() plus the
     collective-bytes breakdown parsed from the HLO for the roofline.

Results go to results/dryrun/<mesh>/<arch>__<shape>.json, one file per
cell, so the sweep is restartable.  Usage:

  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--skip-done]
"""
import argparse
import json
import re
import sys
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.configs.shapes import SHAPES, input_specs, shape_applicable
from repro.distributed.sharding import logical_to_spec, use_rules
from repro.launch.mesh import make_production_mesh, make_rules
from repro.models.model import LMModel, cache_specs, count_params
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.optim.schedule import ScheduleConfig
from repro.runtime.train_loop import make_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

# ---------------------------------------------------------------------------
# collective-bytes analysis from the post-SPMD HLO
# ---------------------------------------------------------------------------
_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)
_SHAPE_RE = re.compile(r"(f32|bf16|f16|s32|s8|u8|u32|s64|pred|f64)\[([\d,]*)\]")
_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "s8": 1, "u8": 1,
          "u32": 4, "s64": 8, "pred": 1, "f64": 8}


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op, by kind."""
    out = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        # match "<shape> <name> = op(...)" instruction lines, not comments
        m = re.match(r"^(?:ROOT )?%?[\w\.\-]+ = (.+)$", s)
        if not m:
            continue
        rhs = m.group(1)
        for kind in _COLLECTIVES:
            # ops appear as e.g. "bf16[...] all-gather(...)" or fused names
            if re.search(rf"\b{kind}(?:-start|-done)?\(", rhs):
                if f"{kind}-done(" in rhs:
                    continue          # avoid double count of async pairs
                head = rhs.split(f" {kind}", 1)[0]
                out[kind] += _shape_bytes(head)
                out["count"] += 1
    return out


def peak_memory_bytes(mem) -> int:
    """Peak device memory from a ``CompiledMemoryStats``."""
    return int(mem.peak_memory_in_bytes)


# ---------------------------------------------------------------------------
# cell construction
# ---------------------------------------------------------------------------
def _shardings_for(tree_specs, mesh, rules):
    return jax.tree.map(
        lambda axes: NamedSharding(mesh, logical_to_spec(axes, rules, mesh)),
        tree_specs,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x
        ),
    )


def _batch_specs(cfg, shape_name: str, microbatches: int):
    """Logical axes for the (pre-split) train batch / serve inputs."""
    spec = SHAPES[shape_name]
    emb = cfg.frontend in ("vision_stub", "audio_stub")
    mrope = cfg.pos_embedding == "mrope"
    if spec.mode == "train":
        tok = (None, "batch", "seq", None) if emb else (None, "batch", "seq")
        pos = (None, "batch", "seq", None) if mrope else (None, "batch", "seq")
        return {
            "inputs": tok,
            "targets": (None, "batch", "seq"),
            "positions": pos,
        }
    tok = ("batch", "seq", None) if emb else ("batch", "seq")
    pos = ("batch", "seq", None) if mrope else ("batch", "seq")
    return {"inputs": tok, "positions": pos}


def _presplit_train_specs(cfg, shape_name: str, microbatches: int):
    spec = SHAPES[shape_name]
    b, s = spec.global_batch, spec.seq_len
    mb = b // microbatches
    emb = cfg.frontend in ("vision_stub", "audio_stub")
    mrope = cfg.pos_embedding == "mrope"
    tok = (
        jax.ShapeDtypeStruct((microbatches, mb, s, cfg.d_model), jnp.bfloat16)
        if emb else jax.ShapeDtypeStruct((microbatches, mb, s), jnp.int32)
    )
    pos = (
        jax.ShapeDtypeStruct((microbatches, mb, s, 3), jnp.int32)
        if mrope else jax.ShapeDtypeStruct((microbatches, mb, s), jnp.int32)
    )
    return {
        "inputs": tok,
        "targets": jax.ShapeDtypeStruct((microbatches, mb, s), jnp.int32),
        "positions": pos,
    }


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool = False,
    verbose: bool = True,
    optimized: bool = False,
) -> dict:
    """Lower + compile one cell; returns the roofline record.

    optimized=True applies the beyond-paper perf pass (EXPERIMENTS.md
    §Perf): causal block skipping, 'names' remat policy, and the serving
    weight/cache layout -- the baseline records stay untouched.
    """
    import dataclasses as _dc

    cfg = get_config(arch)
    if optimized:
        cfg = _dc.replace(cfg, causal_skip=True, remat_policy="names")
    spec = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    rules = make_rules(cfg, mesh, global_batch=spec.global_batch,
                       shape_name=shape_name, optimized=optimized)
    if optimized and spec.mode == "decode" and rules.seq_kv is not None:
        # hillclimb #3: shard-preserving cache insert (see cache_insert)
        cfg = _dc.replace(cfg, cache_update="onehot")
    model = LMModel(cfg)

    abstract_params = model.abstract_params()
    if optimized and spec.mode != "train":
        # hillclimb #4: serving stores bf16 weights (the standard serving
        # checkpoint format) -- halves every remaining FSDP gather payload.
        abstract_params = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16)
            if x.dtype == jnp.float32 else x,
            abstract_params,
        )
    p_shardings = _shardings_for(model.param_specs(), mesh, rules)

    batch_shards = 1
    for ax in (rules.batch or ()):
        batch_shards *= dict(zip(mesh.axis_names, mesh.devices.shape))[ax]

    t0 = time.time()
    with mesh, use_rules(rules):
        if spec.mode == "train":
            microbatches = max(1, spec.global_batch // max(batch_shards, 1))
            opt_cfg = AdamWConfig(
                m_dtype="bfloat16" if count_params(cfg) > 1e11 else "float32",
                v_dtype="bfloat16" if count_params(cfg) > 1e11 else "float32",
            )
            abstract_opt = jax.eval_shape(
                lambda p: adamw_init(p, opt_cfg), abstract_params
            )
            opt_shardings = {
                "m": p_shardings, "v": p_shardings,
                "step": NamedSharding(mesh, P()),
            }
            step = make_train_step(
                model, opt_cfg, ScheduleConfig(), microbatches=microbatches,
                presplit=True, donate=False, jit=False,
            )
            batch_abs = _presplit_train_specs(cfg, shape_name, microbatches)
            batch_sh = _shardings_for(
                _batch_specs(cfg, shape_name, microbatches), mesh, rules
            )
            lowered = jax.jit(
                step,
                in_shardings=(p_shardings, opt_shardings, batch_sh),
            ).lower(abstract_params, abstract_opt, batch_abs)
        else:
            cache_len = spec.seq_len
            abstract_caches = jax.eval_shape(
                lambda: model.init_caches(spec.global_batch, cache_len)
            )
            c_shardings = _shardings_for(cache_specs(cfg), mesh, rules)
            ins = input_specs(cfg, shape_name)
            in_sh = _shardings_for(
                _batch_specs(cfg, shape_name, 1), mesh, rules
            )

            def serve_step(params, caches, inputs, positions):
                logits, new_caches, _ = model.apply(
                    params, inputs, positions, caches=caches
                )
                return logits[:, -1:], new_caches

            lowered = jax.jit(
                serve_step,
                in_shardings=(p_shardings, c_shardings,
                              in_sh["inputs"], in_sh["positions"]),
            ).lower(abstract_params, abstract_caches,
                    ins["inputs"], ins["positions"])

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())

    record = {
        "arch": arch,
        "shape": shape_name,
        "optimized": optimized,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "devices": n_dev,
        "mode": spec.mode,
        "params": count_params(cfg),
        "active_params": count_params(cfg, active_only=True),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "flops": cost.get("flops", 0.0),
        "hlo_bytes": cost.get("bytes accessed", 0.0),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "peak_bytes": peak_memory_bytes(mem),
        },
        "collectives": coll,
        "rules": {
            "batch": rules.batch, "heads": rules.heads,
            "kv_heads": rules.kv_heads, "seq_kv": rules.seq_kv,
            "fsdp": rules.fsdp, "experts": rules.experts,
        },
    }
    if verbose:
        print(json.dumps(record, indent=None, default=str))
    return record


def _result_path(arch: str, shape_name: str, multi_pod: bool,
                 optimized: bool = False) -> str:
    mesh_tag = "2x16x16" if multi_pod else "16x16"
    base = RESULTS_DIR + "_opt" if optimized else RESULTS_DIR
    d = os.path.join(base, mesh_tag)
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, f"{arch}__{shape_name}.json")


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--optimized", action="store_true",
                    help="apply the §Perf optimizations (results go to "
                         "results/dryrun_opt)")
    args = ap.parse_args(argv)

    cells = []
    if args.all:
        for arch in ARCH_IDS:
            cfg = get_config(arch)
            for shape_name in SHAPES:
                if shape_applicable(cfg, shape_name):
                    cells.append((arch, shape_name))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    failures = []
    for arch, shape_name in cells:
        path = _result_path(arch, shape_name, args.multi_pod, args.optimized)
        if args.skip_done and os.path.exists(path):
            continue
        print(f"=== {arch} x {shape_name} x "
              f"{'2x16x16' if args.multi_pod else '16x16'}"
              f"{' [optimized]' if args.optimized else ''} ===", flush=True)
        try:
            record = run_cell(arch, shape_name, multi_pod=args.multi_pod,
                              optimized=args.optimized)
            with open(path, "w") as f:
                json.dump(record, f, indent=2, default=str)
        except Exception as e:
            traceback.print_exc()
            failures.append((arch, shape_name, repr(e)))
    if failures:
        print(f"FAILED {len(failures)} cells:")
        for f in failures:
            print("  ", f)
        return 1
    print(f"all {len(cells)} cells compiled OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
