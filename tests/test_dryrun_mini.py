"""Dry-run machinery tests.

The full 512-device sweep runs via ``python -m repro.launch.dryrun --all``
(results under results/dryrun/).  Here we validate the machinery at test
scale: an 8-device host-platform mesh in a SUBPROCESS (so the main test
process keeps seeing 1 device), lowering a REDUCED arch through the same
helpers, plus unit tests of the HLO collective parser.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.launch.dryrun import collective_bytes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestCollectiveParser:
    def test_sums_result_shapes(self):
        hlo = textwrap.dedent("""\
            %x = bf16[8,128] all-gather(bf16[1,128] %a), replica_groups={}
            %y = f32[256] all-reduce(f32[256] %b), to_apply=%sum
            %z = f32[4,64] reduce-scatter(f32[32,64] %c), dimensions={0}
            ROOT %r = (f32[2]) tuple(%y)
        """)
        out = collective_bytes(hlo)
        assert out["all-gather"] == 8 * 128 * 2
        assert out["all-reduce"] == 256 * 4
        assert out["reduce-scatter"] == 4 * 64 * 4
        assert out["count"] == 3

    def test_async_pairs_not_double_counted(self):
        hlo = textwrap.dedent("""\
            %s = f32[64] all-gather-start(f32[8] %a)
            %d = f32[64] all-gather-done(f32[64] %s)
        """)
        out = collective_bytes(hlo)
        assert out["count"] == 1

    def test_ignores_non_collectives(self):
        out = collective_bytes("%m = f32[128,128] dot(f32[128,64] %a, f32[64,128] %b)")
        assert out["count"] == 0


MINI_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.distributed.sharding import logical_to_spec, use_rules
from repro.launch.mesh import make_rules
from repro.launch.dryrun import _shardings_for, collective_bytes, peak_memory_bytes
from repro.models.model import LMModel, cache_specs

mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
cfg = get_config("gemma2-27b", reduced=True)
rules = make_rules(cfg, mesh, global_batch=4)
model = LMModel(cfg)

with mesh, use_rules(rules):
    abstract_params = model.abstract_params()
    p_sh = _shardings_for(model.param_specs(), mesh, rules)
    caches = jax.eval_shape(lambda: model.init_caches(4, 64))
    c_sh = _shardings_for(cache_specs(cfg), mesh, rules)

    def serve_step(params, caches, tokens):
        logits, new_caches, _ = model.apply(params, tokens, caches=caches)
        return logits[:, -1:], new_caches

    lowered = jax.jit(
        serve_step,
        in_shardings=(p_sh, c_sh, NamedSharding(mesh, P("data", None))),
    ).lower(abstract_params, caches, jax.ShapeDtypeStruct((4, 1), jnp.int32))
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    coll = collective_bytes(compiled.as_text())
    print(json.dumps({
        "ok": True,
        "peak": peak_memory_bytes(mem),
        "collective_count": coll["count"],
    }))
"""


@pytest.mark.slow
def test_mini_dryrun_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, "-c", MINI_SCRIPT],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["ok"] and out["peak"] > 0


def test_dryrun_results_exist_and_complete():
    """The committed sweep results cover every applicable cell x both meshes."""
    from repro.configs import ARCH_IDS, get_config
    from repro.configs.shapes import SHAPES, shape_applicable

    base = os.path.join(REPO, "results", "dryrun")
    if not os.path.isdir(base):
        pytest.skip("dry-run sweep has not been executed yet")
    for mesh in ("16x16", "2x16x16"):
        mesh_dir = os.path.join(base, mesh)
        if not os.path.isdir(mesh_dir):
            pytest.skip(f"{mesh} sweep not finished")
        for arch in ARCH_IDS:
            cfg = get_config(arch)
            for shape in SHAPES:
                if not shape_applicable(cfg, shape):
                    continue
                path = os.path.join(mesh_dir, f"{arch}__{shape}.json")
                assert os.path.exists(path), f"missing cell {mesh}/{arch}/{shape}"
                with open(path) as f:
                    rec = json.load(f)
                assert rec["memory"]["peak_bytes"] > 0
