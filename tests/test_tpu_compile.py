"""Compile the stereo path's Pallas kernels and wave programs for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a v5e that is described (``get_topology_desc``), from shapes only.
That catches what interpret mode cannot -- block shapes off the (8, 128)
tiling, constructs Mosaic does not lower, VMEM overflow -- at no chip
time.  Nothing here runs a kernel.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and every test worker imports
this file.  Keep every such compile in this one file.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.configs.elas_stereo import KITTI, SYNTH, TSUKUBA
from repro.core import pipeline
from repro.kernels.dense_match import dense_match_stream_pallas
from repro.kernels.median import median3x3_pallas
from repro.kernels.sobel import sobel_pallas
from repro.kernels.support_match import support_match_pallas

CONFIGS = {c.name: c for c in (SYNTH, TSUKUBA, KITTI)}
WAVE = 4
BACKEND = "pallas_tpu"


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(fn, *specs) -> str:
    return jax.jit(fn).lower(*specs).compile().as_text()


def _kernel_call(name, cfg, spec):
    """(function, argument shapes) of one kernel at one configuration."""
    p, h, w = cfg.params, cfg.height, cfg.width
    if name == "sobel":
        return (lambda img: sobel_pallas(img, interpret=False),
                [spec((h, w), jnp.float32)])
    if name == "median3x3":
        return (lambda d: median3x3_pallas(d, interpret=False),
                [spec((h, w), jnp.float32)])
    if name == "support_match":
        rows = spec((h // p.candidate_step, w, 16), jnp.int8)
        return (lambda a, b: support_match_pallas(
            a, b, num_disp=p.num_disp, step=p.candidate_step,
            offset=p.candidate_step // 2, support_texture=p.support_texture,
            support_ratio=p.support_ratio, lr_threshold=p.lr_threshold,
            disp_min=p.disp_min, interpret=False,
        ), [rows, rows])
    desc = spec((h, w, 16), jnp.int8)
    mu = spec((h, w), jnp.float32)
    gmask = spec((h, w // p.grid_size, p.num_disp), jnp.bool_)
    return (lambda *a: dense_match_stream_pallas(
        *a, num_disp=p.num_disp, disp_min=p.disp_min,
        plane_radius=p.plane_radius, cell_px=p.grid_size, beta=p.beta,
        gamma=p.gamma, sigma=p.sigma, match_texture=p.match_texture,
        interpret=False,
    ), [desc, desc, mu, mu, gmask, gmask])


@pytest.mark.parametrize("config", list(CONFIGS))
@pytest.mark.parametrize(
    "kernel", ["sobel", "median3x3", "support_match", "dense_match_stream"]
)
def test_kernel_compiles_for_v5e(one_chip, kernel, config):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = _kernel_call(kernel, CONFIGS[config], spec)
    assert "tpu_custom_call" in _compile(fn, *args)


def test_support_wave_compiles_for_v5e(one_chip):
    p, h, w = TSUKUBA.params, TSUKUBA.height, TSUKUBA.width
    img = jax.ShapeDtypeStruct((WAVE, h, w), jnp.float32, sharding=one_chip)
    text = _compile(lambda a, b: pipeline.ielas_support_stage_batched(
        a, b, p, backend=BACKEND), img, img)
    assert "tpu_custom_call" in text


def _gathers(text: str) -> int:
    """The number of ``gather`` instructions in a compiled HLO text."""
    return len(re.findall(r"(?<![\w-])gather\(", text))


@pytest.mark.parametrize("batch", [WAVE, 1])
def test_libelas_order_support_wave_compiles_for_v5e(one_chip, batch):
    """The video configuration's support wave (libelas's redundancy order)
    at KITTI size, as a wave and as the post-hoc re-run's batch 1: it keeps
    the support search's two descriptor-row gathers and adds none."""
    p = dataclasses.replace(KITTI.params, redun_order="sequential")
    img = jax.ShapeDtypeStruct((batch, KITTI.height, KITTI.width), jnp.float32,
                               sharding=one_chip)
    text = _compile(lambda a, b: pipeline.ielas_support_stage_batched(
        a, b, p, backend=BACKEND), img, img)
    assert "tpu_custom_call" in text
    assert _gathers(text) == 2


@pytest.fixture(scope="module")
def dense_wave(one_chip):
    """Compiled HLO text of the (warm) dense wave program, once per shape."""
    texts = {}

    def compiled(config, batch, warm=False) -> str:
        key = (config, batch, warm)
        if key not in texts:
            cfg = CONFIGS[config]
            p, h, w = cfg.params, cfg.height, cfg.width

            def spec(shape, dtype):
                return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

            desc = spec((batch, h, w, 16), jnp.int8)
            if warm:
                texts[key] = _compile(
                    lambda a, b, d: pipeline.ielas_warm_dense_stage_batched(
                        a, b, d, p, backend=BACKEND),
                    desc, desc, spec((batch, h, w), jnp.float32))
            else:
                texts[key] = _compile(
                    lambda a, b, s: pipeline.ielas_dense_stage_batched(
                        a, b, s, p, backend=BACKEND),
                    desc, desc, spec((batch, *p.grid_shape(h, w)), jnp.float32))
        return texts[key]

    return compiled


def test_dense_wave_compiles_for_v5e(dense_wave):
    text = dense_wave(TSUKUBA.name, WAVE)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("config,batch", [
    (TSUKUBA.name, WAVE), (KITTI.name, 1), (KITTI.name, WAVE),
])
def test_dense_wave_holds_no_gather(dense_wave, config, batch):
    """The dense wave's lookups are static slices and selects: an element
    gather costs the chip about 10 ns an element, a frame's worth of them
    most of the program's time."""
    n = _gathers(dense_wave(config, batch))
    assert n == 0, f"{n} gather instructions in the {config} dense wave at batch {batch}"


def test_warm_dense_wave_compiles_for_v5e(dense_wave):
    """The warm scan is plain XLA (no kernel yet); it must still compile
    for the chip under the ``pallas_tpu`` dispatch, and hold no gather."""
    text = dense_wave(TSUKUBA.name, WAVE, warm=True)
    assert "HloModule" in text
    n = _gathers(text)
    assert n == 0, f"{n} gather instructions in the warm dense wave"
