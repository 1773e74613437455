"""A stand-in reference module with a warm answer, for the harness's own tests.

The cold answer is the plain reference's (``reference/ielas.py``).  The warm
answer is built on the program's own pieces: descriptors, the warm priors
re-gridded from the previous disparity, the band-only warm oracle of
``repro.kernels.ref`` over the whole frame at once, and post-processing.
So it checks the harness's plumbing (which prior seeds which frame, which
frames may go warm), not the warm algorithm: a configuration's own reference
module brings a plain warm reference.  ``ftype`` rounds the priors through
that type, so the bfloat16 control differs from the float32 answer.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from benchmarks.chip import harness

_plain = harness.load_module(harness.HERE / "reference" / "ielas.py")
params_from = _plain.params_from
disparity = _plain.disparity


def warm_disparity(left, right, prior, p, warm_band: int, ftype: str = "float32"):
    """(H, W) images and the previous frame's delivered (H, W) disparity ->
    the (H, W) float32 warm disparity."""
    from repro.core.params import ElasParams

    return _warm(left, right, prior, ElasParams(**dataclasses.asdict(p)), warm_band, ftype)


@functools.partial(jax.jit, static_argnames=("p", "warm_band", "ftype"))
def _warm(left, right, prior, p, warm_band, ftype):
    from repro.core import pipeline
    from repro.core.descriptor import extract
    from repro.kernels.ref import dense_match_rows_warm_ref

    h, w = left.shape
    mu_l, mu_r = (mu.astype(ftype).astype(jnp.float32)
                  for mu in pipeline._warm_priors(prior, h, w, p))
    disp_l, disp_r = dense_match_rows_warm_ref(
        extract(left), extract(right), mu_l, mu_r, num_disp=p.num_disp,
        disp_min=p.disp_min, warm_band=warm_band, beta=p.beta, sigma=p.sigma,
        match_texture=p.match_texture)
    return pipeline.postprocess(disp_l, disp_r, p)
