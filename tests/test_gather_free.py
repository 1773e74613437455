"""The dense and support programs' lookups, gather-free, against the gather
formulations they replaced -- bit for bit.

The plane prior's corner lookups, the post-processing lookups, the grid
vector's pooling and rank selection, the right-view re-projection and the
support interpolation's nearest-valid search were once element gathers
(``support[iy, jx]``, ``take_along_axis``, strided advanced indexing).
The references below keep those formulations as plain oracles; every case
compares the float32 bit patterns, so a ``-0.0`` or a last-bit change fails.
"""
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.grid_vector import build_grid_vector
from repro.core.interpolation import interpolate_support
from repro.core.params import ElasParams
from repro.core.postprocess import gap_interpolation, lr_consistency
from repro.core.prior import plane_prior, right_view_support

INVALID = -1.0
KITTI_P = ElasParams(disp_max=127, s_delta=10, epsilon=15.0, const_fill=60.0)


# ------------------------------------------------------------ references
def ref_plane_prior(support, height, width, p):
    gh, gw = support.shape
    step = p.candidate_step
    off = step // 2
    y = jnp.arange(height, dtype=jnp.float32)
    x = jnp.arange(width, dtype=jnp.float32)
    iy = jnp.clip(jnp.floor((y - off) / step).astype(jnp.int32), 0, gh - 2)
    jx = jnp.clip(jnp.floor((x - off) / step).astype(jnp.int32), 0, gw - 2)
    fy = (y - off) / step - iy.astype(jnp.float32)
    fx = (x - off) / step - jx.astype(jnp.float32)
    d_tl = support[iy[:, None], jx[None, :]]
    d_tr = support[iy[:, None], jx[None, :] + 1]
    d_bl = support[iy[:, None] + 1, jx[None, :]]
    d_br = support[iy[:, None] + 1, jx[None, :] + 1]
    fyb = fy[:, None]
    fxb = fx[None, :]
    upper = d_tl + fxb * (d_tr - d_tl) + fyb * (d_br - d_tr)
    lower = d_tl + fyb * (d_bl - d_tl) + fxb * (d_br - d_bl)
    return jnp.where(fxb >= fyb, upper, lower)


def ref_right_view_support(support_left, p):
    gh, gw = support_left.shape
    step = p.candidate_step
    us = jnp.arange(gw, dtype=jnp.float32) * step + step // 2
    valid = support_left != INVALID
    proj = us[None, :] - support_left
    dist = jnp.abs(proj[:, None, :] - us[None, :, None])
    dist = jnp.where(valid[:, None, :], dist, jnp.float32(1e9))
    k = jnp.argmin(dist, axis=-1)
    dmin = jnp.take_along_axis(dist, k[..., None], axis=-1)[..., 0]
    dval = jnp.take_along_axis(support_left, k, axis=-1)
    return jnp.where(dmin <= step, dval, INVALID)


def ref_build_grid_vector(support, p):
    gh, gw = support.shape
    npc = p.grid_size // p.candidate_step
    ch, cw = gh // npc, gw // npc
    k = p.grid_vector_k
    win = 3 * npc
    padded = jnp.pad(support[: ch * npc, : cw * npc], ((npc, npc), (npc, npc)),
                     constant_values=INVALID)
    pool = jnp.stack([padded[dy: dy + ch * npc: npc, dx: dx + cw * npc: npc]
                      for dy in range(win) for dx in range(win)], axis=-1)
    valid = pool != INVALID
    sorted_pool = jnp.sort(jnp.where(valid, pool, jnp.float32(1e9)), axis=-1)
    n_valid = jnp.sum(valid, axis=-1)
    ranks = jnp.arange(k, dtype=jnp.float32)[None, None, :]
    scale = jnp.maximum(n_valid - 1, 0).astype(jnp.float32)[..., None]
    idx = jnp.where(n_valid[..., None] > 0,
                    jnp.round(ranks * scale / jnp.maximum(k - 1, 1)).astype(jnp.int32), 0)
    reps = jnp.take_along_axis(sorted_pool, idx, axis=-1)
    return jnp.where(n_valid[..., None] > 0, reps, p.const_fill)


def _ref_nearest_lr(grid):
    """Unbounded nearest valid entry on each side by ``cummax`` + gather."""
    valid = grid != INVALID
    col = jnp.broadcast_to(jnp.arange(grid.shape[1])[None, :], grid.shape)
    big = jnp.int32(1 << 30)
    idx_l = jax.lax.cummax(jnp.where(valid, col, -1), axis=1)
    val_l = jnp.take_along_axis(grid, jnp.maximum(idx_l, 0), axis=1)
    dist_l = jnp.where(idx_l >= 0, col - idx_l, big)
    rev = jnp.flip(grid, axis=1)
    idx_r = jax.lax.cummax(jnp.where(rev != INVALID, col, -1), axis=1)
    val_r = jnp.flip(jnp.take_along_axis(rev, jnp.maximum(idx_r, 0), axis=1), axis=1)
    dist_r = jnp.flip(jnp.where(idx_r >= 0, col - idx_r, big), axis=1)
    return val_l, dist_l, val_r, dist_r


def _ref_axis_interpolation(grid, p, border_extend):
    gw = grid.shape[1]
    val_l, dist_l, val_r, dist_r = _ref_nearest_lr(grid)
    has_l = dist_l <= p.s_delta
    has_r = dist_r <= p.s_delta
    pair = jnp.where(jnp.abs(val_l - val_r) <= p.epsilon,
                     0.5 * (val_l + val_r), jnp.minimum(val_l, val_r))
    found = has_l & has_r
    value = jnp.where(found, pair, INVALID)
    if border_extend:
        ext = has_l & ((jnp.arange(gw)[None, :] + p.s_delta) >= gw) & ~found
        value = jnp.where(ext, val_l, value)
        found = found | ext
    return value, found


def ref_interpolate_support(grid, p, border_extend=True):
    h_val, h_found = _ref_axis_interpolation(grid, p, border_extend)
    v_val_t, v_found_t = _ref_axis_interpolation(grid.T, p, border_extend)
    filled = jnp.where(h_found, h_val,
                       jnp.where(v_found_t.T, v_val_t.T, p.const_fill))
    return jnp.where(grid != INVALID, grid, filled)


def ref_lr_consistency(disp_left, disp_right, p):
    w = disp_left.shape[1]
    u = jnp.arange(w, dtype=jnp.float32)[None, :]
    ur = jnp.clip(u - disp_left, 0, w - 1).astype(jnp.int32)
    d_r = jnp.take_along_axis(disp_right, ur, axis=1)
    ok = ((disp_left != INVALID) & (d_r != INVALID)
          & (jnp.abs(disp_left - d_r) <= p.lr_check_threshold))
    return jnp.where(ok, disp_left, INVALID)


def ref_gap_interpolation(disp, p):
    val_l, dist_l, val_r, dist_r = _ref_nearest_lr(disp)
    gap = dist_l + dist_r - 1
    fillable = ((disp == INVALID) & (dist_l < disp.shape[1] + 1)
                & (dist_r < disp.shape[1] + 1) & (gap <= p.ipol_gap_width))
    t = dist_l.astype(jnp.float32) / jnp.maximum(dist_l + dist_r, 1).astype(jnp.float32)
    linear = val_l + t * (val_r - val_l)
    fill = jnp.where(jnp.abs(val_l - val_r) <= 5.0, linear, jnp.minimum(val_l, val_r))
    return jnp.where(fillable, fill, disp)


# ------------------------------------------------------------ inputs
def _grid(rng, shape, lo, hi, invalid_share, whole=False):
    """A support grid: values in [lo, hi] (whole numbers if ``whole``), a
    share of INVALID, INVALID runs touching the left and right borders, an
    all-INVALID row and column."""
    g = rng.uniform(lo, hi, shape).astype(np.float32)
    if whole:
        g = np.round(g)
    g[rng.random(shape) < invalid_share] = INVALID
    g[1, :3] = INVALID
    g[2, -4:] = INVALID
    g[shape[0] // 2] = INVALID
    g[:, shape[1] // 3] = INVALID
    return g


def _windows(p, shape, reach):
    """Rows whose valid entries lie exactly ``reach`` and ``reach + 1`` apart
    (a gap of ``reach - 1`` and of ``reach``), plus ones ``reach`` from
    either border."""
    g = np.full(shape, INVALID, np.float32)
    w = shape[1]

    def put(row, cols, vals):
        for c, v in zip(cols, vals):
            if 0 <= c < w:
                g[row, c] = v

    put(0, [0, reach + 1], [10.0, 20.0])                # gap of exactly reach
    put(1, [0, reach + 2], [10.0, 12.0])                # one more
    put(2, [3, 3 + reach], [30.0, 33.0])                # nodes reach apart
    put(3, [3, 4 + reach], [30.0, 31.0])                # one more
    put(4, [reach], [7.0])                              # reach from the left border
    put(5, [w - 1 - reach], [9.0])                      # reach from the right border
    g[6, ::reach + 1] = 5.0
    g[7, ::reach + 2] = 50.0
    return g


def _dense_pair(rng, p, h, w):
    """(disp_left, disp_right) as a dense scan gives them: whole
    disparities in [disp_min, disp_max] or INVALID, with d = disp_min and
    disp_max present, u < d near the left border, INVALID runs touching
    both borders and an all-INVALID row."""
    dl = rng.integers(p.disp_min, p.disp_max + 1, (h, w)).astype(np.float32)
    dl[rng.random((h, w)) < 0.2] = INVALID
    dl[0, :] = p.disp_min
    dl[1, :] = p.disp_max                               # u < d for u < disp_max
    dl[2, : min(w, 9)] = INVALID
    dl[3, -9:] = INVALID
    dl[4, :] = INVALID
    # The right view agrees at the matched column in most pixels, so the
    # check passes as well as fails.
    dr = np.full((h, w), INVALID, np.float32)
    for v in range(h):
        for u in range(w):
            if dl[v, u] != INVALID:
                ur = min(max(u - int(dl[v, u]), 0), w - 1)
                dr[v, ur] = dl[v, u] + rng.choice([0.0, 0.0, 1.0, -1.0, 2.0])
    holes = dr == INVALID
    dr[holes] = rng.integers(p.disp_min, p.disp_max + 1, holes.sum())
    dr[rng.random((h, w)) < 0.1] = INVALID
    dr[:, 0] = INVALID
    return dl, dr


def _gap_rows(rng, p, h, w):
    g = _windows(p, (h, w), p.ipol_gap_width)
    rest = rng.uniform(0, 60, (h - 8, w)).astype(np.float32)
    rest[rng.random(rest.shape) < 0.5] = INVALID
    rest[0, :5] = INVALID
    rest[1, -5:] = INVALID
    rest[2] = INVALID
    g[8:] = rest
    return g


# (stage, case) -> (new, reference, arguments, static arguments)
def _case(stage, case):
    rng = np.random.default_rng(zlib.crc32(f"{stage}/{case}".encode()))
    if stage == "plane_prior":
        p, h, w = {"kitti": (KITTI_P, 375, 1242), "odd": (ElasParams(), 37, 53),
                   "step7": (ElasParams(candidate_step=7), 50, 131)}[case]
        gh, gw = p.grid_shape(h, w)
        return plane_prior, ref_plane_prior, (_grid(rng, (gh, gw), 0, 127, 0.0),), (h, w, p)
    if stage == "lr_consistency":
        p, h, w = {"kitti": (KITTI_P, 12, 1242),
                   "disp_min": (ElasParams(disp_min=4, disp_max=40), 10, 70),
                   "narrow": (ElasParams(disp_max=63), 8, 40)}[case]
        return lr_consistency, ref_lr_consistency, _dense_pair(rng, p, h, w), (p,)
    if stage == "gap_interpolation":
        p, h, w = {"kitti": (KITTI_P, 16, 1242),
                   "gap3": (ElasParams(ipol_gap_width=3), 14, 30),
                   "narrow": (ElasParams(), 12, 6)}[case]
        return gap_interpolation, ref_gap_interpolation, (_gap_rows(rng, p, h, w),), (p,)
    if stage == "build_grid_vector":
        p, shape = {"kitti": (KITTI_P, (75, 248)), "odd": (ElasParams(), (13, 22)),
                    "sparse": (ElasParams(), (16, 16))}[case]
        share = 0.95 if case == "sparse" else 0.3
        return build_grid_vector, ref_build_grid_vector, (_grid(rng, shape, 0, 127, share),), (p,)
    if stage == "right_view_support":
        p, shape = {"kitti": (KITTI_P, (75, 248)), "odd": (ElasParams(), (9, 23)),
                    "disp_min": (ElasParams(disp_min=4, disp_max=40), (8, 30))}[case]
        g = _grid(rng, shape, p.disp_min, p.disp_max, 0.3, whole=True)   # ties
        return right_view_support, ref_right_view_support, (g,), (p,)
    if stage == "interpolate_support":
        p, shape, extend = {
            "kitti": (KITTI_P, (75, 248), True),
            "windows": (ElasParams(s_delta=5), (12, 40), True),
            "no_extend": (ElasParams(s_delta=5), (12, 40), False),
            "wide": (ElasParams(s_delta=32), (20, 24), True),
        }[case]
        if case == "kitti":
            g = _grid(rng, shape, 0, 127, 0.6)
        else:
            g = _windows(p, shape, p.s_delta)
            g[8:] = _grid(rng, (shape[0] - 8, shape[1]), 0, 60, 0.7)
            g[:, 8 + shape[1] // 2] = INVALID
        return (lambda s, q: interpolate_support(s, q, border_extend=extend),
                lambda s, q: ref_interpolate_support(s, q, border_extend=extend),
                (g,), (p,))
    raise KeyError(stage)


CASES = [
    ("plane_prior", "kitti"), ("plane_prior", "odd"), ("plane_prior", "step7"),
    ("lr_consistency", "kitti"), ("lr_consistency", "disp_min"),
    ("lr_consistency", "narrow"),
    ("gap_interpolation", "kitti"), ("gap_interpolation", "gap3"),
    ("gap_interpolation", "narrow"),
    ("build_grid_vector", "kitti"), ("build_grid_vector", "odd"),
    ("build_grid_vector", "sparse"),
    ("right_view_support", "kitti"), ("right_view_support", "odd"),
    ("right_view_support", "disp_min"),
    ("interpolate_support", "kitti"), ("interpolate_support", "windows"),
    ("interpolate_support", "no_extend"), ("interpolate_support", "wide"),
]


def _bits(x):
    return np.asarray(x, np.float32).view(np.uint32)


@pytest.mark.parametrize("stage,case", CASES, ids=[f"{s}-{c}" for s, c in CASES])
def test_gather_free_matches_gather_reference(stage, case):
    new, ref, arrays, static = _case(stage, case)
    args = [jnp.asarray(a) for a in arrays]
    got = new(*args, *static)
    want = jax.jit(lambda *a: ref(*a, *static))(*args)
    assert got.shape == want.shape
    np.testing.assert_array_equal(_bits(got), _bits(want))


def test_gather_free_stages_hold_no_gather():
    """The rewritten stages lower to no ``gather`` at a KITTI frame."""
    p, h, w = KITTI_P, 375, 1242
    gh, gw = p.grid_shape(h, w)
    grid = jax.ShapeDtypeStruct((gh, gw), jnp.float32)
    disp = jax.ShapeDtypeStruct((h, w), jnp.float32)

    def stages(support, dl, dr):
        sup_r = interpolate_support(right_view_support(support, p), p)
        return (plane_prior(sup_r, h, w, p), build_grid_vector(sup_r, p),
                gap_interpolation(lr_consistency(dl, dr, p), p))

    text = jax.jit(stages).lower(grid, disp, disp).as_text()
    assert " gather" not in text and "stablehlo.gather" not in text
