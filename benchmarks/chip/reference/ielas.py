"""Plain reference of the iELAS frame program, for the benchmark's output check.

A straightforward ``jax.numpy`` rendering of the algorithm the served path
computes (ELAS, Geiger et al. ACCV 2010, with the iELAS support
interpolation of arXiv:2104.05112 Sec. II-B), written from the algorithm's
definition and independent of the program: no tiling, no streaming scan,
no kernels, no batching.  Every cost is a materialised ``(D, rows, W)``
volume reduced with ``argmin`` (first minimum wins ties).

Stages, in order:

1. 3x3 Sobel responses (``//4``, clipped to int8) and the libelas
   16-sample descriptor (12 samples of the horizontal map, centre twice,
   4 of the vertical map);
2. support search on the ``candidate_step`` lattice over
   ``[0, num_disp)``: SAD argmin, uniqueness ratio against the best cost
   outside +-1 of the argmin, texture, and a left/right check against the
   right view's own argmin;
3. support filtering (inconsistent, then redundant nodes);
4. the iELAS interpolation: nearest valid nodes within ``s_delta``
   horizontally, else vertically (mean if within ``epsilon``, else the
   min; the trailing window cut by the border extends the leading value),
   else ``const_fill``;
5. dense priors: the plane through each pixel's lattice triangle (cells
   split along TL-BR), the per-cell grid vector (``grid_vector_k`` evenly
   spaced order statistics of the 3x3-cell neighbourhood), and the same
   for the right view from the re-projected support;
6. dense matching of both views: energy ``beta * SAD - log(gamma +
   exp(-(d - mu)^2 / 2 sigma^2))`` over the grid-vector candidates and the
   band ``|d - round(mu)| <= plane_radius``;
7. post-processing: left/right consistency, gap interpolation, 3x3 median
   over valid neighbours.

``ftype`` is the floating type every real-valued quantity is computed in:
``float32`` as the configuration states, ``bfloat16`` for the control that
must fail the check.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

BIG = 1 << 28          # cost of a disparity that leaves the image
BIGF = 1e9             # energy of a disparity outside the candidate set
INVALID = -1.0

DU_OFFSETS = ((-2, 0), (-1, -2), (-1, 0), (-1, 2), (0, -1), (0, 0), (0, 0),
              (0, 1), (1, -2), (1, 0), (1, 2), (2, 0))
DV_OFFSETS = ((-1, 0), (0, -1), (0, 1), (1, 0))


@dataclasses.dataclass(frozen=True)
class Params:
    """The algorithm's parameters, as a configuration file states them."""

    disp_min: int
    disp_max: int
    candidate_step: int
    support_texture: int
    support_ratio: float
    lr_threshold: int
    incon_window: int
    incon_threshold: int
    incon_min_support: int
    redun_max_dist: int
    redun_threshold: int
    s_delta: int
    epsilon: float
    const_fill: float
    grid_size: int
    grid_vector_k: int
    plane_radius: int
    beta: float
    gamma: float
    sigma: float
    match_texture: int
    lr_check_threshold: float
    ipol_gap_width: int
    median_radius: int
    invalid: float

    @property
    def num_disp(self) -> int:
        return self.disp_max - self.disp_min + 1


def params_from(config: dict) -> Params:
    return Params(**config["params"])


def _shift(x, dy, dx, fill):
    """x shifted by (dy, dx), vacated cells set to ``fill``."""
    h, w = x.shape
    p = jnp.pad(x, ((abs(dy), abs(dy)), (abs(dx), abs(dx))), constant_values=fill)
    return p[abs(dy) - dy: abs(dy) - dy + h, abs(dx) - dx: abs(dx) - dx + w]


# ----------------------------------------------------------- descriptors
def descriptors(img):
    """(H, W) image -> (H, W, 16) int32 descriptor."""
    x = img.astype(jnp.int32)
    h, w = x.shape
    p = jnp.pad(x, 1, mode="edge")

    def at(dy, dx):
        return p[1 + dy: 1 + dy + h, 1 + dx: 1 + dx + w]

    gx = (at(-1, -1) + 2 * at(0, -1) + at(1, -1)) - (at(-1, 1) + 2 * at(0, 1) + at(1, 1))
    gy = (at(-1, -1) + 2 * at(-1, 0) + at(-1, 1)) - (at(1, -1) + 2 * at(1, 0) + at(1, 1))
    gx = jnp.pad(jnp.clip(gx // 4, -128, 127), 2, mode="edge")
    gy = jnp.pad(jnp.clip(gy // 4, -128, 127), 2, mode="edge")
    feats = [gx[2 + dy: 2 + dy + h, 2 + dx: 2 + dx + w] for dy, dx in DU_OFFSETS]
    feats += [gy[2 + dy: 2 + dy + h, 2 + dx: 2 + dx + w] for dy, dx in DV_OFFSETS]
    return jnp.stack(feats, axis=-1)


def texture(desc):
    return jnp.sum(jnp.abs(desc), axis=-1)


def sad_volume(src, dst, num_disp, sign):
    """V[d, v, u] = sum_k |src[v, u, k] - dst[v, u + sign*d, k]| over
    d in [0, num_disp); BIG where ``u + sign*d`` leaves the image."""
    h, w, _ = src.shape
    pad = jnp.pad(dst, ((0, 0), (num_disp, num_disp), (0, 0)))
    u = jnp.arange(w)[None, :]

    def one(d):
        moved = jax.lax.dynamic_slice_in_dim(pad, num_disp + sign * d, w, axis=1)
        sad = jnp.sum(jnp.abs(src - moved), axis=-1)
        inside = (u + sign * d >= 0) & (u + sign * d < w)
        return jnp.where(inside, sad, BIG)

    return jax.lax.map(one, jnp.arange(num_disp))


def best_two(cost):
    """(argmin over axis 0, min, min outside +-1 of the argmin)."""
    best = jnp.argmin(cost, axis=0).astype(jnp.int32)
    d = jnp.arange(cost.shape[0]).reshape((-1,) + (1,) * (cost.ndim - 1))
    near = jnp.abs(d - best[None]) <= 1
    return best, jnp.min(cost, axis=0), jnp.min(jnp.where(near, BIG, cost), axis=0)


# ------------------------------------------------------------ support
def support_grid(dl, dr, p: Params, ftype):
    h, w, _ = dl.shape
    step, off = p.candidate_step, p.candidate_step // 2
    gh, gw = h // step, w // step
    rows_l = dl[off: off + (gh - 1) * step + 1: step]
    rows_r = dr[off: off + (gh - 1) * step + 1: step]
    us = jnp.arange(gw) * step + off

    cost_l = sad_volume(rows_l, rows_r, p.num_disp, -1)[:, :, us]     # (D, GH, GW)
    cost_r = sad_volume(rows_r, rows_l, p.num_disp, +1)               # (D, GH, W)
    best_l, min1_l, min2_l = best_two(cost_l)
    best_r, min1_r, min2_r = best_two(cost_r)

    tex_l = texture(rows_l)[:, us]
    tex_r = texture(rows_r)
    ok_l = ((min1_l.astype(ftype) < p.support_ratio * min2_l.astype(ftype))
            & (tex_l >= p.support_texture) & (min1_l < BIG))
    ok_r = ((min1_r.astype(ftype) < p.support_ratio * min2_r.astype(ftype))
            & (tex_r >= p.support_texture) & (min1_r < BIG))
    ur = jnp.clip(us[None, :] - best_l, 0, w - 1)
    d_r = jnp.take_along_axis(best_r, ur, axis=1)
    ok_r_at = jnp.take_along_axis(ok_r, ur, axis=1)
    valid = (ok_l & ok_r_at & (jnp.abs(best_l - d_r) <= p.lr_threshold)
             & (us >= p.disp_min + 2)[None, :])
    return jnp.where(valid, best_l.astype(ftype), jnp.asarray(INVALID, ftype))


def filter_support(g, p: Params):
    inv = jnp.asarray(INVALID, g.dtype)
    count = jnp.zeros(g.shape, jnp.int32)
    r = p.incon_window
    for dy in range(-r, r + 1):
        for dx in range(-r, r + 1):
            if dy or dx:
                nb = _shift(g, dy, dx, INVALID)
                count += ((nb != inv) & (jnp.abs(nb - g) <= p.incon_threshold)).astype(jnp.int32)
    g = jnp.where((g != inv) & (count >= p.incon_min_support), g, inv)

    redundant = jnp.zeros(g.shape, bool)
    for axis in (0, 1):
        before = jnp.zeros(g.shape, bool)
        after = jnp.zeros(g.shape, bool)
        for k in range(1, p.redun_max_dist + 1):
            dy, dx = (k, 0) if axis == 0 else (0, k)
            nb_b = _shift(g, dy, dx, INVALID)
            nb_a = _shift(g, -dy, -dx, INVALID)
            before |= (nb_b != inv) & (jnp.abs(nb_b - g) <= p.redun_threshold)
            after |= (nb_a != inv) & (jnp.abs(nb_a - g) <= p.redun_threshold)
        redundant |= before & after
    return jnp.where((g != inv) & ~redundant, g, inv)


def _nearest_valid(g):
    """Per row: value of and distance to the nearest valid entry on each side
    (distance 2**30 where there is none)."""
    n = g.shape[1]
    col = jnp.broadcast_to(jnp.arange(n)[None, :], g.shape)
    none = jnp.int32(1 << 30)

    def leftwards(x):
        idx = jax.lax.cummax(jnp.where(x != INVALID, col, -1), axis=1)
        val = jnp.take_along_axis(x, jnp.maximum(idx, 0), axis=1)
        return val, jnp.where(idx >= 0, col - idx, none)

    val_l, dist_l = leftwards(g)
    val_r, dist_r = leftwards(jnp.flip(g, axis=1))
    return val_l, dist_l, jnp.flip(val_r, axis=1), jnp.flip(dist_r, axis=1)


def _interpolate_rows(g, p: Params):
    n = g.shape[1]
    val_l, dist_l, val_r, dist_r = _nearest_valid(g)
    has_l, has_r = dist_l <= p.s_delta, dist_r <= p.s_delta
    pair = jnp.where(jnp.abs(val_l - val_r) <= p.epsilon,
                     0.5 * (val_l + val_r), jnp.minimum(val_l, val_r))
    found = has_l & has_r
    value = jnp.where(found, pair, jnp.asarray(INVALID, g.dtype))
    ext = has_l & ((jnp.arange(n)[None, :] + p.s_delta) >= n) & ~found
    return jnp.where(ext, val_l, value), found | ext


def interpolate(g, p: Params):
    h_val, h_found = _interpolate_rows(g, p)
    v_val, v_found = _interpolate_rows(g.T, p)
    filled = jnp.where(h_found, h_val,
                       jnp.where(v_found.T, v_val.T, jnp.asarray(p.const_fill, g.dtype)))
    return jnp.where(g != INVALID, g, filled)


# ------------------------------------------------------------ dense priors
def plane_prior(g, h, w, p: Params):
    gh, gw = g.shape
    step, off = p.candidate_step, p.candidate_step // 2
    ft = g.dtype
    y = jnp.arange(h, dtype=ft)
    x = jnp.arange(w, dtype=ft)
    iy = jnp.clip(jnp.floor((y - off) / step).astype(jnp.int32), 0, gh - 2)
    jx = jnp.clip(jnp.floor((x - off) / step).astype(jnp.int32), 0, gw - 2)
    fy = ((y - off) / step - iy.astype(ft))[:, None]
    fx = ((x - off) / step - jx.astype(ft))[None, :]
    tl = g[iy[:, None], jx[None, :]]
    tr = g[iy[:, None], jx[None, :] + 1]
    bl = g[iy[:, None] + 1, jx[None, :]]
    br = g[iy[:, None] + 1, jx[None, :] + 1]
    upper = tl + fx * (tr - tl) + fy * (br - tr)
    lower = tl + fy * (bl - tl) + fx * (br - bl)
    return jnp.where(fx >= fy, upper, lower)


def grid_vector(g, p: Params):
    gh, gw = g.shape
    npc = p.grid_size // p.candidate_step
    ch, cw = gh // npc, gw // npc
    k = p.grid_vector_k
    ft = g.dtype
    padded = jnp.pad(g[: ch * npc, : cw * npc], npc, constant_values=INVALID)
    win = 3 * npc
    pool = jnp.stack([padded[dy: dy + ch * npc: npc, dx: dx + cw * npc: npc]
                      for dy in range(win) for dx in range(win)], axis=-1)
    valid = pool != INVALID
    ordered = jnp.sort(jnp.where(valid, pool, jnp.asarray(BIGF, ft)), axis=-1)
    n_valid = jnp.sum(valid, axis=-1)
    ranks = jnp.arange(k, dtype=ft)[None, None, :]
    scale = jnp.maximum(n_valid - 1, 0).astype(ft)[..., None]
    idx = jnp.where(n_valid[..., None] > 0,
                    jnp.round(ranks * scale / max(k - 1, 1)).astype(jnp.int32), 0)
    reps = jnp.take_along_axis(ordered, idx, axis=-1)
    return jnp.where(n_valid[..., None] > 0, reps, jnp.asarray(p.const_fill, ft))


def right_view_support(g, p: Params):
    step = p.candidate_step
    ft = g.dtype
    us = jnp.arange(g.shape[1], dtype=ft) * step + step // 2
    proj = us[None, :] - g
    dist = jnp.abs(proj[:, None, :] - us[None, :, None])
    dist = jnp.where((g != INVALID)[:, None, :], dist, jnp.asarray(BIGF, ft))
    k = jnp.argmin(dist, axis=-1)
    dmin = jnp.take_along_axis(dist, k[..., None], axis=-1)[..., 0]
    return jnp.where(dmin <= step, jnp.take_along_axis(g, k, axis=-1),
                     jnp.asarray(INVALID, ft))


# ------------------------------------------------------------ dense matching
def dense_view(src, dst, mu, gv, sign, p: Params):
    """Disparity of the ``src`` view: (H, W) in mu's type, INVALID where no
    candidate is inside the image or the texture is too low."""
    h, w, _ = src.shape
    ft = mu.dtype
    d = jnp.arange(p.num_disp) + p.disp_min
    cost = sad_volume(src, dst, p.num_disp, sign)          # (D, H, W); disp_min == 0

    vals = jnp.clip(jnp.round(gv), p.disp_min, p.disp_max).astype(jnp.int32)
    cells = jnp.any(vals[..., None] == d, axis=-2)          # (CH, CW, D)
    ch, cw = cells.shape[:2]
    cy = jnp.clip(jnp.arange(h) // p.grid_size, 0, ch - 1)
    cx = jnp.clip(jnp.arange(w) // p.grid_size, 0, cw - 1)
    in_cell = jnp.moveaxis(cells[cy][:, cx], -1, 0)         # (D, H, W)
    r = jnp.round(mu)
    lo = jnp.clip(r - p.plane_radius, p.disp_min, p.disp_max)
    hi = jnp.clip(r + p.plane_radius, p.disp_min, p.disp_max)
    df = d.astype(ft)[:, None, None]
    in_band = (df >= lo[None]) & (df <= hi[None])

    diff = df - mu[None]
    prior = -jnp.log(p.gamma + jnp.exp(-(diff * diff) / (2.0 * p.sigma * p.sigma)))
    energy = p.beta * cost.astype(ft) + prior
    energy = jnp.where((in_cell | in_band) & (cost < BIG), energy, jnp.asarray(BIGF, ft))
    best = (jnp.argmin(energy, axis=0) + p.disp_min).astype(ft)
    ok = (jnp.min(energy, axis=0) < BIGF) & (texture(src) >= p.match_texture)
    return jnp.where(ok, best, jnp.asarray(INVALID, ft))


# ------------------------------------------------------------ post-processing
def postprocess(disp_l, disp_r, p: Params):
    h, w = disp_l.shape
    ft = disp_l.dtype
    inv = jnp.asarray(INVALID, ft)
    u = jnp.arange(w, dtype=ft)[None, :]
    ur = jnp.clip(u - disp_l, 0, w - 1).astype(jnp.int32)
    d_r = jnp.take_along_axis(disp_r, ur, axis=1)
    ok = (disp_l != inv) & (d_r != inv) & (jnp.abs(disp_l - d_r) <= p.lr_check_threshold)
    d = jnp.where(ok, disp_l, inv)

    val_l, dist_l, val_r, dist_r = _nearest_valid(d)
    fillable = ((d == inv) & (dist_l < w + 1) & (dist_r < w + 1)
                & (dist_l + dist_r - 1 <= p.ipol_gap_width))
    t = dist_l.astype(ft) / jnp.maximum(dist_l + dist_r, 1).astype(ft)
    linear = val_l + t * (val_r - val_l)
    fill = jnp.where(jnp.abs(val_l - val_r) <= 5.0, linear, jnp.minimum(val_l, val_r))
    d = jnp.where(fillable, fill, d)

    pad = jnp.pad(d, 1, mode="edge")
    wins = jnp.stack([pad[dy: dy + h, dx: dx + w] for dy in range(3) for dx in range(3)])
    wins = jnp.where(wins == inv, d[None], wins)
    med = jnp.sort(wins, axis=0)[4]
    return jnp.where(d == inv, inv, med)


@functools.partial(jax.jit, static_argnames=("p", "ftype"))
def disparity(left, right, p: Params, ftype: str = "float32"):
    """(H, W) left and right images -> (H, W) float32 left disparity."""
    if p.disp_min != 0 or p.median_radius != 1 or p.invalid != INVALID:
        raise ValueError("the reference covers disp_min=0, a 3x3 median, invalid=-1")
    h, w = left.shape
    dl, dr = descriptors(left), descriptors(right)
    sup = interpolate(filter_support(support_grid(dl, dr, p, ftype), p), p)
    sup_r = interpolate(right_view_support(sup, p), p)
    disp_l = dense_view(dl, dr, plane_prior(sup, h, w, p), grid_vector(sup, p), -1, p)
    disp_r = dense_view(dr, dl, plane_prior(sup_r, h, w, p), grid_vector(sup_r, p), +1, p)
    return postprocess(disp_l, disp_r, p).astype(jnp.float32)
