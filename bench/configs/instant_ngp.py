"""Plain reference of the served quantized Instant-NGP field and its
renderer, in straightforward jax.numpy and float32 at highest precision.

It imports nothing of the program under test. It takes the float field
weights (the model checkpoint) and the configuration file, and derives
everything the serve path derives on its own: the per-tensor quantization
grids of the hash tables and weights (the paper's Eq. 4-7 on the
conventional symmetric weight grid the configuration states), the
activation ranges (min/max of each linear's input in the float field at
the configuration's calibration points, rounded outward to
`act_range_sig_bits` significant bits), and the occupancy grid (density
of the float field on a supersampled grid, max-pooled, thresholded and
dilated).

Rendering follows the configuration: `n_samples` evenly spaced depths in
[near, far]; a sample counts only strictly inside the [-0.5, 0.5)^3 box
and in an occupied cell; alpha compositing with exclusive-cumprod
transmittance and a white background (Mildenhall et al. 2020, Eq. 3).

Layer equations (Muller et al. 2022): hash encoding over L levels of
resolution floor(N_min b^l), dense indexing where (N_l+1)^3 <= T and the
spatial hash (x*1 ^ y*2654435761 ^ z*805459861) mod T elsewhere,
trilinear interpolation of F features; density MLP enc -> 64 -> ReLU ->
1 + 15 (density exp(clip(., -10, 10))); color MLP (15 geometry features
++ the 16 spherical-harmonic coefficients of the view direction, bands
0-3) -> 64 -> ReLU -> 64 -> ReLU -> 3 -> sigmoid. Each linear quantizes
its input (asymmetric) and its weight (symmetric) at the policy's bits.

A quantized linear is an integer dot product of activation and weight
codes, scaled once: y = s_x s_w sum((q_x - z) q_w) + b (exact in float32
at these sizes). `precision` selects how the gathered hash-table values
reach the interpolation: "highest" (the configuration's: exact),
"high" (as three bfloat16 passes of a one-hot matmul would give them)
or "bfloat16" (one pass) -- the last two are controls, the precisions a
later change might be tempted to take for the gather.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

PRIMES = (1, 2654435761, 805459861)
LINEARS = ("sigma/0", "sigma/1", "color/0", "color/1", "color/2")
HIGHEST = jax.lax.Precision.HIGHEST


def resolutions(m: Dict) -> List[int]:
    L = m["n_levels"]
    lo, hi = m["base_resolution"], m["max_resolution"]
    b = 1.0 if L == 1 else float(np.exp((np.log(hi) - np.log(lo)) / (L - 1)))
    return [int(np.floor(lo * b ** l)) for l in range(L)]


# ---------------------------------------------------------------------------
# Arithmetic
# ---------------------------------------------------------------------------
def _bf(x):
    """Round float32 to the nearest bfloat16 value (ties to even) by bit
    arithmetic: a float32 -> bfloat16 -> float32 round trip may be elided
    by the compiler, this cannot."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    u = u + jnp.uint32(0x7FFF) + ((u >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(u & jnp.uint32(0xFFFF0000), jnp.float32)


def gathered(v, precision: str):
    """Table values as a one-hot matmul at `precision` returns them."""
    if precision == "highest":
        return v
    hi = _bf(v)
    if precision == "high":
        return hi + _bf(v - hi)
    if precision == "bfloat16":
        return hi
    raise ValueError(f"unknown precision {precision!r}")


def quant_weight(w, bits: int, codes: bool = False):
    """Symmetric grid (Eq. 4-5) with q in [-(2^(b-1) - 1), 2^(b-1) - 1],
    which b bits and int8 hold. Called outside `jit`, one operation at a
    time: inside a fused program the compiler may rewrite the divisions
    (a reciprocal, a reassociation), which moves the scale or a code by
    one bit. Returns the dequantized tensor, or with `codes` the
    (codes, scale) pair."""
    b = jnp.asarray(bits, jnp.float32)
    r = jnp.maximum(jnp.max(w) - jnp.min(w), 1e-8)
    s = r / (2.0 ** b - 1.0)
    top = 2.0 ** (b - 1.0) - 1.0
    q = jnp.clip(jnp.round(w / s), -top, top)
    return (q, s) if codes else q * s


def round_range(lo: float, hi: float, sig_bits: int):
    """A calibrated range rounded outward to `sig_bits` significant bits
    of its larger endpoint (the configuration's calibration rule: ulp-level
    differences between two computations of the min and max then give the
    same range)."""
    m, e = math.frexp(max(abs(lo), abs(hi), 1e-30))  # top = m * 2^e
    step = 2.0 ** ((e - 1 if m == 0.5 else e) - sig_bits)
    return math.floor(lo / step) * step, math.ceil(hi / step) * step


def act_grid(lo: float, hi: float, bits: int):
    """Scale, zero point and top code of the asymmetric activation grid
    (Eq. 6), for a calibrated range given as Python floats (their
    difference is taken in double precision, then rounded to float32)."""
    levels = jnp.float32(2.0 ** bits - 1.0)
    r = jnp.maximum(jnp.float32(hi - lo), jnp.float32(1e-8))
    s = r / levels
    z = jnp.round((1.0 - jnp.float32(hi) / r) * levels)
    return s, z, levels


def act_codes(x, s, z, levels):
    """Eq. 7 on the grid of `act_grid` (passed as arrays), shifted by the
    zero point: the integers q_x - z."""
    return jnp.clip(jnp.round(x / s + z), 0.0, levels) - z


def sh16(d):
    """The 16 real spherical-harmonic coefficients of bands 0-3."""
    x, y, z = d[..., 0], d[..., 1], d[..., 2]
    xx, yy, zz = x * x, y * y, z * z
    xy, yz, xz = x * y, y * z, x * z
    return jnp.stack([
        jnp.full_like(x, 0.28209479177387814),
        -0.48860251190291987 * y, 0.48860251190291987 * z,
        -0.48860251190291987 * x,
        1.0925484305920792 * xy, -1.0925484305920792 * yz,
        0.94617469575755997 * zz - 0.31539156525251999,
        -1.0925484305920792 * xz, 0.54627421529603959 * (xx - yy),
        0.59004358992664352 * y * (-3.0 * xx + yy),
        2.8906114426405538 * x * y * z,
        0.45704579946446572 * y * (1.0 - 5.0 * zz),
        0.3731763325901154 * z * (5.0 * zz - 3.0),
        0.45704579946446572 * x * (1.0 - 5.0 * zz),
        1.4453057213202769 * z * (xx - yy),
        0.59004358992664352 * x * (-xx + 3.0 * yy),
    ], axis=-1)


# ---------------------------------------------------------------------------
# The field
# ---------------------------------------------------------------------------
def encode(tables, pts, m: Dict, precision: str = "highest"):
    """(P, 3) points in [0, 1] -> (P, L*F) interpolated features."""
    T = 1 << m["log2_table_size"]
    corners = np.array([[(c >> a) & 1 for a in range(3)] for c in range(8)],
                       np.int32)
    feats = []
    for l, res in enumerate(resolutions(m)):
        x = pts * res
        x0 = jnp.floor(x)
        frac = x - x0
        c = jnp.clip(jnp.clip(x0.astype(jnp.int32), 0, res)[:, None, :]
                     + corners[None], 0, res).astype(jnp.uint32)
        if (res + 1) ** 3 <= T:
            idx = c[..., 0] + c[..., 1] * (res + 1) + c[..., 2] * (res + 1) ** 2
        else:
            n = tables[l].shape[0]
            idx = (c[..., 0] * jnp.uint32(PRIMES[0])
                   ^ c[..., 1] * jnp.uint32(PRIMES[1])
                   ^ c[..., 2] * jnp.uint32(PRIMES[2])) % jnp.uint32(n)
        cf = jnp.asarray(corners, jnp.float32)[None]
        w = jnp.prod(cf * frac[:, None, :] + (1.0 - cf) * (1.0 - frac[:, None, :]),
                     axis=-1)
        vals = gathered(tables[l][idx.astype(jnp.int32)], precision)  # (P, 8, F)
        feats.append(jnp.sum(vals * w[..., None], axis=1))
    return jnp.concatenate(feats, axis=-1)


def field(q, pts, dirs, m: Dict, precision: str, taps=None):
    """Density and color of the field. `q` holds "tables" (per level),
    "b" per linear, and either float weights "w" (the float field) or the
    quantized form: weight codes "wq", weight scales "ws" and "act"
    ((scale, zero point, top code) per linear)."""
    def lin(i, name, x):
        if taps is not None:
            taps[name] = x
        if "act" not in q:
            return jnp.matmul(x, q["w"][name], precision=HIGHEST) + q["b"][name]
        s, z, levels = q["act"][i]
        acc = jnp.matmul(act_codes(x, s, z, levels), q["wq"][name],
                         precision=HIGHEST)  # integers, exact
        return acc * s * q["ws"][name] + q["b"][name]

    h = jax.nn.relu(lin(0, "sigma/0", encode(q["tables"], pts, m, precision)))
    h = lin(1, "sigma/1", h)
    sigma = jnp.exp(jnp.clip(h[:, 0], -10.0, 10.0))
    c = jnp.concatenate([h[:, 1:], sh16(dirs)], axis=-1)
    c = jax.nn.relu(lin(2, "color/0", c))
    c = jax.nn.relu(lin(3, "color/1", c))
    rgb = jax.nn.sigmoid(lin(4, "color/2", c))
    return sigma, rgb


def float_field(params: Dict, m: Dict) -> Dict:
    return {
        "tables": [params["hash"][f"level_{l}"] for l in range(m["n_levels"])],
        "w": {n: params[n]["w"] for n in LINEARS},
        "b": {n: params[n]["b"] for n in LINEARS},
    }


def sample_depths(cfg: Dict) -> np.ndarray:
    return np.linspace(cfg["near"], cfg["far"], cfg["n_samples"], dtype=np.float32)


@functools.partial(jax.jit, static_argnames=("statics",))
def render_rays(arrays, occ, ro, rd, *, statics):
    """Colors (R, 3) of rays (R, 3) through the quantized field."""
    precision, m_items, near, far, n_samples, white_bg = statics
    m = dict(m_items)
    q = arrays
    t = jnp.asarray(np.linspace(near, far, n_samples, dtype=np.float32))
    R, S = ro.shape[0], t.shape[0]
    pts = ro[:, None, :] + rd[:, None, :] * t[None, :, None]
    unit = jnp.clip(pts + 0.5, 0.0, 1.0)
    inside = jnp.all((pts > -0.5) & (pts < 0.5), axis=-1)
    G = occ.shape[0]
    cell = jnp.clip((unit * G).astype(jnp.int32), 0, G - 1)
    active = inside & (occ[cell[..., 0], cell[..., 1], cell[..., 2]] > 0.5)
    dirs = jnp.broadcast_to(rd[:, None, :], pts.shape).reshape(-1, 3)
    sigma, rgb = field(q, unit.reshape(-1, 3), dirs, m, precision)
    sigma = jnp.where(active, sigma.reshape(R, S), 0.0)
    rgb = jnp.where(active[..., None], rgb.reshape(R, S, 3), 0.0)
    delta = jnp.concatenate([jnp.diff(t), jnp.full((1,), 1e10)])[None]
    alpha = 1.0 - jnp.exp(-sigma * delta)
    trans = jnp.cumprod(1.0 - alpha + 1e-10, axis=-1)
    trans = jnp.concatenate([jnp.ones_like(trans[:, :1]), trans[:, :-1]], -1)
    w = trans * alpha
    color = jnp.sum(w[..., None] * rgb, axis=1)
    if white_bg:
        color = color + (1.0 - jnp.sum(w, axis=-1, keepdims=True))
    return color


@functools.partial(jax.jit, static_argnames=("m_items", "bake"))
def _prepare(fq, calib_pts, calib_dirs, grid_pts, grid_dirs, *, m_items, bake):
    """From the float field: each linear's input range at the calibration
    points ((5, 2) min and max) and the occupancy grid."""
    m = dict(m_items)
    taps: Dict = {}
    field(fq, calib_pts, calib_dirs, m, "highest", taps)
    ranges = jnp.stack([jnp.stack([jnp.min(taps[n]), jnp.max(taps[n])])
                        for n in LINEARS])
    res, ss, threshold, dilate = bake
    fine = res * ss
    sig = field(fq, grid_pts, grid_dirs, m, "highest")[0].reshape(fine, fine, fine)
    sig = jax.lax.reduce_window(sig, -jnp.inf, jax.lax.max, (ss,) * 3,
                                (ss,) * 3, "VALID")
    occ = (sig > threshold).astype(jnp.float32)
    for _ in range(dilate):
        occ = jax.lax.reduce_window(occ, -jnp.inf, jax.lax.max, (3, 3, 3),
                                    (1, 1, 1), "SAME")
    return ranges, occ


class Reference:
    """The configuration's served field, derived from float weights."""

    def __init__(self, params: Dict, cfg: Dict, calib_pts, calib_dirs,
                 precision: str = "highest"):
        self.cfg, self.m, self.precision = cfg, cfg["model"], precision
        m, pol = self.m, cfg["policy"]
        if m["sh_degree"] != 3 or pol["paper_exact"]:
            raise ValueError("the reference serves 16 SH coefficients (sh_degree 3) "
                             "on the conventional weight grid (paper_exact false)")
        fq = float_field(params, m)
        res, ss = cfg["occ_resolution"], cfg["occ_supersample"]
        axis = (np.arange(res * ss, dtype=np.float32) + 0.5) / (res * ss)
        grid = np.stack(np.meshgrid(axis, axis, axis, indexing="ij"), -1).reshape(-1, 3)
        with jax.default_matmul_precision("highest"):
            ranges, self.occ = _prepare(
                fq, jnp.asarray(calib_pts), jnp.asarray(calib_dirs),
                jnp.asarray(grid), jnp.asarray(np.broadcast_to(
                    np.float32([0.0, 0.0, 1.0]), grid.shape)),
                m_items=tuple(sorted(m.items())),
                bake=(res, ss, float(cfg["occ_threshold"]), cfg["occ_dilate"]))
        tables = [quant_weight(t, b) for t, b in zip(fq["tables"], pol["hash_bits"])]
        wq = {n: quant_weight(fq["w"][n], pol["linears"][n]["weight"], codes=True)
              for n in LINEARS}
        ranges = np.asarray(ranges)
        self.ranges = [round_range(float(ranges[i, 0]), float(ranges[i, 1]),
                                   cfg["act_range_sig_bits"])
                       for i in range(len(LINEARS))]
        act = [act_grid(lo, hi, pol["linears"][n]["act"])
               for (lo, hi), n in zip(self.ranges, LINEARS)]
        self.statics = (
            precision, tuple(sorted(m.items())),
            float(cfg["near"]), float(cfg["far"]), int(cfg["n_samples"]),
            bool(cfg["white_bg"]))
        self.arrays = {"tables": tables, "wq": {n: c for n, (c, _) in wq.items()},
                       "ws": {n: s for n, (_, s) in wq.items()},
                       "b": fq["b"], "act": act}

    def render(self, ro: np.ndarray, rd: np.ndarray, block: int = 8192) -> np.ndarray:
        """(N, 3) colors of rays (N, 3), in blocks of `block` rays."""
        out = []
        with jax.default_matmul_precision("highest"):
            for s in range(0, ro.shape[0], block):
                a, b = ro[s:s + block], rd[s:s + block]
                n = a.shape[0]
                if n < block:
                    a = np.concatenate([a, np.full((block - n, 3), 10.0, np.float32)])
                    b = np.concatenate([b, np.zeros((block - n, 3), np.float32)])
                out.append(np.asarray(render_rays(
                    self.arrays, self.occ, jnp.asarray(a), jnp.asarray(b),
                    statics=self.statics))[:n])
        return np.concatenate(out)
