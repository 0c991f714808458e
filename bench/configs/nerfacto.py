"""Plain reference of the served quantized Nerfacto field and its
renderer, in straightforward jax.numpy and float32 at highest precision.

It imports nothing of the program under test. It takes the float
weights (the model checkpoint) and the configuration file, and derives
everything the serve path derives on its own: the per-tensor
quantization grids of the three fields' hash tables and linears (the
conventional symmetric weight grid the configuration states), the
activation ranges (min/max of each linear's input in the float render
of the configuration's calibration rays, rounded outward to
`act_range_sig_bits` significant bits) and the served appearance input
(the mean of the per-image embeddings). Hash encoding, quantization and
spherical harmonics are `instant_ngp.py`'s.

Rendering follows nerfstudio's Nerfacto in eval (Tancik et al. 2023;
`NerfactoModelConfig`, `UniformLinDispPiecewiseSampler`, `PDFSampler`,
`HashMLPDensityField`, `NerfactoField`):

- spacing s = g(t), g(t) = t/2 below 1 and 1 - 1/(2t) above; the
  `n_initial` first intervals uniform in s between g(near) and g(far),
  mapped back by g^-1; samples at Euclidean interval midpoints, delta =
  end - start;
- positions contracted, c(x) = x for |x|_inf <= 1 and
  (2 - 1/|x|_inf) x/|x|_inf beyond, fed as (c(x) + 2)/4, density zero
  outside (0, 1)^3;
- proposal field k: its hash grid, linear -> ReLU -> linear,
  density exp; weights w_i = (1 - e^{-sigma_i delta_i})
  e^{-sum_{j<i} sigma_j delta_j};
- resampling: weights + `histogram_padding`, the 1e-5 padding, normalized,
  cumsum clamped at 1 with 0 prepended; u_j = (j + 1/2)/(n + 1);
  searchsorted(cdf, u, "right"); linear interpolation between the
  previous spacing edges; the new n intervals are consecutive edges;
- main field: hash grid -> 64 -> ReLU -> 1 + 15, density exp; color MLP
  on [16 SH coefficients of the direction, 15 geometry features, the
  appearance input] -> 64 -> 64 -> 3 -> sigmoid;
- composite over the final intervals' own deltas on a white background.

Departures (the configuration's `assumed`): the linears keep biases
(nerfstudio's tcnn MLPs have none), resolutions are floor(N_min b^l) as
in Instant-NGP, and the background is white, not `last_sample`.

A quantized linear is an integer dot product of activation and weight
codes, scaled once. `precision` selects how gathered table values reach
the interpolation ("highest", the configuration's; "high" and
"bfloat16", the controls); `skip_pass` leaves one proposal pass out
(resampling straight to the next count from the pass before), a control
of the sampler.
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

_spec = importlib.util.spec_from_file_location(
    "bench_reference_nerfacto_ngp", Path(__file__).with_name("instant_ngp.py"))
ngp = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ngp)

HIGHEST = jax.lax.Precision.HIGHEST
MAIN = ("sigma/0", "sigma/1", "color/0", "color/1", "color/2")


def linears(m: Dict):
    names = list(MAIN)
    for k in range(len(m["proposals"])):
        names += [f"prop{k + 1}/0", f"prop{k + 1}/1"]
    return names


def _g(t: float) -> float:
    return t / 2.0 if t < 1.0 else 1.0 - 1.0 / (2.0 * t)


def euclidean(s, near: float, far: float):
    y = s * np.float32(_g(far)) + (1.0 - s) * np.float32(_g(near))
    return jnp.where(y < 0.5, 2.0 * y, 1.0 / (2.0 - 2.0 * y))


def contract(x):
    """Field coordinates and selector of world points (P, 3)."""
    n = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    safe = jnp.where(n <= 1.0, 1.0, n)
    c = jnp.where(n <= 1.0, x, (2.0 - 1.0 / safe) * (x / safe))
    u = (c + 2.0) / 4.0
    sel = jnp.all((u > 0.0) & (u < 1.0), axis=-1)
    return jnp.where(sel[:, None], u, 0.0), sel


def weights(sigma, delta):
    tau = sigma * delta
    before = jnp.concatenate(
        [jnp.zeros_like(tau[:, :1]), jnp.cumsum(tau[:, :-1], axis=-1)], -1)
    return (1.0 - jnp.exp(-tau)) * jnp.exp(-before)


_searchsorted = jax.vmap(lambda a, v: jnp.searchsorted(a, v, side="right"))


def resample(bins, w, n: int, padding: float):
    w = w + padding
    total = jnp.sum(w, axis=-1, keepdims=True)
    pad = jnp.maximum(1e-5 - total, 0.0)
    w = w + pad / w.shape[-1]
    total = total + pad
    cdf = jnp.minimum(1.0, jnp.cumsum(w / total, axis=-1))
    cdf = jnp.concatenate([jnp.zeros_like(cdf[:, :1]), cdf], axis=-1)
    u = jnp.broadcast_to(
        jnp.asarray(((np.arange(n + 1) + 0.5) / (n + 1)).astype(np.float32)),
        (bins.shape[0], n + 1))
    i = _searchsorted(cdf, u)
    lo = jnp.clip(i - 1, 0, bins.shape[-1] - 1)
    hi = jnp.clip(i, 0, bins.shape[-1] - 1)
    c0, c1 = (jnp.take_along_axis(cdf, j, -1) for j in (lo, hi))
    b0, b1 = (jnp.take_along_axis(bins, j, -1) for j in (lo, hi))
    t = jnp.clip(jnp.nan_to_num((u - c0) / (c1 - c0), nan=0.0), 0.0, 1.0)
    return b0 + t * (b1 - b0)


def _lin(q, i, name, x, taps):
    if taps is not None:
        taps[name] = x
    if "act" not in q:
        return jnp.matmul(x, q["w"][name], precision=HIGHEST) + q["b"][name]
    s, z, levels = q["act"][i]
    acc = jnp.matmul(ngp.act_codes(x, s, z, levels), q["wq"][name],
                     precision=HIGHEST)  # integers, exact
    return acc * s * q["ws"][name] + q["b"][name]


def render(q, ro, rd, statics, taps=None):
    """Colors (R, 3) of rays (R, 3): `q` holds each field's "tables" (a
    list per field key: "hash", "prop1", ...), "b", the served
    "appearance", and float "w" or quantized "wq", "ws" and "act"."""
    precision, m_field, m_props, near, far, n0, ns, padding, skip = statics
    m_field, m_props = dict(m_field), [dict(p) for p in m_props]
    names = linears({"proposals": m_props})
    R = ro.shape[0]
    bins = jnp.broadcast_to(
        jnp.asarray(np.linspace(0.0, 1.0, n0 + 1).astype(np.float32)),
        (R, n0 + 1))
    passes = [k for k in range(len(m_props)) if k != skip]
    counts = [ns[k] for k in passes[:-1]] + [ns[-1]]

    def samples(b):
        e = euclidean(b, near, far)
        mid = (e[:, 1:] + e[:, :-1]) / 2.0
        pts = ro[:, None, :] + rd[:, None, :] * mid[..., None]
        return pts, e[:, 1:] - e[:, :-1]

    for k, n in zip(passes, counts):
        pts, delta = samples(bins)
        x, sel = contract(pts.reshape(-1, 3))
        key = f"prop{k + 1}"
        enc = ngp.encode(q["tables"][key], x, m_props[k], precision)
        a, b = f"{key}/0", f"{key}/1"
        h = jax.nn.relu(_lin(q, names.index(a), a, enc, taps))
        h = _lin(q, names.index(b), b, h, taps)
        sigma = jnp.where(sel, jnp.exp(h[:, 0]), 0.0).reshape(delta.shape)
        bins = resample(bins, weights(sigma, delta), n, padding)

    pts, delta = samples(bins)
    S = delta.shape[1]
    x, sel = contract(pts.reshape(-1, 3))
    dirs = jnp.broadcast_to(rd[:, None, :], pts.shape).reshape(-1, 3)
    enc = ngp.encode(q["tables"]["hash"], x, m_field, precision)
    h = jax.nn.relu(_lin(q, 0, "sigma/0", enc, taps))
    h = _lin(q, 1, "sigma/1", h, taps)
    app = jnp.broadcast_to(q["appearance"], (x.shape[0],) + q["appearance"].shape)
    c = jnp.concatenate([ngp.sh16(dirs), h[:, 1:], app], axis=-1)
    c = jax.nn.relu(_lin(q, 2, "color/0", c, taps))
    c = jax.nn.relu(_lin(q, 3, "color/1", c, taps))
    rgb = jax.nn.sigmoid(_lin(q, 4, "color/2", c, taps)).reshape(R, S, 3)
    sigma = jnp.where(sel, jnp.exp(h[:, 0]), 0.0).reshape(R, S)
    w = weights(sigma, delta)
    return (jnp.sum(w[..., None] * rgb, axis=1)
            + (1.0 - jnp.sum(w, axis=-1, keepdims=True)))


@functools.partial(jax.jit, static_argnames=("statics",))
def render_jit(q, ro, rd, *, statics):
    return render(q, ro, rd, statics)


@functools.partial(jax.jit, static_argnames=("statics",))
def _ranges(q, ro, rd, *, statics):
    """Each linear's input (min, max) over the float render of the rays."""
    taps: Dict = {}
    render(q, ro, rd, statics, taps)
    return {n: jnp.stack([jnp.min(v), jnp.max(v)]) for n, v in taps.items()}


def _statics(cfg: Dict, precision: str, skip: Optional[int]):
    m = cfg["model"]
    return (precision, tuple(sorted(m["field"].items())),
            tuple(tuple(sorted(p.items())) for p in m["proposals"]),
            float(m["near"]), float(m["far"]), int(m["n_initial"]),
            tuple(m["n_resampled"]), float(m["histogram_padding"]), skip)


def float_field(params: Dict, cfg: Dict) -> Dict:
    m = cfg["model"]
    keys = {"hash": ("hash", m["field"]["n_levels"])}
    for k, p in enumerate(m["proposals"]):
        keys[f"prop{k + 1}"] = (f"prop{k + 1}/hash", p["n_levels"])
    names = linears(m)
    return {
        "tables": {key: [params[top][f"level_{l}"] for l in range(n)]
                   for key, (top, n) in keys.items()},
        "w": {n: params[n]["w"] for n in names},
        "b": {n: params[n]["b"] for n in names},
        "appearance": jnp.mean(params["appearance"]["embedding"], axis=0),
    }


def render_float(params: Dict, cfg: Dict, ro, rd) -> np.ndarray:
    """The float field's colors (no quantization)."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(render_jit(float_field(params, cfg), jnp.asarray(ro),
                                     jnp.asarray(rd),
                                     statics=_statics(cfg, "highest", None)))


class Reference:
    """The configuration's served field, derived from float weights;
    calibrated on the rays (`calib_o`, `calib_d`)."""

    def __init__(self, params: Dict, cfg: Dict, calib_o, calib_d,
                 precision: str = "highest", skip_pass: Optional[int] = None):
        m, pol = cfg["model"], cfg["policy"]
        if m["field"]["sh_degree"] != 3 or pol["paper_exact"]:
            raise ValueError("the reference serves 16 SH coefficients (sh_degree 3) "
                             "on the conventional weight grid (paper_exact false)")
        fq = float_field(params, cfg)
        names = linears(m)
        with jax.default_matmul_precision("highest"):
            taps = _ranges(fq, jnp.asarray(calib_o), jnp.asarray(calib_d),
                           statics=_statics(cfg, "highest", None))
        self.ranges = [ngp.round_range(float(taps[n][0]), float(taps[n][1]),
                                       cfg["act_range_sig_bits"]) for n in names]
        tables = {key: [ngp.quant_weight(t, b) for t, b in
                        zip(ts, pol["hash_bits"][key])]
                  for key, ts in fq["tables"].items()}
        wq = {n: ngp.quant_weight(fq["w"][n], pol["linears"][n]["weight"],
                                  codes=True) for n in names}
        self.arrays = {
            "tables": tables, "b": fq["b"], "appearance": fq["appearance"],
            "wq": {n: c for n, (c, _) in wq.items()},
            "ws": {n: s for n, (_, s) in wq.items()},
            "act": [ngp.act_grid(lo, hi, pol["linears"][n]["act"])
                    for (lo, hi), n in zip(self.ranges, names)],
        }
        self.statics = _statics(cfg, precision, skip_pass)

    def render(self, ro: np.ndarray, rd: np.ndarray, block: int = 4096) -> np.ndarray:
        """(N, 3) colors of rays (N, 3), in blocks of `block` rays."""
        out = []
        with jax.default_matmul_precision("highest"):
            for s in range(0, ro.shape[0], block):
                a, b = ro[s:s + block], rd[s:s + block]
                n = a.shape[0]
                if n < block:
                    a = np.concatenate([a, np.full((block - n, 3), 10.0, np.float32)])
                    b = np.concatenate([b, np.zeros((block - n, 3), np.float32)])
                out.append(np.asarray(render_jit(
                    self.arrays, jnp.asarray(a), jnp.asarray(b),
                    statics=self.statics))[:n])
        return np.concatenate(out)
