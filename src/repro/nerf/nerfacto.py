"""Nerfacto: proposal-network sampling over hash-grid fields.

Nerfacto is nerfstudio's default method (Tancik et al. 2023, arXiv
2302.04264; `NerfactoModelConfig` in `nerfstudio/models/nerfacto.py`).
Along each ray:

1. 256 intervals uniform in the spacing s = g(t) between g(near) and
   g(far), with g(t) = t/2 for t < 1 and 1 - 1/(2t) otherwise
   (`UniformLinDispPiecewiseSampler`; eval, no jitter). A sample sits at
   its interval's Euclidean midpoint; delta = end - start.
2. Proposal density field 1 (a 5-level hash grid and a 10 -> 16 -> 1
   MLP) at those samples; interval weights
   w_i = (1 - exp(-sigma_i delta_i)) exp(-sum_{j<i} sigma_j delta_j).
3. Inverse-CDF resampling to 96 intervals (`PDFSampler`, eval), then
   proposal field 2 at them, then resampling to 48 intervals.
4. The main field (Instant-NGP's widths, plus a 32-wide appearance
   embedding fed to the color MLP) at the 48 samples, composited on a
   white background.

Positions reach every field through the L-inf scene contraction
c(x) = x where |x|_inf <= 1, else (2 - 1/|x|_inf) x/|x|_inf, as
(c(x) + 2)/4; a point outside (0, 1)^3 has zero density.

This module holds the config, the initialization, the float forward
pass of the three fields (training and calibration), contraction and
both samplers (shared with the fused serve programs in
`nerf/fast_render.py`), the quantization-unit walk and spec, and a
trainer with nerfstudio's losses (RGB MSE + 1.0 x interlevel + 0.002 x
distortion, from mip-NeRF 360).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.nerf.hash_encoding import (
    HashEncodingConfig,
    hash_encode,
    init_hash_tables,
)
from repro.nerf.ngp import NGPConfig, NGPQuantSpec, sh_encode
from repro.optim import AdamWConfig, adamw_init, adamw_update, clip_by_global_norm
from repro.quant.policy import QuantPolicy, QuantUnit, UnitKind


@dataclasses.dataclass(frozen=True)
class NerfactoConfig:
    field: NGPConfig = NGPConfig()  # main field; its color MLP also takes
    #                                 the appearance embedding
    proposals: Tuple[HashEncodingConfig, ...] = (
        HashEncodingConfig(n_levels=5, log2_table_size=17,
                           base_resolution=16, max_resolution=128),
        HashEncodingConfig(n_levels=5, log2_table_size=17,
                           base_resolution=16, max_resolution=256),
    )
    proposal_hidden: int = 16
    appearance_dim: int = 32
    n_images: int = 1  # rows of the appearance embedding (training images)
    n_initial: int = 256  # uniform intervals proposal 1 is queried at
    n_resampled: Tuple[int, ...] = (96, 48)  # after each proposal pass
    near: float = 0.05
    far: float = 1000.0
    histogram_padding: float = 0.01

    @property
    def proposal_samples_per_ray(self) -> int:
        """Samples at which the proposal fields are queried (256 + 96)."""
        return self.n_initial + sum(self.n_resampled[:-1])

    @property
    def shade_samples_per_ray(self) -> int:
        return self.n_resampled[-1]


def proposal_names(k: int) -> Tuple[str, str]:
    return (f"prop{k + 1}/0", f"prop{k + 1}/1")


def linear_names(cfg: NerfactoConfig) -> List[str]:
    """The quantization walk's linear order: main field, then proposals."""
    names = ["sigma/0", "sigma/1", "color/0", "color/1", "color/2"]
    for k in range(len(cfg.proposals)):
        names += list(proposal_names(k))
    return names


def linear_dims(cfg: NerfactoConfig) -> Dict[str, Tuple[int, int]]:
    f = cfg.field
    dims = {
        "sigma/0": (f.hash.out_dim, f.hidden_dim),
        "sigma/1": (f.hidden_dim, 1 + f.geo_feat_dim),
        "color/0": (f.sh_dim + f.geo_feat_dim + cfg.appearance_dim,
                    f.color_hidden_dim),
        "color/1": (f.color_hidden_dim, f.color_hidden_dim),
        "color/2": (f.color_hidden_dim, 3),
    }
    for k, h in enumerate(cfg.proposals):
        a, b = proposal_names(k)
        dims[a] = (h.out_dim, cfg.proposal_hidden)
        dims[b] = (cfg.proposal_hidden, 1)
    return dims


def hash_key(k: Optional[int]) -> str:
    """Params key of the main field's tables (None) or proposal k's."""
    return "hash" if k is None else f"prop{k + 1}/hash"


def init_nerfacto(key: jax.Array, cfg: NerfactoConfig) -> Dict:
    """Hash tables uniform in [-1e-4, 1e-4], linears He-normal with zero
    bias, appearance embeddings N(0, 1) (torch's `nn.Embedding`)."""
    params: Dict = {}
    for k, h in [(None, cfg.field.hash)] + list(enumerate(cfg.proposals)):
        key, sub = jax.random.split(key)
        params[hash_key(k)] = init_hash_tables(sub, h)
    for name, (d_in, d_out) in linear_dims(cfg).items():
        key, sub = jax.random.split(key)
        params[name] = {
            "w": jax.random.normal(sub, (d_in, d_out), jnp.float32)
            * float(np.sqrt(2.0 / d_in)),
            "b": jnp.zeros((d_out,), jnp.float32),
        }
    key, sub = jax.random.split(key)
    params["appearance"] = {"embedding": jax.random.normal(
        sub, (cfg.n_images, cfg.appearance_dim), jnp.float32)}
    return params


def serve_appearance(params: Dict) -> jnp.ndarray:
    """The appearance input at serve time: the mean of the trained
    per-image embeddings (`use_average_appearance_embedding`)."""
    return jnp.mean(params["appearance"]["embedding"], axis=0)


# ---------------------------------------------------------------------------
# Contraction and samplers (shared by the float forward and the serve path)
# ---------------------------------------------------------------------------
def field_coords(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """World points (..., 3) -> ((c(x) + 2)/4, selector): the L-inf
    contraction (module docstring) into the fields' unit cube, zeroed
    where the point falls outside (0, 1)^3, and that selector. With the
    norm floored at 1 the one expression is the identity inside the
    unit ball, exactly."""
    n = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1.0)
    u = ((2.0 - 1.0 / n) * (x / n) + 2.0) / 4.0
    sel = jnp.all((u > 0.0) & (u < 1.0), axis=-1)
    return jnp.where(sel[..., None], u, 0.0), sel


def initial_bins(cfg: NerfactoConfig) -> np.ndarray:
    """The n_initial + 1 normalized spacing edges, linspace(0, 1)."""
    return np.linspace(0.0, 1.0, cfg.n_initial + 1).astype(np.float32)


def spacing_to_euclidean(s: jnp.ndarray, cfg: NerfactoConfig) -> jnp.ndarray:
    """Normalized spacing edges in [0, 1] -> ray distances:
    g^-1(s g(far) + (1 - s) g(near)), g^-1(y) = 2y for y < 1/2 and
    1/(2 - 2y) otherwise."""
    def g(t):
        return t / 2.0 if t < 1.0 else 1.0 - 1.0 / (2.0 * t)

    y = s * np.float32(g(cfg.far)) + (1.0 - s) * np.float32(g(cfg.near))
    return jnp.where(y < 0.5, 2.0 * y, 1.0 / (2.0 - 2.0 * y))


def sample_points(rays_o, rays_d, edges):
    """Interval midpoints (R, n, 3) and lengths (R, n) of Euclidean edges
    (R, n + 1)."""
    mid = (edges[:, :-1] + edges[:, 1:]) / 2.0
    pts = rays_o[:, None, :] + rays_d[:, None, :] * mid[..., None]
    return pts, edges[:, 1:] - edges[:, :-1]


def ray_weights(sigma: jnp.ndarray, delta: jnp.ndarray) -> jnp.ndarray:
    """w_i = (1 - exp(-sigma_i delta_i)) exp(-sum_{j<i} sigma_j delta_j)."""
    tau = sigma * delta
    excl = jnp.concatenate(
        [jnp.zeros_like(tau[:, :1]), jnp.cumsum(tau[:, :-1], axis=-1)], axis=-1)
    return (1.0 - jnp.exp(-tau)) * jnp.exp(-excl)


def pdf_resample(bins: jnp.ndarray, weights: jnp.ndarray, n: int,
                 padding: float) -> jnp.ndarray:
    """`PDFSampler` in eval (no jitter, no original bins): n + 1 new
    spacing edges (R, n + 1) from the previous edges (R, m + 1) and
    their intervals' weights (R, m). The searchsorted (side "right") is
    a count of CDF entries at or below each u, which the CDF's order
    makes the same index."""
    eps = 1e-5
    w = weights + padding
    wsum = jnp.sum(w, axis=-1, keepdims=True)
    pad = jax.nn.relu(eps - wsum)
    w = w + pad / w.shape[-1]
    wsum = wsum + pad
    cdf = jnp.minimum(1.0, jnp.cumsum(w / wsum, axis=-1))
    cdf = jnp.concatenate([jnp.zeros_like(cdf[:, :1]), cdf], axis=-1)
    u = jnp.asarray(((np.arange(n + 1) + 0.5) / (n + 1)).astype(np.float32))
    inds = jnp.sum(cdf[:, None, :] <= u[None, :, None], axis=-1)
    last = bins.shape[-1] - 1
    below = jnp.clip(inds - 1, 0, last)
    above = jnp.clip(inds, 0, last)
    cdf0 = jnp.take_along_axis(cdf, below, axis=-1)
    cdf1 = jnp.take_along_axis(cdf, above, axis=-1)
    b0 = jnp.take_along_axis(bins, below, axis=-1)
    b1 = jnp.take_along_axis(bins, above, axis=-1)
    t = jnp.clip(jnp.nan_to_num((u - cdf0) / (cdf1 - cdf0), nan=0.0), 0.0, 1.0)
    return jax.lax.stop_gradient(b0 + t * (b1 - b0))


def propose(density_fns, rays_o, rays_d, cfg: NerfactoConfig):
    """The proposal passes: `density_fns[k]` maps field coordinates (P, 3)
    to proposal k's densities (P,). Returns (spacing edges of every pass,
    weights of every proposal pass), the last edges being the (R, n + 1)
    intervals the main field shades."""
    R = rays_o.shape[0]
    bins = jnp.broadcast_to(jnp.asarray(initial_bins(cfg)),
                            (R, cfg.n_initial + 1))
    bins_list, weights_list = [bins], []
    for fn, n in zip(density_fns, cfg.n_resampled):
        pts, delta = sample_points(rays_o, rays_d,
                                   spacing_to_euclidean(bins, cfg))
        x01, sel = field_coords(pts.reshape(-1, 3))
        sigma = jnp.where(sel, fn(x01), 0.0).reshape(delta.shape)
        w = ray_weights(sigma, delta)
        bins = pdf_resample(bins, w, n, cfg.histogram_padding)
        bins_list.append(bins)
        weights_list.append(w)
    return bins_list, weights_list


# ---------------------------------------------------------------------------
# Float forward (training, calibration)
# ---------------------------------------------------------------------------
@jax.custom_vjp
def trunc_exp(x):
    """exp(x); its gradient takes x clamped at 15 (nerfstudio's
    `trunc_exp`)."""
    return jnp.exp(x)


def _trunc_exp_fwd(x):
    return jnp.exp(x), x


def _trunc_exp_bwd(x, g):
    return (g * jnp.exp(jnp.minimum(x, 15.0)),)


trunc_exp.defvjp(_trunc_exp_fwd, _trunc_exp_bwd)


def _linear(params, name, x, taps):
    if taps is not None:
        taps[name] = x  # pre-quantization input (calibration point)
    return x @ params[name]["w"] + params[name]["b"]


def proposal_density(params, k: int, x01, cfg: NerfactoConfig, taps=None):
    """Proposal field k's density at field coordinates (P, 3)."""
    a, b = proposal_names(k)
    enc = hash_encode(params[hash_key(k)], x01, cfg.proposals[k])
    h = _linear(params, b, jax.nn.relu(_linear(params, a, enc, taps)), taps)
    return trunc_exp(h[:, 0])


def main_field(params, x01, dirs, appearance, cfg: NerfactoConfig,
               taps=None):
    """The main field's (density (P,), rgb (P, 3)) at field coordinates;
    the color MLP takes [SH(dir), geometry features, appearance]."""
    f = cfg.field
    enc = hash_encode(params["hash"], x01, f.hash)
    h = jax.nn.relu(_linear(params, "sigma/0", enc, taps))
    h = _linear(params, "sigma/1", h, taps)
    app = jnp.broadcast_to(appearance, (x01.shape[0], cfg.appearance_dim))
    c = jnp.concatenate([sh_encode(dirs, f.sh_degree), h[:, 1:], app], -1)
    c = jax.nn.relu(_linear(params, "color/0", c, taps))
    c = jax.nn.relu(_linear(params, "color/1", c, taps))
    rgb = jax.nn.sigmoid(_linear(params, "color/2", c, taps))
    return trunc_exp(h[:, 0]), rgb


def render_rays(params, rays_o, rays_d, cfg: NerfactoConfig, appearance,
                taps=None):
    """Float render of (R, 3) rays. `appearance` is (A,) or per ray
    (R, A). Returns (color (R, 3), spacing edges of every pass, weights
    of every pass, the main field's last). With `taps` (a dict) each
    linear's input lands in `taps[name]`."""
    fns = [functools.partial(proposal_density, params, k, cfg=cfg, taps=taps)
           for k in range(len(cfg.proposals))]
    bins_list, weights_list = propose(fns, rays_o, rays_d, cfg)
    pts, delta = sample_points(rays_o, rays_d,
                               spacing_to_euclidean(bins_list[-1], cfg))
    R, S = delta.shape
    x01, sel = field_coords(pts.reshape(-1, 3))
    dirs = jnp.broadcast_to(rays_d[:, None, :], pts.shape).reshape(-1, 3)
    app = appearance if appearance.ndim == 1 else jnp.repeat(
        appearance, S, axis=0)
    sigma, rgb = main_field(params, x01, dirs, app, cfg, taps)
    sigma = jnp.where(sel, sigma, 0.0).reshape(R, S)
    w = ray_weights(sigma, delta)
    color = jnp.sum(w[..., None] * rgb.reshape(R, S, 3), axis=1)
    color = color + (1.0 - jnp.sum(w, axis=-1, keepdims=True))
    return color, bins_list, weights_list + [w]


# ---------------------------------------------------------------------------
# Losses (mip-NeRF 360, as nerfstudio computes them)
# ---------------------------------------------------------------------------
_searchsorted = jax.vmap(functools.partial(jnp.searchsorted, side="right"))


def _outer(t0, t1, y1):
    """Weight of the intervals `t1` (edges, y1 their weights) that
    overlap each interval of `t0` (edges)."""
    cy1 = jnp.concatenate([jnp.zeros_like(y1[:, :1]), jnp.cumsum(y1, -1)], -1)
    last = y1.shape[-1] - 1
    lo = jnp.clip(_searchsorted(t1[:, :-1], t0[:, :-1]) - 1, 0, last)
    hi = jnp.clip(_searchsorted(t1[:, 1:], t0[:, 1:]), 0, last)
    return (jnp.take_along_axis(cy1[:, 1:], hi, -1)
            - jnp.take_along_axis(cy1[:, :-1], lo, -1))


def interlevel_loss(bins_list, weights_list) -> jnp.ndarray:
    """Proposal weights must bound the main field's (which are held)."""
    c = jax.lax.stop_gradient(bins_list[-1])
    w = jax.lax.stop_gradient(weights_list[-1])
    eps = float(np.finfo(np.float32).eps) + 1e-7
    loss = 0.0
    for cp, wp in zip(bins_list[:-1], weights_list[:-1]):
        outer = _outer(c, cp, wp)
        loss = loss + jnp.mean(jnp.clip(w - outer, 0.0) ** 2 / (w + eps))
    return loss


def distortion_loss(bins, w) -> jnp.ndarray:
    mid = (bins[:, 1:] + bins[:, :-1]) / 2.0
    d = jnp.abs(mid[:, :, None] - mid[:, None, :])
    inter = jnp.sum(w * jnp.sum(w[:, None, :] * d, axis=-1), axis=-1)
    intra = jnp.sum(w ** 2 * (bins[:, 1:] - bins[:, :-1]), axis=-1) / 3.0
    return jnp.mean(inter + intra)


# ---------------------------------------------------------------------------
# Quantization units and spec
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class NerfactoQuantSpec:
    """One `NGPQuantSpec` per field: the main field's (its 5 linears) and
    each proposal field's (its 2 linears)."""

    main: NGPQuantSpec
    proposals: Tuple[NGPQuantSpec, ...]


def _fields(cfg: NerfactoConfig):
    """(hash-unit prefix, hash config, linear names) of each field."""
    out = [("hash", cfg.field.hash, linear_names(cfg)[:5])]
    for k, h in enumerate(cfg.proposals):
        out.append((f"prop{k + 1}/hash", h, list(proposal_names(k))))
    return out


def make_quant_units(cfg: NerfactoConfig) -> List[QuantUnit]:
    """Walk order: main hash levels, proposal 1's, proposal 2's, then each
    linear's activation and weight in `linear_names` order."""
    units: List[QuantUnit] = []
    for prefix, h, _ in _fields(cfg):
        for l in range(h.n_levels):
            units.append(QuantUnit(
                name=f"{prefix}/level_{l}", kind=UnitKind.HASH_LEVEL,
                layer_type=1, d_in=h.n_features, d_out=h.level_entries(l),
                param_size=l, index=len(units)))
    dims = linear_dims(cfg)
    for name in linear_names(cfg):
        d_in, d_out = dims[name]
        for suffix, kind in ((":a", UnitKind.ACTIVATION),
                             (":w", UnitKind.WEIGHT)):
            units.append(QuantUnit(
                name=name + suffix, kind=kind, layer_type=0, d_in=d_in,
                d_out=d_out, param_size=d_in * d_out, index=len(units)))
    return units


def spec_from_policy(cfg: NerfactoConfig, policy: QuantPolicy,
                     act_ranges: jnp.ndarray) -> NerfactoQuantSpec:
    """Per-field traced specs from a host-side policy; `act_ranges`
    (n_linear, 2) in `linear_names` order."""
    bits = policy.bits_by_name()
    order = linear_names(cfg)
    specs = []
    for prefix, h, names in _fields(cfg):
        rows = [order.index(n) for n in names]
        specs.append(NGPQuantSpec(
            hash_bits=jnp.asarray([float(bits[f"{prefix}/level_{l}"])
                                   for l in range(h.n_levels)]),
            weight_bits=jnp.asarray([float(bits[n + ":w"]) for n in names]),
            act_bits=jnp.asarray([float(bits[n + ":a"]) for n in names]),
            act_ranges=jnp.asarray(act_ranges)[jnp.asarray(rows)],
        ))
    return NerfactoQuantSpec(main=specs[0], proposals=tuple(specs[1:]))


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------
# nerfstudio's `interlevel_loss_mult` and `distortion_loss_mult`.
INTERLEVEL_MULT = 1.0
DISTORTION_MULT = 0.002


@dataclasses.dataclass(frozen=True)
class NerfactoTrainConfig:
    steps: int = 300
    batch_rays: int = 1024
    lr: float = 1e-2  # nerfstudio: 1e-2 for the fields and the proposals
    seed: int = 0


def _loss(params, ro, rd, target, image, cfg):
    app = params["appearance"]["embedding"][image]
    color, bins_list, weights_list = render_rays(params, ro, rd, cfg, app)
    return (jnp.mean((color - target) ** 2)
            + INTERLEVEL_MULT * interlevel_loss(bins_list, weights_list)
            + DISTORTION_MULT * distortion_loss(bins_list[-1],
                                                weights_list[-1]))


@functools.partial(jax.jit, static_argnames=("cfg", "lr"))
def _train_step(params, opt_state, ro, rd, target, image, *, cfg, lr):
    loss, grads = jax.value_and_grad(_loss)(params, ro, rd, target, image, cfg)
    grads, _ = clip_by_global_norm(grads, 10.0)
    params, opt_state = adamw_update(
        grads, opt_state, params, AdamWConfig(lr=lr, eps=1e-15))
    return params, opt_state, loss


def train_nerfacto(dataset, cfg: NerfactoConfig,
                   tcfg: NerfactoTrainConfig) -> Tuple[Dict, float]:
    """Fit a fresh Nerfacto to `dataset`'s training rays (Adam, the three
    losses). Each ray's appearance embedding is its training image's.
    Returns (params, final loss)."""
    params = init_nerfacto(jax.random.PRNGKey(tcfg.seed), cfg)
    opt_state = adamw_init(params)
    rng = np.random.RandomState(tcfg.seed)
    n = dataset.train_rays_o.shape[0]
    per_image = n // cfg.n_images
    loss = None
    for _ in range(tcfg.steps):
        idx = rng.randint(0, n, size=tcfg.batch_rays)
        params, opt_state, loss = _train_step(
            params, opt_state, jnp.asarray(dataset.train_rays_o[idx]),
            jnp.asarray(dataset.train_rays_d[idx]),
            jnp.asarray(dataset.train_rgb[idx]),
            jnp.asarray(idx // per_image), cfg=cfg, lr=tcfg.lr)
    return params, float(loss) if loss is not None else float("nan")
