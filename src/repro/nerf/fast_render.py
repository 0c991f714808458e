"""Fused quantized render engine: occupancy-culled, kernel-backed inference.

The training path (`render_rays`) stays the differentiable fake-quant
oracle. This module is the INFERENCE path the HERO reward loop actually
spends its time in — full-frame PSNR after each episode finetune, and the
batched env's PSNR proxy — rebuilt around three ideas:

1. **Empty-space culling** (`nerf/occupancy.py`): sample points falling
   outside the scene box or in unoccupied grid cells are compacted away
   BEFORE the field query. For fixed rays (held-out eval views, the proxy
   ray subset) the compaction is precomputed once on the host as a
   `CullPlan` — pure gather indices, no cumsum/scatter in the hot path,
   and an EXACT per-chunk budget (the active mask depends only on
   geometry and the frozen grid, never on params or the policy). Ad-hoc
   rays take the active mask from the occupancy ray-march kernel and
   compact on device with a `nonzero` gather.
2. **Real integer inference** (`mode="fused"`): a `FusedPack` precomputes
   sub-byte PACKED weight codes per linear layer and packed integer
   hash-table codes (`repro.quant.packing.PackedTensor` — b-bit payloads
   bit-packed into int32 words, so a 4-bit policy stores 4-bit weights,
   not an int8 or float inflation); activations are quantized to integer
   codes on the fly and the five NGP linears lower through
   `kernels.ops.quant_matmul_packed` (packed words expanded to int8 codes
   inside the kernel + int32 MXU accumulation), the hash lookups through
   XLA's gather (`kernels.ops.hash_encode`) over the dequantized codes.
   On backends without an int8 matmul unit (CPU), the same codes run on
   a float carrier — identical quantization grid, f32 accumulation — because
   XLA's int32 dot is ~2.5x slower than f32 there; `use_pallas=True`
   forces the integer kernels everywhere (the parity tests do).
   `mode="reference"` keeps fake-quant `ngp_apply` as the oracle inside
   the same culled pipeline.

   **The one-LSB clamp edge.** The paper-exact symmetric grid (Eq. 5,
   q_min = -2^(b-1) - 1) spans 2^b + 1 levels — one more than a b-bit
   payload can hold. `pack_codes` stores the top-exact window
   [max(q) - 2^b + 1, max(q)]: a weight or hash tensor whose codes use
   the FULL span clamps its single lowest level up by one LSB; all other
   tensors (including any near-symmetric distribution) round-trip
   exactly. This generalizes the old int8 path's b = 8 note (codes at
   -129 clamping to -128): the deployable payload IS the truth, so the
   serve path and the in-process fused path agree bit-for-bit at every
   width, and the fake-quant oracle differs only on full-span tensors.
3. **Device-resident frames**: full-frame evaluation stages the test set
   on device once, then runs ONE jitted call per evaluation — `lax.map`
   over ray chunks with squared error reduced on device — so a single
   scalar crosses to the host where the old loop synced a color buffer
   per 4096-ray chunk.

Compositing goes through `kernels.ops.alpha_composite` (with
transmittance-based early chunk termination on the Pallas path).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.backend import on_tpu
from repro.kernels.ops import (
    alpha_composite as ops_alpha_composite,
    fused_field_query as ops_fused_field_query,
    hash_encode as ops_hash_encode,
    quant_matmul_packed as ops_quant_matmul_packed,
    ray_march as ops_ray_march,
)
from repro.kernels.repack import DEFAULT_TILE_BK, repack_tile_native
from repro.nerf import nerfacto
from repro.nerf.hash_encoding import (
    HashEncodingConfig,
    cell_corner_rows,
    level_corner_data,
)
from repro.nerf.ngp import (
    NGPConfig,
    NGPQuantSpec,
    ngp_apply,
    ngp_linear_names,
    no_quant_spec,
    sh_encode,
)
from repro.nerf.occupancy import (
    OccupancyGrid,
    cull_budget,
    occupancy_lookup,
    ray_t_samples,
    sample_active_mask,
)
from repro.quant.linear_quant import (
    activation_qparams,
    fake_quant_weight,
    quantize_weight,
    weight_qparams,
)
from repro.quant.packing import PackedTensor, pack_codes

# ---------------------------------------------------------------------------
# FusedPack: host-built integer inference parameters for ONE concrete policy.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class FusedPack:
    """Per-layer packed integer codes + scales, and packed hash tables.

    `modes[i]` (static) selects the lowering of linear layer i:
      "int"        — packed weight codes + on-the-fly activation codes
                     through `quant_matmul_packed` (float carrier off-TPU,
                     same grid — module docstring);
      "float_qact" — f32 matmul, activations fake-quantized on the fly
                     (activation bits in the 9..15 band);
      "float"      — f32 matmul, activations untouched (>= 16 sentinel).

    Weight STORAGE is orthogonal to the mode and depends only on the
    weight bits: `wq` (a sub-byte `PackedTensor`) for bits <= 8, a
    fake-quantized f32 `w` for the 9..15 band, the raw f32 `w` at the
    >= 16 sentinel. Hash tables likewise: `PackedTensor` integer codes +
    scale for bits <= 8 (the bits actually shrink the pack), f32 carriers
    above. `fused_pack_stored_bytes` measures exactly these payloads.

    `layers` / `hash_tables` are always the STORAGE truth (planar packed
    words) — what the artifact serializes and `model_bytes` measures.
    `compute` holds the derived kernel-native forms staged once by
    `repack_fused_pack` (`layout` records which repack): tile-native
    packed words per layer, the concatenated dequantized hash table and
    the direct-indexed levels' cell-major table for the fused encode,
    float weight carriers. The field query reads only those staged
    forms: a `"planar"` pack (empty `compute`) is a storage form, which
    is serialized and measured but never served — `repack_fused_pack`
    stages it first. `hash_cfg` is the tables' encoding (which levels
    are direct-indexed, at what resolution); a pack without it stages no
    cell-major table.
    """

    layers: Dict[str, Dict[str, jnp.ndarray]]
    hash_tables: Dict[str, jnp.ndarray]
    modes: Tuple[str, ...]
    layout: str = "planar"
    compute: Dict[str, jnp.ndarray] = dataclasses.field(default_factory=dict)
    hash_cfg: Optional[HashEncodingConfig] = None

    def fields(self):
        """(name, pack) of each field: this one, named ""."""
        return [("", self)]


jax.tree_util.register_dataclass(
    FusedPack,
    data_fields=["layers", "hash_tables", "compute"],
    meta_fields=["modes", "layout", "hash_cfg"],
)


def _pack_weight(w, bits: float, paper_exact: bool) -> PackedTensor:
    """Quantize one weight/table tensor and bit-pack its codes (b <= 8).

    The code offset is the top-exact window: only a tensor using the full
    2^b + 1 paper-exact span clamps, by one LSB at q_min (module
    docstring, "the one-LSB clamp edge")."""
    qp = weight_qparams(jnp.min(w), jnp.max(w), bits, paper_exact=paper_exact)
    return pack_codes(quantize_weight(w, qp), int(round(bits)), scale=qp.scale)


def build_fused_pack(
    params: Dict,
    cfg: NGPConfig,
    spec: Optional[NGPQuantSpec] = None,
    layout: str = f"tile:{DEFAULT_TILE_BK}",
) -> FusedPack:
    """Lower a (params, spec) pair to packed integer inference form.

    `layout` selects the staged compute representation
    (`repack_fused_pack`): the default tile-native repack + fused-encode
    staging, or `"planar"` for the bare storage-only pack, which is not
    served until `repack_fused_pack` stages it.

    Requires a CONCRETE spec (host floats, not tracers): the bit widths
    pick the lowering per layer at build time, and the packing windows
    need host min/max. Codes fed to the MXU clip to [-128, 127]; packed
    storage additionally clamps full-span tensors by one LSB at q_min
    (the paper-exact grid's extra -2^(b-1)-1 level — module docstring).
    The float carrier dequantizes the SAME stored codes, so off-TPU and
    kernel paths — and anything loaded from a saved artifact — share one
    set of weights bit-for-bit.
    """
    if spec is None:
        spec = no_quant_spec(cfg)
    return pack_field(params, ngp_linear_names(cfg), params["hash"],
                      cfg.hash, spec, layout)


def pack_field(
    params: Dict,
    names,
    hash_tables: Dict[str, jnp.ndarray],
    hcfg: HashEncodingConfig,
    spec: NGPQuantSpec,
    layout: str = f"tile:{DEFAULT_TILE_BK}",
) -> FusedPack:
    """`build_fused_pack` for any field: the linears `names` (in the
    spec's order) and the `level_<l>` tables of `hash_tables`, encoded
    as `hcfg` says."""
    wb = np.asarray(spec.weight_bits, np.float32)
    ab = np.asarray(spec.act_bits, np.float32)
    ar = np.asarray(spec.act_ranges, np.float32)
    hb = np.asarray(spec.hash_bits, np.float32)
    pe = spec.paper_exact

    layers: Dict[str, Dict[str, jnp.ndarray]] = {}
    modes = []
    for i, name in enumerate(names):
        w, b = params[name]["w"], params[name]["b"]
        wbi, abi = float(wb[i]), float(ab[i])
        lo, hi = float(ar[i, 0]), float(ar[i, 1])

        # Weight storage: packed codes / fake-quant f32 / raw f32.
        if wbi <= 8.0:
            store = dict(wq=_pack_weight(w, wbi, pe))
        elif wbi < 16.0:
            qp_w = weight_qparams(jnp.min(w), jnp.max(w), wbi, paper_exact=pe)
            store = dict(w=fake_quant_weight(w, qp_w))
        else:
            store = dict(w=w)

        if wbi <= 8.0 and abi <= 8.0:
            qp_a = activation_qparams(lo, hi, abi)
            off = 2.0 ** (abi - 1.0)  # shift codes [0, 2^b-1] into int8
            layers[name] = dict(
                store,
                b=b,
                sx=jnp.asarray(qp_a.scale, jnp.float32),
                zx=jnp.asarray(qp_a.zero_point - off, jnp.int32),
                zx_f=jnp.asarray(qp_a.zero_point, jnp.float32),
                qmax=jnp.asarray(qp_a.q_max, jnp.float32),
                off=jnp.asarray(off, jnp.float32),
            )
            modes.append("int")
        elif abi < 16.0:
            qp_a = activation_qparams(lo, hi, abi)
            layers[name] = dict(
                store, b=b,
                sx=jnp.asarray(qp_a.scale, jnp.float32),
                zx_f=jnp.asarray(qp_a.zero_point, jnp.float32),
                qmax=jnp.asarray(qp_a.q_max, jnp.float32),
            )
            modes.append("float_qact")
        else:
            layers[name] = dict(store, b=b)
            modes.append("float")

    tables: Dict[str, jnp.ndarray] = {}
    for l in range(len(hash_tables)):
        t = hash_tables[f"level_{l}"]
        bits = float(hb[l])
        if bits <= 8.0:
            # Integer codes + scale, bit-packed: hash bits shrink the pack.
            tables[f"level_{l}"] = _pack_weight(t, bits, pe)
        elif bits < 16.0:
            qp = weight_qparams(jnp.min(t), jnp.max(t), bits, paper_exact=pe)
            tables[f"level_{l}"] = fake_quant_weight(t, qp)
        else:
            tables[f"level_{l}"] = t
    pack = FusedPack(layers=layers, hash_tables=tables, modes=tuple(modes),
                     hash_cfg=hcfg)
    return repack_fused_pack(pack, layout) if layout != "planar" else pack


def repack_fused_pack(
    pack: FusedPack, layout: str = f"tile:{DEFAULT_TILE_BK}"
) -> FusedPack:
    """Stage the compute-layout forms next to the storage pack (one-time,
    at artifact compile/load or pack build — never per render call).

    compute entries:
      "table_cat"       (sum_l T_l, F) f32 — every level table
                        dequantized and stacked row-wise, so the fused
                        encode has no per-level dequantize inside the
                        jitted hot path (level row counts are static:
                        `cfg.hash.level_entries`);
      "cells_cat"       (sum_d T_d, 8F) f32 — for each direct-indexed
                        level d of `pack.hash_cfg` (`is_direct`), its
                        dequantized rows regathered cell-major: row c
                        holds the 8 corner rows of the cell whose corner 0
                        is row c (`hash_encoding.cell_corner_rows`), so the
                        encode gathers one row a point there, not eight.
                        Absent when the pack has no `hash_cfg` or the
                        field no direct level;
      "<name>::wq_tile" tile-native `PackedTensor` per packed layer (the
                        `kernels/repack.py` permutation the matmul
                        kernel unpacks with a single broadcast shift);
      "<name>::w_f32"   dequantized f32 carrier per packed layer for the
                        off-TPU float path (same codes, staged once).

    `layers`/`hash_tables` are untouched — serialization still sees only
    the storage truth, byte-identical to schema v2.
    """
    if layout == "planar":
        return dataclasses.replace(pack, layout=layout, compute={})
    bk = int(layout.split(":", 1)[1])
    compute: Dict[str, jnp.ndarray] = {}
    tabs = []
    for l in range(len(pack.hash_tables)):
        t = pack.hash_tables[f"level_{l}"]
        tabs.append(t.dequantize() if isinstance(t, PackedTensor) else t)
    compute["table_cat"] = jnp.concatenate(tabs, axis=0)
    hcfg = pack.hash_cfg
    res = [] if hcfg is None else hcfg.resolutions()
    cells = [jnp.take(tabs[l], jnp.asarray(cell_corner_rows(res[l])), axis=0)
             .reshape(tabs[l].shape[0], -1)
             for l in range(len(res)) if hcfg.is_direct(l)]
    if cells:
        compute["cells_cat"] = jnp.concatenate(cells, axis=0)
    for name, lyr in pack.layers.items():
        if "wq" in lyr:
            compute[f"{name}::wq_tile"] = repack_tile_native(lyr["wq"], bk)
            compute[f"{name}::w_f32"] = lyr["wq"].dequantize()
    return dataclasses.replace(pack, layout=layout, compute=compute)


def fused_pack_stored_bytes(pack: FusedPack) -> int:
    """Exact bytes of the pack's quantized model payload — the weight
    representation per linear layer (packed words or f32 carrier) plus
    every hash table. The SAME quantities `policy_model_bytes` predicts
    from the bit vectors: the frontier objective and the shipped artifact
    measure one number."""
    total = 0
    for lyr in pack.layers.values():
        if "wq" in lyr:
            total += lyr["wq"].nbytes_packed
        else:
            total += int(np.size(lyr["w"])) * 4
    for tab in pack.hash_tables.values():
        if isinstance(tab, PackedTensor):
            total += tab.nbytes_packed
        else:
            total += int(np.size(tab)) * 4
    return total


def hash_lookups(pack: FusedPack) -> Tuple[int, int]:
    """Corner lookups one point costs in this field's encode: (all of
    them, 8 a level; the part served by cell-major rows, 8 for each
    direct-indexed level when `cells_cat` is staged)."""
    L, hcfg = len(pack.hash_tables), pack.hash_cfg
    if "cells_cat" not in pack.compute:
        return 8 * L, 0
    return 8 * L, 8 * sum(hcfg.is_direct(l) for l in range(L))


def _use_kernels(use_pallas) -> bool:
    """Whether the integer Pallas matmul path is active (vs the float
    carrier of the same codes, the off-TPU default)."""
    return use_pallas is True or (use_pallas == "auto" and on_tpu())


def _layer_wq(pack: FusedPack, name: str) -> PackedTensor:
    """The kernel-facing packed weight: the staged tile-native repack."""
    return pack.compute[f"{name}::wq_tile"]


def _fused_weight_f32(pack: FusedPack, name: str) -> jnp.ndarray:
    """The layer's float-carrier weight: the staged dequantized carrier
    of sub-byte codes, the stored f32 carrier otherwise."""
    lyr = pack.layers[name]
    if "wq" in lyr:
        return pack.compute[f"{name}::w_f32"]
    return lyr["w"]


def _fused_linear(pack: FusedPack, i: int, name: str, x, use_pallas):
    lyr = pack.layers[name]
    mode = pack.modes[i]
    if mode == "int":
        codes = jnp.clip(jnp.round(x / lyr["sx"] + lyr["zx_f"]), 0.0, lyr["qmax"])
        if _use_kernels(use_pallas):
            ci8 = (codes - lyr["off"]).astype(jnp.int8)
            y = ops_quant_matmul_packed(
                ci8, _layer_wq(pack, name), lyr["sx"], lyr["wq"].scale,
                lyr["zx"], use_pallas=use_pallas,
            )
        else:
            # Float carrier of the SAME stored codes (module docstring):
            # (codes - Z) * s is exactly the dequantized activation, the
            # unpacked code grid exactly the kernel's weights.
            y = ((codes - lyr["zx_f"]) * lyr["sx"]) @ _fused_weight_f32(
                pack, name
            )
        return y + lyr["b"]
    if mode == "float_qact":
        codes = jnp.clip(jnp.round(x / lyr["sx"] + lyr["zx_f"]), 0.0, lyr["qmax"])
        xq = (codes - lyr["zx_f"]) * lyr["sx"]
        return xq @ _fused_weight_f32(pack, name) + lyr["b"]
    return x @ _fused_weight_f32(pack, name) + lyr["b"]


def _fused_first_linear(pack: FusedPack, points, hcfg, name: str,
                        use_pallas, corner_data=None):
    """Hash encode of `points` (P, 3) in [0, 1] over the pack's tables,
    then its first linear `name` (mode 0): the pre-activation (P, N).

    The encode is the fused `ops.hash_encode` over the tables
    `repack_fused_pack` staged — the concatenated table for hashed
    levels, the cell-major one for direct-indexed levels where it is
    staged — which keeps per-level `dequantize()` out of the jitted hot
    path, where XLA:CPU fuses it into every gather lane; on the kernel
    path the linear folds into `ops.fused_field_query`."""
    L = hcfg.n_levels
    if corner_data is None:
        per_level = [level_corner_data(points, l, hcfg) for l in range(L)]
        idx = jnp.stack([i for i, _ in per_level])  # (L, P, 8)
        w = jnp.stack([w_ for _, w_ in per_level])
    else:
        idx, w = corner_data
    cat = pack.compute["table_cat"]
    rows = tuple(hcfg.level_entries(l) for l in range(L))
    cells = pack.compute.get("cells_cat")
    direct = (() if cells is None
              else tuple(hcfg.is_direct(l) for l in range(L)))
    if pack.modes[0] == "int" and _use_kernels(use_pallas):
        lyr = pack.layers[name]
        return ops_fused_field_query(
            idx, w, cat, rows, _layer_wq(pack, name), lyr,
            use_pallas=use_pallas, cells_cat=cells, direct=direct,
        ) + lyr["b"]
    enc = ops_hash_encode(idx, w, cat, rows, cells, direct)
    return _fused_linear(pack, 0, name, enc, use_pallas)


def fused_ngp_apply(
    pack: FusedPack,
    points: jnp.ndarray,  # (P, 3) in [0, 1]
    dirs: jnp.ndarray,  # (P, 3) unit
    cfg: NGPConfig,
    use_pallas="auto",
    corner_data=None,  # optional precomputed (idx (L,P,8), w (L,P,8))
    sh: Optional[jnp.ndarray] = None,  # optional precomputed (P, sh_dim)
):
    """Integer-mode field query. Mirrors `ngp_apply`'s fake-quant forward;
    exact up to float roundoff (integer accumulation where lowered).
    `corner_data` / `sh` take the geometry-only work precomputed by a
    `CullPlan` for fixed sample points. The encode and the first linear
    are `_fused_first_linear`'s."""
    names = ngp_linear_names(cfg)
    h = _fused_first_linear(pack, points, cfg.hash, names[0], use_pallas,
                            corner_data)
    h = jax.nn.relu(h)
    h = _fused_linear(pack, 1, names[1], h, use_pallas)
    raw_sigma, geo = h[..., 0], h[..., 1:]
    if cfg.density_activation == "exp":
        sigma = jnp.exp(jnp.clip(raw_sigma, -10.0, 10.0))
    else:
        sigma = jax.nn.softplus(raw_sigma)

    if sh is None:
        sh = sh_encode(dirs, cfg.sh_degree)
    c = jnp.concatenate([geo, sh], axis=-1)
    c = jax.nn.relu(_fused_linear(pack, 2, names[2], c, use_pallas))
    c = jax.nn.relu(_fused_linear(pack, 3, names[3], c, use_pallas))
    rgb = jax.nn.sigmoid(_fused_linear(pack, 4, names[4], c, use_pallas))
    return sigma, rgb


# ---------------------------------------------------------------------------
# CullPlan: host-precomputed compaction for FIXED rays.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class CullPlan:
    """Per-chunk precomputed compaction of active samples.

    For C chunks of R rays x S samples (P = R*S flattened samples):
      buf_pts  (C, B, 3) f32 — the active sample points, compacted, in
                               [0,1]^3 (deterministic eval sampling is
                               policy- and params-independent, so the
                               culled field-query INPUTS are fixed too);
      buf_dirs (C, B, 3) f32 — matching ray directions;
      take     (C, P) int32  — buffer slot holding sample k's result;
      valid    (C, P) bool   — sample k survives culling.
    B is EXACT (max active count over chunks, 128-aligned): the active
    mask depends only on ray geometry and the frozen occupancy grid.

    Everything else geometry-static is baked too, so the fused hot path
    starts at the table gathers / MLP matmuls:
      hash_idx (C, L, B, 8) int32 — per-level voxel-corner table rows;
      hash_w   (C, L, B, 8) f32   — matching trilinear weights;
      sh       (C, B, sh_dim) f32 — spherical-harmonic view basis.
    """

    buf_pts: jnp.ndarray
    buf_dirs: jnp.ndarray
    take: jnp.ndarray
    valid: jnp.ndarray
    hash_idx: jnp.ndarray
    hash_w: jnp.ndarray
    sh: jnp.ndarray

    @property
    def budget(self) -> int:
        return self.buf_pts.shape[-2]


jax.tree_util.register_dataclass(
    CullPlan,
    data_fields=[
        "buf_pts", "buf_dirs", "take", "valid", "hash_idx", "hash_w", "sh"
    ],
    meta_fields=[],
)


def build_cull_plan(
    occ: OccupancyGrid,
    ro_chunks: np.ndarray,  # (C, R, 3) rays, padded rows allowed
    rd_chunks: np.ndarray,  # (C, R, 3)
    ray_mask: Optional[np.ndarray],  # (C, R, 1) 1.0 = real ray, or None
    rcfg,  # RenderConfig (deterministic sampling assumed)
    cfg: NGPConfig,
    align: int = 128,
) -> CullPlan:
    """Precompute the compaction for a fixed, chunked ray population."""
    ro = np.asarray(ro_chunks, np.float32)
    rd = np.asarray(rd_chunks, np.float32)
    C, R = ro.shape[:2]
    S = rcfg.n_samples
    # Shared oracle with `cull_budget` — the counts must match exactly.
    active, pts = sample_active_mask(occ, ro, rd, rcfg)  # (C, R, S)
    if ray_mask is not None:
        active &= np.asarray(ray_mask).reshape(C, R, 1) > 0.5
    active = active.reshape(C, R * S)

    counts = active.sum(axis=1)
    B = max(align, int(np.ceil(counts.max() / align) * align))
    B = min(B, R * S)
    pts_unit = np.clip(pts + 0.5, 0.0, 1.0).reshape(C, R * S, 3)
    dirs_flat = np.broadcast_to(rd[:, :, None, :], pts.shape).reshape(C, R * S, 3)
    buf_pts = np.zeros((C, B, 3), np.float32)
    buf_dirs = np.zeros((C, B, 3), np.float32)
    take = np.zeros((C, R * S), np.int32)
    valid = np.zeros((C, R * S), bool)
    for c in range(C):
        idx = np.nonzero(active[c])[0]
        buf_pts[c, : idx.size] = pts_unit[c, idx]
        buf_dirs[c, : idx.size] = dirs_flat[c, idx]
        take[c, idx] = np.arange(idx.size, dtype=np.int32)
        valid[c, idx] = True

    # Bake the remaining geometry-only field-query work (one-time host
    # loop; jitted helpers keep the bake itself fast).
    L = cfg.hash.n_levels
    hash_idx = np.zeros((C, L, B, 8), np.int32)
    hash_w = np.zeros((C, L, B, 8), np.float32)
    sh = np.zeros((C, B, cfg.sh_dim), np.float32)
    corner_fn = jax.jit(
        lambda p: tuple(
            level_corner_data(p, l, cfg.hash) for l in range(L)
        )
    )
    sh_fn = jax.jit(lambda d: sh_encode(d, cfg.sh_degree))
    for c in range(C):
        for l, (ci, cw) in enumerate(corner_fn(jnp.asarray(buf_pts[c]))):
            hash_idx[c, l] = np.asarray(ci)
            hash_w[c, l] = np.asarray(cw)
        sh[c] = np.asarray(sh_fn(jnp.asarray(buf_dirs[c])))
    return CullPlan(
        buf_pts=jnp.asarray(buf_pts), buf_dirs=jnp.asarray(buf_dirs),
        take=jnp.asarray(take), valid=jnp.asarray(valid),
        hash_idx=jnp.asarray(hash_idx), hash_w=jnp.asarray(hash_w),
        sh=jnp.asarray(sh),
    )


# ---------------------------------------------------------------------------
# Occupancy-culled ray rendering (one chunk).
# ---------------------------------------------------------------------------
def _chunk_color(
    params, pack, spec, occ, rays_o, rays_d,
    cfg, rcfg, mode, budget, use_pallas, early_stop,
    key=None, plan_row=None,
):
    """Core renderer for one chunk of rays. Returns (color (R,3), acc (R,1)).

    Ad-hoc rays get their active mask from the occupancy ray-march kernel
    and are compacted with a `nonzero` gather; `plan_row` replaces both
    with a host-baked plan's gathers.
    """
    n_rays = rays_o.shape[0]
    n_s = rcfg.n_samples
    # Staged as a jit constant from the SAME host linspace the plan/budget
    # oracles use -> host-baked plans and on-device compaction see
    # bit-identical sample points (jnp.linspace differs by ~1 ulp).
    t1 = jnp.asarray(ray_t_samples(rcfg))
    t = jnp.broadcast_to(t1, (n_rays, n_s))
    if rcfg.stratified and key is not None:
        dt = (rcfg.far - rcfg.near) / n_s
        t = t + jax.random.uniform(key, t.shape) * dt

    def field(p, d, corner_data=None, sh=None):
        if mode == "fused":
            return fused_ngp_apply(
                pack, p, d, cfg, use_pallas=use_pallas,
                corner_data=corner_data, sh=sh,
            )
        return ngp_apply(params, p, d, cfg, spec)

    if plan_row is not None:
        # Precomputed compaction: the culled field-query inputs (and their
        # hash-corner / SH bases) are staged in the plan — the hot path
        # starts at the table gathers and MLP matmuls.
        buf_pts, buf_dirs, take, valid, hash_idx, hash_w, sh = plan_row
        sigma_b, rgb_b = field(
            buf_pts, buf_dirs, corner_data=(hash_idx, hash_w), sh=sh
        )
        sigma = jnp.where(valid, sigma_b[take], 0.0).reshape(n_rays, n_s)
        rgb = jnp.where(valid[:, None], rgb_b[take], 0.0).reshape(n_rays, n_s, 3)
    else:
        pts = rays_o[:, None, :] + rays_d[:, None, :] * t[..., None]  # (R, S, 3)
        pts_unit = jnp.clip(pts + 0.5, 0.0, 1.0)  # [-0.5,0.5] -> [0,1]
        inside = jnp.all((pts > -0.5) & (pts < 0.5), axis=-1)  # (R, S)
        flat_pts = pts_unit.reshape(-1, 3)
        flat_dirs = jnp.broadcast_to(rays_d[:, None, :], pts.shape).reshape(-1, 3)
        P = n_rays * n_s
        if occ is None:
            sigma, rgb = field(flat_pts, flat_dirs)
            sigma = jnp.where(inside, sigma.reshape(n_rays, n_s), 0.0)
            rgb = rgb.reshape(n_rays, n_s, 3)
        else:
            # Ad-hoc rays: active mask -> stable on-device compaction.
            # The march kernel and the inline lookup agree bit-exactly
            # (`ref.ray_march_ref` IS this expression); stratified sampling
            # perturbs t per ray, which the (S,)-t kernel cannot see.
            if rcfg.stratified and key is not None:
                active = inside.reshape(-1) & occupancy_lookup(occ, flat_pts)
            else:
                active = ops_ray_march(
                    occ.occ, rays_o, rays_d, t1,
                    use_pallas=use_pallas, early_stop=early_stop,
                ).reshape(-1) > 0.5
            B = P if budget is None else min(int(budget), P)
            rank = jnp.cumsum(active) - 1  # (P,) int
            valid = active & (rank < B)  # budget overflow drops samples
            # Gather compaction: nonzero returns the active flat indices
            # in increasing order, so buffer row `rank` holds the sample
            # of that rank.
            (inv_take,) = jnp.nonzero(valid, size=B, fill_value=0)
            buf_pts = flat_pts[inv_take]
            buf_dirs = flat_dirs[inv_take]
            sigma_b, rgb_b = field(buf_pts, buf_dirs)
            take = jnp.clip(rank, 0, B - 1)
            sigma = jnp.where(valid, sigma_b[take], 0.0).reshape(n_rays, n_s)
            rgb = jnp.where(valid[:, None], rgb_b[take], 0.0).reshape(n_rays, n_s, 3)

    delta = jnp.diff(t, axis=-1)
    delta = jnp.concatenate([delta, jnp.full_like(delta[..., :1], 1e10)], axis=-1)
    color, acc = ops_alpha_composite(
        sigma, rgb, delta, use_pallas=use_pallas, early_stop=early_stop
    )
    if rcfg.white_bg:
        color = color + (1.0 - acc)
    return color, acc


def fast_render_rays(
    params: Dict,
    rays_o: jnp.ndarray,  # (R, 3)
    rays_d: jnp.ndarray,  # (R, 3) unit
    cfg: NGPConfig,
    rcfg,  # RenderConfig
    spec: Optional[NGPQuantSpec] = None,
    occ: Optional[OccupancyGrid] = None,
    mode: str = "reference",
    pack: Optional[FusedPack] = None,
    budget: Optional[int] = None,
    key: Optional[jax.Array] = None,
    use_pallas="auto",
    early_stop: bool = True,
    plan: Optional[CullPlan] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Occupancy-culled render of one ray batch -> (color (R,3), acc (R,1)).

    `mode="reference"` queries the fake-quant `ngp_apply` oracle;
    `mode="fused"` queries the integer `FusedPack` path (built from
    (params, spec) on the fly when `pack` is not given — pass a prebuilt
    pack inside jit/vmap, where spec bits are not concrete). A
    single-chunk `plan` (see `build_cull_plan`) replaces the on-device
    compaction with precomputed gathers.
    """
    assert mode in ("reference", "fused"), mode
    if mode == "fused" and pack is None:
        pack = build_fused_pack(params, cfg, spec)
    plan_row = None
    if plan is not None:
        assert plan.buf_pts.shape[0] == 1, "fast_render_rays takes a 1-chunk plan"
        plan_row = (
            plan.buf_pts[0], plan.buf_dirs[0], plan.take[0], plan.valid[0],
            plan.hash_idx[0], plan.hash_w[0], plan.sh[0],
        )
    return _chunk_color(
        params, pack, spec, occ, rays_o, rays_d,
        cfg, rcfg, mode, budget, use_pallas, early_stop, key, plan_row,
    )


# ---------------------------------------------------------------------------
# Device-resident full-frame paths.
# ---------------------------------------------------------------------------
def _effective_chunk(n_rays: int, chunk: int) -> int:
    return min(chunk, -(-n_rays // 128) * 128)


def _pad_frame(rays_o, rays_d, gt, chunk: int):
    """-> (ro (C,chunk,3), rd, gt, mask (C,chunk,1)) host-side prep."""
    n = rays_o.shape[0]
    c = _effective_chunk(n, chunk)
    n_chunks = -(-n // c)
    pad = n_chunks * c - n
    def _p(a):
        return jnp.asarray(
            np.pad(np.asarray(a, np.float32), ((0, pad), (0, 0)))
        ).reshape(n_chunks, c, -1)
    mask = np.zeros((n_chunks * c, 1), np.float32)
    mask[:n] = 1.0
    return _p(rays_o), _p(rays_d), _p(gt), jnp.asarray(mask).reshape(n_chunks, c, 1)


# Device-staged held-out test sets (and their cull plans), keyed by array
# identity. The HERO loop evaluates the SAME views once per episode:
# staging once keeps every later evaluation a single jit dispatch with no
# host->device ray copies and no per-episode plan rebuilds. Cached entries
# pin their source arrays so ids cannot be recycled; both caches are
# bounded (oldest-out) so sweeps over many scenes/seeds cannot accumulate
# staged test sets without limit.
_TEST_STAGE_CACHE: Dict[Tuple, Tuple] = {}
_PLAN_CACHE: Dict[Tuple, Tuple] = {}
_CACHE_CAP = 8


def _cache_put(cache: Dict, key, value) -> None:
    if key not in cache and len(cache) >= _CACHE_CAP:
        cache.pop(next(iter(cache)))  # dicts iterate in insertion order
    cache[key] = value


def _stage_test_set(dataset, chunk: int):
    key = (id(dataset.test_rays_o), chunk)
    hit = _TEST_STAGE_CACHE.get(key)
    if hit is not None and hit[0] is dataset.test_rays_o:
        return hit[1]
    # Views are independent rays: stage them FLAT so a small test set
    # becomes a single chunk (one field query per evaluation) while big
    # ones still chunk to bound memory.
    ro, rd, g, m = _pad_frame(
        dataset.test_rays_o.reshape(-1, 3), dataset.test_rays_d.reshape(-1, 3),
        dataset.test_rgb.reshape(-1, 3), chunk,
    )
    staged = (ro, rd, g, m, int(dataset.test_rgb.size))
    _cache_put(_TEST_STAGE_CACHE, key, (dataset.test_rays_o, staged))
    return staged


def _test_set_plan(
    dataset, occ: OccupancyGrid, rcfg, chunk: int, cfg: NGPConfig
) -> CullPlan:
    key = (id(dataset.test_rays_o), id(occ.occ), rcfg, chunk, cfg)
    hit = _PLAN_CACHE.get(key)
    if hit is not None and hit[0] is dataset.test_rays_o and hit[1] is occ.occ:
        return hit[2]
    ro, rd, _, mask, _ = _stage_test_set(dataset, chunk)
    plan = build_cull_plan(
        occ, np.asarray(ro), np.asarray(rd), np.asarray(mask), rcfg, cfg
    )
    _cache_put(_PLAN_CACHE, key, (dataset.test_rays_o, occ.occ, plan))
    return plan


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "rcfg", "mode", "budget", "use_pallas", "early_stop"),
)
def _frame_se_impl(
    params, pack, spec, occ, plan, rays_o, rays_d, gt, mask,
    *, cfg, rcfg, mode, budget, use_pallas, early_stop,
):
    def body(xs):
        (ro, rd, g, m), plan_row = xs[:4], (xs[4:] or None)
        color, _ = _chunk_color(
            params, pack, spec, occ, ro, rd,
            cfg, rcfg, mode, budget, use_pallas, early_stop,
            plan_row=plan_row,
        )
        return jnp.sum(((color - g) ** 2) * m)
    xs = (rays_o, rays_d, gt, mask)
    if plan is not None:
        xs = xs + (plan.buf_pts, plan.buf_dirs, plan.take, plan.valid,
                   plan.hash_idx, plan.hash_w, plan.sh)
    return jnp.sum(jax.lax.map(body, xs))


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "rcfg", "mode", "budget", "use_pallas",
                     "early_stop"),
)
def _frame_colors_impl(
    params, pack, spec, occ, rays_o, rays_d,
    *, cfg, rcfg, mode, budget, use_pallas, early_stop,
):
    # Image rendering takes arbitrary rays (no precomputed plan): the
    # dynamic compaction path under `budget` applies per chunk.
    def body(xs):
        ro, rd = xs
        color, _ = _chunk_color(
            params, pack, spec, occ, ro, rd,
            cfg, rcfg, mode, budget, use_pallas, early_stop,
        )
        return color
    return jax.lax.map(body, (rays_o, rays_d))


# ---------------------------------------------------------------------------
# Per-slot serve impls: the three pose-cache tiers of `FusedDeviceStep`.
# ---------------------------------------------------------------------------
# One jitted call per (slot_rays,)-shaped slot instead of one lax.map over
# the whole bucket: the bodies were sequential under lax.map anyway, and
# per-slot dispatch lets a bucket MIX cache-hit / warped-plan / ray-march
# slots at fixed padded shapes without a retrace (each tier compiles once
# per shape).

@functools.partial(
    jax.jit,
    static_argnames=("cfg", "rcfg", "mode", "budget", "use_pallas",
                     "early_stop"),
)
def _slot_march_impl(
    params, pack, spec, occ, rays_o, rays_d,
    *, cfg, rcfg, mode, budget, use_pallas, early_stop,
):
    """Cache-miss tier: march render + the TRUE device active count, so
    the engine detects budget overflow from the returned scalar instead of
    a host-side mask pass per step (XLA shares the march between the two
    uses)."""
    color, _ = _chunk_color(
        params, pack, spec, occ, rays_o, rays_d,
        cfg, rcfg, mode, budget, use_pallas, early_stop,
    )
    t1 = jnp.asarray(ray_t_samples(rcfg))
    active = ops_ray_march(
        occ.occ, rays_o, rays_d, t1,
        use_pallas=use_pallas, early_stop=early_stop,
    )
    return color, jnp.sum(active > 0.5).astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "rcfg", "mode", "use_pallas", "early_stop"),
)
def _slot_plan_impl(
    params, pack, spec, occ, rays_o, rays_d, plan_row,
    *, cfg, rcfg, mode, use_pallas, early_stop,
):
    """Cache-hit tier: the slot's rays fingerprint-match a baked plan —
    precomputed gathers, hash corners, and SH bases (CullPlan speed)."""
    color, _ = _chunk_color(
        params, pack, spec, occ, rays_o, rays_d,
        cfg, rcfg, mode, None, use_pallas, early_stop, plan_row=plan_row,
    )
    return color


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "rcfg", "mode", "use_pallas", "early_stop"),
)
def _slot_warp_impl(
    params, pack, spec, occ, rays_o, rays_d, inv_take, take, valid_cons,
    *, cfg, rcfg, mode, use_pallas, early_stop,
):
    """Warped-plan tier: reuse a nearby pose's CONSERVATIVE compaction
    indices for these rays. The cached plan contributes indices only —
    field inputs are the ACTUAL sample points of these rays — and the
    final mask re-intersects with the exact device march, so a
    conservative plan that covers every exact-active sample reproduces
    the march tier's render (same points queried, same samples kept)."""
    n_rays = rays_o.shape[0]
    n_s = rcfg.n_samples
    t1 = jnp.asarray(ray_t_samples(rcfg))
    t = jnp.broadcast_to(t1, (n_rays, n_s))
    pts = rays_o[:, None, :] + rays_d[:, None, :] * t[..., None]
    pts_unit = jnp.clip(pts + 0.5, 0.0, 1.0)
    flat_pts = pts_unit.reshape(-1, 3)
    flat_dirs = jnp.broadcast_to(rays_d[:, None, :], pts.shape).reshape(-1, 3)
    buf_pts = flat_pts[inv_take]
    buf_dirs = flat_dirs[inv_take]
    if mode == "fused":
        sigma_b, rgb_b = fused_ngp_apply(
            pack, buf_pts, buf_dirs, cfg, use_pallas=use_pallas
        )
    else:
        sigma_b, rgb_b = ngp_apply(params, buf_pts, buf_dirs, cfg, spec)
    exact = ops_ray_march(
        occ.occ, rays_o, rays_d, t1,
        use_pallas=use_pallas, early_stop=early_stop,
    ).reshape(-1) > 0.5
    valid = valid_cons & exact
    sigma = jnp.where(valid, sigma_b[take], 0.0).reshape(n_rays, n_s)
    rgb = jnp.where(valid[:, None], rgb_b[take], 0.0).reshape(n_rays, n_s, 3)
    delta = jnp.diff(t, axis=-1)
    delta = jnp.concatenate(
        [delta, jnp.full_like(delta[..., :1], 1e10)], axis=-1
    )
    color, acc = ops_alpha_composite(
        sigma, rgb, delta, use_pallas=use_pallas, early_stop=early_stop
    )
    if rcfg.white_bg:
        color = color + (1.0 - acc)
    return color


# ---------------------------------------------------------------------------
# Nerfacto: three packed fields, two programs per slot.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class NerfactoPack:
    """A Nerfacto field's packed inference form: one `FusedPack` per
    field (the main field's five linears and its tables; each proposal
    field's two linears and its tables) and the appearance input the
    color MLP is served with (`nerfacto.serve_appearance`)."""

    main: FusedPack
    proposals: Tuple[FusedPack, ...]
    appearance: jnp.ndarray  # (A,)

    def fields(self):
        """(name, pack) of each field; the main field's name is ""."""
        return [("", self.main)] + [
            (f"prop{k + 1}", p) for k, p in enumerate(self.proposals)]


jax.tree_util.register_dataclass(
    NerfactoPack, data_fields=["main", "proposals", "appearance"],
    meta_fields=[],
)


def build_nerfacto_pack(
    params: Dict,
    cfg: nerfacto.NerfactoConfig,
    spec: nerfacto.NerfactoQuantSpec,
    layout: str = f"tile:{DEFAULT_TILE_BK}",
) -> NerfactoPack:
    """`pack_field` for each of the three fields (a concrete spec, as for
    `build_fused_pack`)."""
    main = pack_field(params, nerfacto.linear_names(cfg)[:5], params["hash"],
                      cfg.field.hash, spec.main, layout)
    props = tuple(
        pack_field(params, nerfacto.proposal_names(k),
                   params[nerfacto.hash_key(k)], cfg.proposals[k], s, layout)
        for k, s in enumerate(spec.proposals))
    return NerfactoPack(main=main, proposals=props,
                        appearance=nerfacto.serve_appearance(params))


def fused_proposal_density(pack: FusedPack, x01, hcfg, k: int, use_pallas):
    """Proposal field k's density (P,) at field coordinates (P, 3)."""
    a, b = nerfacto.proposal_names(k)
    h = jax.nn.relu(_fused_first_linear(pack, x01, hcfg, a, use_pallas))
    return jnp.exp(_fused_linear(pack, 1, b, h, use_pallas)[:, 0])


def fused_nerfacto_field(pack: NerfactoPack, x01, dirs, cfg, use_pallas):
    """The main field's (density (P,), rgb (P, 3)); the color MLP takes
    [SH(dir), geometry features, the served appearance]."""
    f, main = cfg.field, pack.main
    names = nerfacto.linear_names(cfg)
    h = jax.nn.relu(_fused_first_linear(main, x01, f.hash, names[0],
                                        use_pallas))
    h = _fused_linear(main, 1, names[1], h, use_pallas)
    app = jnp.broadcast_to(pack.appearance, (x01.shape[0], cfg.appearance_dim))
    c = jnp.concatenate([sh_encode(dirs, f.sh_degree), h[:, 1:], app], -1)
    c = jax.nn.relu(_fused_linear(main, 2, names[2], c, use_pallas))
    c = jax.nn.relu(_fused_linear(main, 3, names[3], c, use_pallas))
    rgb = jax.nn.sigmoid(_fused_linear(main, 4, names[4], c, use_pallas))
    return jnp.exp(h[:, 0]), rgb


@functools.partial(jax.jit, static_argnames=("cfg", "use_pallas"))
def _slot_propose_impl(pack, rays_o, rays_d, *, cfg, use_pallas):
    """The proposal passes of one slot (contraction, proposal 1, resample,
    proposal 2, resample): the (R, n + 1) Euclidean edges of the
    intervals the main field shades."""
    fns = [functools.partial(fused_proposal_density, p, hcfg=h, k=k,
                             use_pallas=use_pallas)
           for k, (p, h) in enumerate(zip(pack.proposals, cfg.proposals))]
    bins_list, _ = nerfacto.propose(fns, rays_o, rays_d, cfg)
    return nerfacto.spacing_to_euclidean(bins_list[-1], cfg)


@functools.partial(
    jax.jit, static_argnames=("cfg", "use_pallas", "early_stop"),
)
def _slot_shade_impl(pack, rays_o, rays_d, edges, *, cfg, use_pallas,
                     early_stop):
    """The main field at the slot's interval midpoints, composited over
    the intervals' own lengths on a white background: colors (R, 3)."""
    pts, delta = nerfacto.sample_points(rays_o, rays_d, edges)
    R, S = delta.shape
    x01, sel = nerfacto.field_coords(pts.reshape(-1, 3))
    dirs = jnp.broadcast_to(rays_d[:, None, :], pts.shape).reshape(-1, 3)
    sigma, rgb = fused_nerfacto_field(pack, x01, dirs, cfg, use_pallas)
    sigma = jnp.where(sel, sigma, 0.0).reshape(R, S)
    color, acc = ops_alpha_composite(
        sigma, rgb.reshape(R, S, 3), delta, use_pallas=use_pallas,
        early_stop=early_stop,
    )
    return color + (1.0 - acc)


class FastRenderEngine:
    """Bundles (params, spec, occupancy, mode) into jit-backed frame calls.

    Build one per (params, policy) pair — construction is cheap (the
    FusedPack quantizes five small matrices and the hash tables); the
    underlying jitted functions, staged test sets, and cull plans are
    shared across engines with the same static configuration, so
    per-episode engines neither retrace nor restage.
    """

    def __init__(
        self,
        params: Dict,
        cfg: NGPConfig,
        rcfg,
        spec: Optional[NGPQuantSpec] = None,
        occ: Optional[OccupancyGrid] = None,
        mode: str = "fused",
        chunk: int = 4096,
        budget: Optional[int] = None,
        use_pallas="auto",
        early_stop: bool = True,
        pack: Optional[FusedPack] = None,
    ):
        """`pack=` serves a prebuilt `FusedPack` verbatim (deployable
        artifacts load their packed codes from disk); by default the pack
        is quantized from (params, spec) at construction."""
        assert mode in ("reference", "fused"), mode
        self.params = params
        self.cfg = cfg
        self.rcfg = dataclasses.replace(rcfg, stratified=False)
        self.spec = no_quant_spec(cfg) if spec is None else spec
        self.occ = occ
        self.mode = mode
        self.chunk = chunk
        self.use_pallas = use_pallas
        self.early_stop = early_stop
        if pack is None and mode == "fused":
            pack = build_fused_pack(params, cfg, self.spec)
        self.pack = pack if mode == "fused" else None
        self._budget = budget
        self._budget_cache: Dict[Tuple, int] = {}

    def _resolve_budget(self, rays_o, rays_d) -> Optional[int]:
        """Per-chunk sample budget for the DYNAMIC compaction path:
        explicit > cached-per-ray-content > derived from the rays.

        Keyed by a content fingerprint, NOT object identity: callers
        naturally pass fresh slice views (`dataset.test_rays_o[v]`), so
        ids never repeat, while same-sized but different ray populations
        must not reuse each other's budgets. The render call materializes
        the rays on host anyway, so the hash is marginal."""
        if self.occ is None:
            return None
        if self._budget is not None:
            return self._budget
        ro = np.asarray(rays_o, np.float32).reshape(-1, 3)
        rd = np.asarray(rays_d, np.float32).reshape(-1, 3)
        key = (ro.shape[0], hash(ro.tobytes()), hash(rd.tobytes()))
        hit = self._budget_cache.get(key)
        if hit is not None:
            return hit
        c = _effective_chunk(ro.shape[0], self.chunk)
        budget = cull_budget(self.occ, ro, rd, self.rcfg, c)
        _cache_put(self._budget_cache, key, budget)
        return budget

    def render_rays(self, rays_o, rays_d) -> jnp.ndarray:
        """One-chunk render -> color (R, 3) on device."""
        color, _ = fast_render_rays(
            self.params, jnp.asarray(rays_o), jnp.asarray(rays_d),
            self.cfg, self.rcfg, self.spec, self.occ, self.mode, self.pack,
            self._resolve_budget(rays_o, rays_d),
            use_pallas=self.use_pallas, early_stop=self.early_stop,
        )
        return color

    def frame_se(self, rays_o, rays_d, gt, budget: Optional[int] = None) -> jnp.ndarray:
        """Masked squared error of a full frame — ONE device scalar."""
        if budget is None:
            budget = self._resolve_budget(rays_o, rays_d)
        ro, rd, g, m = _pad_frame(rays_o, rays_d, gt, self.chunk)
        return _frame_se_impl(
            self.params, self.pack, self.spec, self.occ, None, ro, rd, g, m,
            cfg=self.cfg, rcfg=self.rcfg, mode=self.mode, budget=budget,
            use_pallas=self.use_pallas, early_stop=self.early_stop,
        )

    def render_frame(self, rays_o, rays_d) -> jnp.ndarray:
        """Full frame -> (N, 3) colors, device-resident `lax.map` loop."""
        n = rays_o.shape[0]
        budget = self._resolve_budget(rays_o, rays_d)
        gt0 = np.zeros((n, 3), np.float32)  # only for shared padding helper
        ro, rd, _, _ = _pad_frame(rays_o, rays_d, gt0, self.chunk)
        colors = _frame_colors_impl(
            self.params, self.pack, self.spec, self.occ, ro, rd,
            cfg=self.cfg, rcfg=self.rcfg, mode=self.mode, budget=budget,
            use_pallas=self.use_pallas, early_stop=self.early_stop,
        )
        return colors.reshape(-1, 3)[:n]

    def test_views_budget(self, dataset) -> Optional[int]:
        """The exact per-chunk budget the staged test set renders under
        (the cull plan's B), None without an occupancy grid."""
        if self.occ is None:
            return None
        return _test_set_plan(
            dataset, self.occ, self.rcfg, self.chunk, self.cfg
        ).budget

    def evaluate_psnr(self, dataset) -> float:
        """Mean PSNR over held-out views.

        The test set (and its cull plan) is staged on device once and the
        whole evaluation — every view's chunks plus the squared-error
        reduction — is ONE jitted call returning ONE scalar. Per-view SE
        remains available through `frame_se`. An explicit engine `budget`
        overrides the plan: the dynamic compaction renders under that cap
        instead (the caller is bounding memory/compute on purpose).
        """
        ro, rd, gt, mask, total_px = _stage_test_set(dataset, self.chunk)
        plan, budget = None, None
        if self.occ is not None:
            if self._budget is not None:
                budget = self._budget
            else:
                plan = _test_set_plan(
                    dataset, self.occ, self.rcfg, self.chunk, self.cfg
                )
        se = _frame_se_impl(
            self.params, self.pack, self.spec, self.occ, plan, ro, rd, gt, mask,
            cfg=self.cfg, rcfg=self.rcfg, mode=self.mode, budget=budget,
            use_pallas=self.use_pallas, early_stop=self.early_stop,
        )
        from repro.nerf.train import psnr  # lazy: train imports us lazily too

        return psnr(float(se) / total_px)
