"""Packed-kernel parity: storage-planar and tile-native word layouts,
K-padding zero-point handling, block-size invariance, and the fused
field-query entry — all pinned bit-identical to the jnp reference.

This file is the CI fast-lane "kernel parity" gate (bits 2/4/6/8 run in
interpret mode there); keep it dependency-light and seconds-fast.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels import ops
from repro.kernels import autotune
from repro.kernels.quant_matmul import quant_matmul, quant_matmul_packed
from repro.kernels.repack import (
    DEFAULT_TILE_BK,
    repack_tile_native,
    unrepack_planar,
)
from repro.quant.packing import pack_codes


def _packed(k, n, bits, seed=0, scale=0.02):
    rng = np.random.RandomState(seed)
    lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
    return pack_codes(rng.randint(lo, hi + 1, size=(k, n)), bits, scale=scale)


def _x(m, k, seed=1):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randint(-128, 128, size=(m, k)), jnp.int8)


# ---------------------------------------------------------------------------
# K-padding zero-point regression (the suspected unpack-hot-path bug):
# when K % bk != 0 the kernel zero-pads both operands' K tiles. A nonzero
# activation zero point zx must NOT pick up the padded weight rows — the
# padded w codes are zero, so both x.w and zx*colsum(w) see nothing. Pin
# that with K values that leave ragged tails at every block size.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("k,bk", [(129, 64), (129, 128), (200, 128),
                                  (33, 128)])
@pytest.mark.parametrize("zx", [17, 128])
def test_int8_kpad_zero_point_exact(k, bk, zx):
    m, n = 33, 16
    x = _x(m, k)
    rng = np.random.RandomState(2)
    w = jnp.asarray(rng.randint(-127, 128, size=(k, n)), jnp.int8)
    got = quant_matmul(x, w, 0.037, 0.011, zx, bm=32, bn=16, bk=bk)
    want = ref.quant_matmul_ref(x, w, 0.037, 0.011, zx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("k,bk", [(129, 64), (129, 128)])
@pytest.mark.parametrize("zx", [17, 128])
def test_packed_kpad_zero_point_exact(k, bk, zx):
    m, n, bits = 33, 16, 4
    x, wq = _x(m, k), _packed(k, n, bits)
    got = quant_matmul_packed(
        x, wq.words, wq.offset, 0.037, wq.scale, zx,
        bits=bits, bm=32, bn=16, bk=bk,
    )
    want = ref.quant_matmul_packed_ref(x, wq, 0.037, wq.scale, zx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Planar and tile-native unpack-on-load, every bit width
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("bits", [1, 2, 3, 4, 5, 6, 7, 8])
def test_packed_planar_parity_all_bits(bits):
    m, k, n = 33, 129, 16
    x, wq = _x(m, k), _packed(k, n, bits)
    got = ops.quant_matmul_packed(x, wq, 0.1, wq.scale, 17,
                                  use_pallas=True, bm=32, bn=16, bk=64)
    want = ref.quant_matmul_packed_ref(x, wq, 0.1, wq.scale, 17)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("bits", [1, 2, 3, 4, 5, 6, 7, 8])
def test_packed_tile_native_parity_all_bits(bits):
    m, k, n = 33, 129, 16
    x, wq = _x(m, k), _packed(k, n, bits)
    wt = repack_tile_native(wq, bk=128)
    assert wt.layout == "tile:128"
    got = ops.quant_matmul_packed(x, wt, 0.1, wt.scale, 17, use_pallas=True)
    want = ref.quant_matmul_packed_ref(x, wq, 0.1, wq.scale, 17)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_tile_native_reference_path_matches():
    """use_pallas=False on a tile-native weight unpacks via the layout-
    aware codec — same numbers as the planar reference."""
    x, wq = _x(17, 65), _packed(65, 9, 3)
    wt = repack_tile_native(wq, bk=64)
    got = ops.quant_matmul_packed(x, wt, 0.1, wt.scale, 5, use_pallas=False)
    want = ops.quant_matmul_packed(x, wq, 0.1, wq.scale, 5, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("bits", [1, 4, 7, 8])
@pytest.mark.parametrize("bk", [32, 64, 128, 256])
def test_repack_roundtrip_byte_identity(bits, bk):
    wq = _packed(129, 7, bits)
    wt = repack_tile_native(wq, bk=bk)
    back = unrepack_planar(wt)
    assert back.layout == "planar"
    np.testing.assert_array_equal(np.asarray(back.words),
                                  np.asarray(wq.words))
    np.testing.assert_array_equal(np.asarray(wt.codes()),
                                  np.asarray(wq.codes()))
    assert wt.nbytes_packed == wq.nbytes_packed  # storage accounting


def test_repack_is_idempotent_and_checks_layout():
    wq = _packed(64, 8, 4)
    wt = repack_tile_native(wq, bk=DEFAULT_TILE_BK)
    assert repack_tile_native(wt, bk=DEFAULT_TILE_BK) is wt


# ---------------------------------------------------------------------------
# Block sizes never change numerics; tile layout pins bk
# ---------------------------------------------------------------------------
def test_block_size_invariance():
    x, wq = _x(70, 200), _packed(200, 24, 5)
    outs = [
        np.asarray(ops.quant_matmul_packed(
            x, wq, 0.1, wq.scale, 9, use_pallas=True, bm=bm, bn=bn, bk=bk
        ))
        for bm, bn, bk in [(32, 16, 64), (128, 128, 128), (256, 128, 256)]
    ]
    for o in outs[1:]:
        np.testing.assert_array_equal(outs[0], o)


def test_tile_layout_pins_bk():
    x, wq = _x(33, 129), _packed(129, 16, 4)
    wt = repack_tile_native(wq, bk=128)
    with pytest.raises(ValueError, match="tile-native"):
        ops.quant_matmul_packed(x, wt, 0.1, wt.scale, 3,
                                use_pallas=True, bm=128, bn=128, bk=64)


# ---------------------------------------------------------------------------
# Fused field-query entry: hash_encode and fused_field_query
# ---------------------------------------------------------------------------
def _hash_inputs(L=3, B=37, T=64, F=2, seed=3):
    rng = np.random.RandomState(seed)
    idx = jnp.asarray(rng.randint(0, T, size=(L, B, 8)), jnp.int32)
    w = jnp.asarray(rng.dirichlet(np.ones(8), size=(L, B)), jnp.float32)
    tables = [jnp.asarray(rng.randn(T, F), jnp.float32) for _ in range(L)]
    cat = jnp.concatenate(tables, axis=0)
    return idx, w, tables, cat, (T,) * L


def test_hash_encode_matches_per_level_gather():
    idx, w, tables, cat, rows = _hash_inputs()
    got = ops.hash_encode(idx, w, cat, rows)
    per_level = [
        jnp.sum(tables[l][idx[l]] * w[l][..., None], axis=1)
        for l in range(len(tables))
    ]
    want = jnp.concatenate(per_level, axis=-1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_fused_field_query_matches_manual_pipeline():
    idx, w, _, cat, rows = _hash_inputs(L=4, B=29, T=32, F=2)
    K = 4 * 2
    wq = _packed(K, 16, 4, scale=0.03)
    wt = repack_tile_native(wq)
    act = {"sx": 0.05, "zx_f": 128.0, "qmax": 255.0, "off": 128,
           "zx": jnp.int32(0)}
    got = ops.fused_field_query(idx, w, cat, rows, wt, act, use_pallas=True)

    enc = ops.hash_encode(idx, w, cat, rows)
    codes = jnp.clip(jnp.round(enc / act["sx"] + act["zx_f"]), 0.0,
                     act["qmax"])
    ci8 = (codes - act["off"]).astype(jnp.int8)
    want = ref.quant_matmul_packed_ref(ci8, wq, act["sx"], wq.scale,
                                       act["zx"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def _per_level_take(tables, idx, w):
    return jnp.concatenate([
        jnp.sum(jnp.take(tables[l], idx[l], axis=0) * w[l][..., None],
                axis=1)
        for l in range(len(tables))
    ], axis=-1)


def _level_inputs(rows, F, B, seed):
    """Per-level tables of `rows` rows and corner data whose first two
    corners pin each level's first and last row."""
    rng = np.random.RandomState(seed)
    tables = [jnp.asarray(rng.standard_normal((r, F)), jnp.float32)
              for r in rows]
    idx = jnp.asarray(np.stack([rng.randint(0, r, size=(B, 8)) for r in rows]),
                      jnp.int32)
    idx = idx.at[:, 0, 0].set(0)
    idx = idx.at[:, 0, 1].set(jnp.asarray([r - 1 for r in rows]))
    w = jnp.asarray(rng.dirichlet(np.ones(8), size=(len(rows), B)),
                    jnp.float32)
    return tables, idx, w


@pytest.mark.parametrize("use_pallas", [True, False])
def test_hash_encode_routing_across_onehot_domain(use_pallas):
    """Levels on both sides of 2^14 rows (the bound of the one-hot kernel
    this gather replaced): the encode is bit-identical to per-level
    `jnp.take`, and so is the field query's first layer built on it, on
    the kernel path and on the reference path."""
    rows = (300, 2 ** 14, 2 ** 14 + 1, 3 * 2 ** 14)
    tables, idx, w = _level_inputs(rows, F=2, B=16, seed=5)
    cat = jnp.concatenate(tables)
    want = _per_level_take(tables, idx, w)
    got = ops.hash_encode(idx, w, cat, rows)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    wq = repack_tile_native(_packed(2 * len(rows), 16, 4, scale=0.03))
    act = {"sx": 0.05, "zx_f": 128.0, "qmax": 255.0, "off": 128,
           "zx": jnp.int32(0)}
    codes = jnp.clip(jnp.round(want / act["sx"] + act["zx_f"]), 0.0,
                     act["qmax"])
    ci8 = (codes - act["off"]).astype(jnp.int8)
    want_h = ops.quant_matmul_packed(ci8, wq, act["sx"], wq.scale, act["zx"],
                                     use_pallas=use_pallas)
    got_h = ops.fused_field_query(idx, w, cat, rows, wq, act,
                                  use_pallas=use_pallas)
    np.testing.assert_array_equal(np.asarray(got_h), np.asarray(want_h))


_HASH_KEYS = ("n_levels", "n_features", "log2_table_size", "base_resolution",
              "max_resolution")


def _bench_hash_cfg(name, F=2):
    """The hash encoding of a benchmark configuration (`nerfacto-prop<k>`
    names Nerfacto's proposal field k), with F features a level."""
    import json
    from pathlib import Path

    from repro.nerf.hash_encoding import HashEncodingConfig

    conf, _, prop = name.partition("-prop")
    path = Path(__file__).resolve().parents[1] / "bench" / "configs" / \
        f"{conf}.json"
    m = json.loads(path.read_text())["model"]
    if prop:
        m = m["proposals"][int(prop) - 1]
    return HashEncodingConfig(**{**{k: m[k] for k in _HASH_KEYS},
                                 "n_features": F})


def _points_with_boundary(n, seed):
    """Points in [0, 1]^3 with coordinates at exactly 1.0 (corner 0 at
    x0 = res, its upper corners clipped) and 0.0, on each axis and all."""
    rng = np.random.RandomState(seed)
    pts = rng.rand(n, 3).astype(np.float32)
    for a in range(3):
        pts[a, a] = 1.0
        pts[3 + a, a] = 0.0
    pts[6] = 1.0
    pts[7] = 0.0
    return jnp.asarray(pts)


def _stage_cells(tables, hcfg):
    """The cell-major table of the direct levels, staged from the level
    tables as `repack_fused_pack` stages it."""
    from repro.nerf.hash_encoding import cell_corner_rows

    res = hcfg.resolutions()
    return jnp.concatenate([
        jnp.take(t, jnp.asarray(cell_corner_rows(res[l])), axis=0)
        .reshape(t.shape[0], -1)
        for l, t in enumerate(tables) if hcfg.is_direct(l)])


# Direct-indexed levels of each benchmark field: resolutions 16, 22, 30,
# 42, 58 at T=2^19; 16, 22 at T=2^14; 16, 26, 45 and 16, 32 at T=2^17.
_DIRECT_LEVELS = {"ngp-paper-t19": 5, "ngp-paper-t14": 2,
                  "nerfacto-prop1": 3, "nerfacto-prop2": 2}


@pytest.mark.parametrize("F", [2, 4])
@pytest.mark.parametrize("config", ["ngp-paper-t19", "ngp-paper-t14",
                                    "nerfacto-prop1", "nerfacto-prop2"])
def test_hash_encode_bit_identical_at_bench_level_sizes(config, F):
    """Every level of each benchmark field at its real row count (4,913
    rows up to the full table): the one gather over the concatenated
    table; the cell-major encode of the direct-indexed levels, from real
    points on the boundary cells too; and the field's first layer fed the
    precomputed (L, P, 8) corner data a CullPlan passes — each
    bit-identical to gathering each level's table separately."""
    from repro.nerf import fast_render as fr
    from repro.nerf.hash_encoding import level_corner_data

    hcfg = _bench_hash_cfg(config, F)
    L = hcfg.n_levels
    rows = tuple(hcfg.level_entries(l) for l in range(L))
    direct = tuple(hcfg.is_direct(l) for l in range(L))
    assert min(rows) == 17 ** 3 and sum(direct) == _DIRECT_LEVELS[config]
    tables, idx, w = _level_inputs(rows, F=F, B=24, seed=F)
    cat = jnp.concatenate(tables)
    got = ops.hash_encode(idx, w, cat, rows)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(_per_level_take(tables, idx, w)))

    pts = _points_with_boundary(40, seed=F)
    per_level = [level_corner_data(pts, l, hcfg) for l in range(L)]
    idx = jnp.stack([i for i, _ in per_level])
    w = jnp.stack([w_ for _, w_ in per_level])
    cells = _stage_cells(tables, hcfg)
    want = _per_level_take(tables, idx, w)
    got = ops.hash_encode(idx, w, cat, rows, cells, direct)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    rng = np.random.RandomState(F)
    lin = {"w": jnp.asarray(rng.standard_normal((L * F, 16)), jnp.float32),
           "b": jnp.asarray(rng.standard_normal(16), jnp.float32)}
    staged = fr.repack_fused_pack(fr.FusedPack(
        layers={"sigma/0": lin},
        hash_tables={f"level_{l}": t for l, t in enumerate(tables)},
        modes=("float",), hash_cfg=hcfg))
    np.testing.assert_array_equal(np.asarray(staged.compute["cells_cat"]),
                                  np.asarray(cells))
    want = _per_level_take(tables, idx, w) @ lin["w"] + lin["b"]
    for corner_data in ((idx, w), None):
        got = fr._fused_first_linear(staged, pts, hcfg, "sigma/0", False,
                                     corner_data=corner_data)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _eqns(jaxpr):
    """Every equation of `jaxpr` and its sub-jaxprs, not descending into
    Pallas kernel bodies."""
    for e in jaxpr.eqns:
        yield e
        if e.primitive.name == "pallas_call":
            continue
        for v in e.params.values():
            for x in v if isinstance(v, (tuple, list)) else (v,):
                if hasattr(x, "eqns"):
                    yield from _eqns(x)
                elif hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                    yield from _eqns(x.jaxpr)


@pytest.mark.parametrize("use_pallas", [True, False])
def test_fused_field_query_is_one_gather_and_the_quant_kernel(use_pallas):
    """The hash lookup is one gather over each staged table: a field with
    no direct-indexed level has exactly one, over the concatenated table,
    8 indices a point a level; with direct levels the cell-major table
    takes one more, one index a point a direct level. The only Pallas
    kernel in the field query is the quantized matmul's."""
    L, B, T, F = 4, 29, 32, 2
    idx, w, _, cat, rows = _hash_inputs(L=L, B=B, T=T, F=F)
    cells = jnp.ones((2 * T, 8 * F), jnp.float32)
    wq = repack_tile_native(_packed(8, 16, 4, scale=0.03))
    act = {"sx": 0.05, "zx_f": 128.0, "qmax": 255.0, "off": 128,
           "zx": jnp.int32(0)}

    def gathers(*table_args):
        jaxpr = jax.make_jaxpr(
            lambda i, w_, c, *ce: ops.fused_field_query(
                i, w_, c, rows, wq, act, use_pallas=use_pallas,
                **dict(zip(("cells_cat", "direct"),
                           ce + ((True, True, False, False),)))
                if ce else {})
        )(idx, w, cat, *table_args).jaxpr
        eqns = list(_eqns(jaxpr))
        kernels = [e.params["jaxpr"].debug_info.func_name for e in eqns
                   if e.primitive.name == "pallas_call"]
        assert kernels == (["_qmm_packed_kernel"] if use_pallas else [])
        return sorted((e.invars[0].aval.shape, e.invars[1].aval.shape[0])
                      for e in eqns if e.primitive.name == "gather")

    assert gathers() == [(cat.shape, L * B * 8)]
    assert gathers(cells) == sorted([(cells.shape, 2 * B),
                                     (cat.shape, 2 * B * 8)])


def _single_gather_encode(corner_idx, corner_w, table_cat, level_rows):
    """The encode of a field with no direct-indexed level, as it was
    before cell-major tables existed: one gather over the concatenated
    table, the trilinear sum."""
    L, B, C = corner_idx.shape
    rows = [int(r) for r in level_rows]
    offs = np.concatenate([[0], np.cumsum(rows)[:-1]]).astype(np.int32)
    flat = (corner_idx + jnp.asarray(offs)[:, None, None]).reshape(-1)
    vals = ref.hash_gather_ref(flat, table_cat).reshape(L, B, C, -1)
    feats = jnp.sum(vals * corner_w[..., None], axis=2)
    return jnp.moveaxis(feats, 0, 1).reshape(B, -1)


def test_field_without_direct_levels_lowers_to_the_single_gather():
    """A field with no direct-indexed level (and an unstaged pack) runs
    exactly the single-gather program: the lowered text is the same."""
    from repro.nerf import fast_render as fr
    from repro.nerf.hash_encoding import HashEncodingConfig

    hcfg = HashEncodingConfig(n_levels=3, log2_table_size=6,
                              base_resolution=4, max_resolution=16)
    assert not any(hcfg.is_direct(l) for l in range(3))
    rows = tuple(hcfg.level_entries(l) for l in range(3))
    idx, w, _, cat, _ = _hash_inputs(L=3, B=37, T=rows[0], F=2)
    text = lambda f: jax.jit(f).lower(idx, w, cat).as_text()  # noqa: E731
    want = text(lambda i, w_, c: _single_gather_encode(i, w_, c, rows))
    for direct in ((), (False,) * 3):
        assert text(lambda i, w_, c: ops.hash_encode(
            i, w_, c, rows, None, direct)) == want

    tables = {f"level_{l}": cat[sum(rows[:l]):sum(rows[:l + 1])]
              for l in range(3)}
    pack = fr.repack_fused_pack(fr.FusedPack(
        layers={"a": {"w": jnp.ones((6, 4)), "b": jnp.zeros(4)}},
        hash_tables=tables, modes=("float",), hash_cfg=hcfg))
    assert "cells_cat" not in pack.compute
    assert fr.hash_lookups(pack) == (24, 0)


# ---------------------------------------------------------------------------
# Autotune lookup: measured-table selection, fixed_bk pinning, fallback
# ---------------------------------------------------------------------------
_TABLE = {"version": 1, "entries": {"test:backend": [
    {"m": 6656, "k": 16, "n": 16, "bits": 8,
     "bm": 512, "bn": 128, "bk": 128, "ms": 1.0, "default_ms": 2.0},
    {"m": 64, "k": 256, "n": 64, "bits": 2,
     "bm": 128, "bn": 128, "bk": 256, "ms": 1.0, "default_ms": 2.0},
]}}


def test_lookup_block_nearest_entry():
    got = autotune.lookup_block(6000, 16, 16, 8, table=_TABLE,
                                key="test:backend")
    assert got == (512, 128, 128)
    got = autotune.lookup_block(60, 300, 60, 2, table=_TABLE,
                                key="test:backend")
    assert got == (128, 128, 256)


def test_lookup_block_fixed_bk_filters_and_falls_back():
    got = autotune.lookup_block(64, 256, 64, 2, fixed_bk=128, table=_TABLE,
                                key="test:backend")
    assert got == (512, 128, 128)  # only the bk=128 entry survives
    got = autotune.lookup_block(64, 256, 64, 2, fixed_bk=64, table=_TABLE,
                                key="test:backend")
    assert got == (128, 128, 64)  # nothing measured at bk=64: default, pinned


def test_lookup_block_empty_table_default():
    assert autotune.lookup_block(10, 10, 10, table={"entries": {}},
                                 key="x") == autotune.DEFAULT_BLOCK


def test_committed_table_entries_well_formed():
    """The committed autotune_table.json (if present) parses and every
    entry carries the fields lookup/never-loses need — matmul entries
    MXU-aligned, ray-march entries tagged with their own shape keys."""
    table = autotune.load_table()
    for key, entries in table.get("entries", {}).items():
        for e in entries:
            if e.get("kernel") == "ray_march":
                for f in ("r", "s", "g", "br", "bs", "bt", "ms",
                          "default_ms"):
                    assert f in e, (key, e)
                continue
            for f in ("m", "k", "n", "bits", "bm", "bn", "bk", "ms",
                      "default_ms"):
                assert f in e, (key, e)
            assert e["bm"] % 128 == 0 and e["bn"] % 128 == 0
            assert e["bk"] % 128 == 0


# ---------------------------------------------------------------------------
# Occupancy ray-march: the ad-hoc serve fast path. The kernel's {0,1}
# active mask must be bit-identical to `ref.ray_march_ref` for every
# block choice, with and without early termination, including degenerate
# rays (zero direction, origins outside the box) and ragged R/S/G that
# force padding in every axis.
# ---------------------------------------------------------------------------
def _march_operands(r=70, s=9, g=16, seed=3):
    rng = np.random.RandomState(seed)
    occ = jnp.asarray((rng.rand(g, g, g) < 0.3).astype(np.float32))
    ro = jnp.asarray(rng.randn(r, 3).astype(np.float32) * 0.4)
    rd = rng.randn(r, 3).astype(np.float32)
    rd = jnp.asarray(rd / np.linalg.norm(rd, axis=1, keepdims=True))
    t = jnp.asarray(np.linspace(0.03, 2.2, s, dtype=np.float32))
    return occ, ro, rd, t


@pytest.mark.parametrize("br,bs,bt", [(16, 4, 256), (32, 8, 128),
                                      (128, 8, 512)])
def test_ray_march_parity_block_invariance(br, bs, bt):
    occ, ro, rd, t = _march_operands()
    want = ref.ray_march_ref(occ, ro, rd, t)
    got = ops.ray_march(occ, ro, rd, t, use_pallas=True,
                        br=br, bs=bs, bt=bt)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("early_stop", [True, False])
def test_ray_march_early_stop_invariance(early_stop):
    """Early termination skips only provably-outside sample chunks, so
    toggling it never changes the mask."""
    occ, ro, rd, t = _march_operands()
    want = ref.ray_march_ref(occ, ro, rd, t)
    got = ops.ray_march(occ, ro, rd, t, use_pallas=True,
                        br=16, bs=4, bt=256, early_stop=early_stop)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ray_march_degenerate_rays_exact_zero_rows():
    """Zero-direction rays parked far outside the box and rays that
    never enter the box must produce exact all-zero mask rows."""
    g = 8
    rng = np.random.RandomState(5)
    occ = jnp.ones((g, g, g), jnp.float32)
    ro = np.zeros((6, 3), np.float32)
    rd = np.zeros((6, 3), np.float32)
    ro[0] = (10.0, 10.0, 10.0)          # parked outside, zero direction
    ro[1] = (0.0, 5.0, 0.0)             # above the box ...
    rd[1] = (1.0, 0.0, 0.0)             # ... marching parallel to it
    ro[2] = (0.0, 0.0, 0.0)             # inside, zero direction: stays in
    ro[3:] = rng.randn(3, 3) * 0.3
    rd[3:] = rng.randn(3, 3)
    t = jnp.asarray(np.linspace(0.05, 3.0, 7, dtype=np.float32))
    want = np.asarray(ref.ray_march_ref(occ, jnp.asarray(ro),
                                        jnp.asarray(rd), t))
    got = np.asarray(ops.ray_march(occ, jnp.asarray(ro), jnp.asarray(rd),
                                   t, use_pallas=True,
                                   br=16, bs=4, bt=64))
    np.testing.assert_array_equal(got, want)
    assert not want[0].any() and not want[1].any()
    assert want[2].all()  # origin cell is occupied at every t


@pytest.mark.parametrize("r,s,g", [(1, 1, 4), (70, 9, 8), (130, 17, 16)])
def test_ray_march_ragged_shapes(r, s, g):
    occ, ro, rd, t = _march_operands(r=r, s=s, g=g, seed=7)
    want = ref.ray_march_ref(occ, ro, rd, t)
    got = ops.ray_march(occ, ro, rd, t, use_pallas=True,
                        br=16, bs=4, bt=64)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ray_march_autotune_dispatch_matches_ref():
    """ops.ray_march with no explicit blocks pulls (br, bs, bt) from the
    autotune table — whatever it picks, the mask is still exact."""
    occ, ro, rd, t = _march_operands(r=40, s=8, g=8, seed=11)
    want = ref.ray_march_ref(occ, ro, rd, t)
    got = ops.ray_march(occ, ro, rd, t, use_pallas=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Autotune table: ray-march entries share the per-backend list with the
# matmul entries, tagged `"kernel": "ray_march"`; each lookup must see
# only its own kind.
# ---------------------------------------------------------------------------
_RM_TABLE = {"entries": {"test:backend": [
    {"m": 4096, "k": 16, "n": 16, "bits": 8,
     "bm": 512, "bn": 128, "bk": 128, "ms": 1.0, "default_ms": 2.0},
    {"kernel": "ray_march", "r": 512, "s": 16, "g": 32,
     "br": 64, "bs": 4, "bt": 256, "ms": 1.0, "default_ms": 2.0},
    {"kernel": "ray_march", "r": 4096, "s": 32, "g": 128,
     "br": 256, "bs": 16, "bt": 1024, "ms": 1.0, "default_ms": 2.0},
]}}


def test_lookup_ray_march_nearest_and_default():
    got = autotune.lookup_ray_march(600, 16, 32, table=_RM_TABLE,
                                    key="test:backend")
    assert got == (64, 4, 256)
    got = autotune.lookup_ray_march(5000, 24, 128, table=_RM_TABLE,
                                    key="test:backend")
    assert got == (256, 16, 1024)
    assert autotune.lookup_ray_march(
        100, 8, 16, table={"entries": {}}, key="x"
    ) == autotune.RAY_MARCH_DEFAULT


def test_lookup_kinds_do_not_cross_contaminate():
    """lookup_block never returns a ray-march entry and vice versa, even
    when the other kind is the nearest row in the shared list."""
    got = autotune.lookup_block(4096, 16, 16, 8, table=_RM_TABLE,
                                key="test:backend")
    assert got == (512, 128, 128)
    only_march = {"entries": {"test:backend": [
        e for e in _RM_TABLE["entries"]["test:backend"]
        if e.get("kernel") == "ray_march"
    ]}}
    assert autotune.lookup_block(
        4096, 16, 16, 8, table=only_march, key="test:backend"
    ) == autotune.DEFAULT_BLOCK
    only_mm = {"entries": {"test:backend": [
        e for e in _RM_TABLE["entries"]["test:backend"] if "kernel" not in e
    ]}}
    assert autotune.lookup_ray_march(
        512, 16, 32, table=only_mm, key="test:backend"
    ) == autotune.RAY_MARCH_DEFAULT
