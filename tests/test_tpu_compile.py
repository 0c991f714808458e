"""Compile rehearsal: the serve path's Pallas kernels (and the hash
encode's XLA gather) at the paper's Instant-NGP widths, for a described
(not attached) TPU v5e.

Interpret mode cannot see what the chip's compiler refuses: blocks not
aligned to the (8, 128) tiling, operand types the MXU does not take,
primitives Mosaic cannot lower, VMEM overruns. Each test lowers and
compiles one kernel for one chip of a described `v5e:2x2` topology and
checks that the Mosaic kernel is in the compiled program
(`tpu_custom_call`). Nothing runs.

The topology is described inside a module-scoped fixture, never at
import, so that every test worker collects the same tests and only the
worker given this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs.ngp import nerfacto, paper
from repro.kernels.alpha_composite import alpha_composite
from repro.kernels.autotune import RAY_MARCH_DEFAULT
from repro.kernels.ops import hash_encode
from repro.kernels.quant_matmul import quant_matmul_packed
from repro.kernels.ray_march import ray_march
from repro.nerf.nerfacto import linear_dims as nerfacto_linear_dims
from repro.nerf.ngp import _linear_dims

POINTS = 4096  # field-query batch: culled samples per serve chunk


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory placed on one chip of the topology."""
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip
    )


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("layout", ["planar", "tile:128"])
@pytest.mark.parametrize("bits", [4, 6, 8])
def test_quant_matmul_packed_compiles_at_paper_mlp_shapes(spec, bits, layout):
    _compile_packed_mlp(spec, _linear_dims(paper()), bits, layout)


@pytest.mark.parametrize("bits", [4, 8])
def test_quant_matmul_packed_compiles_at_nerfacto_shapes(spec, bits):
    """Nerfacto's linears beyond Instant-NGP's: color/0 with K = 63 (SH,
    geometry and appearance), the proposal fields' 10 -> 16 -> 1."""
    dims = nerfacto_linear_dims(nerfacto())
    _compile_packed_mlp(spec, {n: dims[n] for n in
                               ("color/0", "prop1/0", "prop1/1")},
                        bits, "tile:128")


def _compile_packed_mlp(spec, dims, bits, layout):
    bk = 128
    args, shapes = [], []
    for k, n in dims.values():
        kp = -(-k // bk) * bk
        rows = (kp // bk) * (bk // 32) * bits if layout != "planar" \
            else -(-k // 32) * bits
        shapes.append((k, n))
        args += [spec((POINTS, k), jnp.int8), spec((rows, n), jnp.int32)]
    scalars = [spec((), jnp.int32), spec((), jnp.float32),
               spec((), jnp.float32), spec((), jnp.int32)]

    def mlp(*a):
        off, sx, sw, zx = a[-4:]
        return [
            quant_matmul_packed(a[2 * i], a[2 * i + 1], off, sx, sw, zx,
                                bits=bits, bm=128, bn=128, bk=bk,
                                layout=layout, interpret=False)
            for i in range(len(shapes))
        ]

    text = _compiled_text(mlp, *args, *scalars)
    assert text.count("tpu_custom_call") >= len(shapes)


def test_hash_encode_compiles_to_xla_gather_at_paper_widths(spec):
    """All 16 levels of the T=2^19 field, 4,096 points: one XLA gather
    over the concatenated table and no Mosaic kernel."""
    cfg = paper().hash
    L = cfg.n_levels
    rows = tuple(cfg.level_entries(l) for l in range(L))
    text = _compiled_text(
        lambda i, w, t: hash_encode(i, w, t, rows),
        spec((L, POINTS, 8), jnp.int32), spec((L, POINTS, 8), jnp.float32),
        spec((sum(rows), cfg.n_features), jnp.float32),
    )
    assert " gather(" in text
    assert "tpu_custom_call" not in text


@pytest.mark.parametrize("field", ["paper", "proposal1", "proposal2"])
def test_cell_major_hash_encode_compiles_at_published_widths(spec, field):
    """The encode with the direct-indexed levels' cell-major table (levels
    0-4 of the T=2^19 field, 0-2 and 0-1 of Nerfacto's proposal fields),
    4,096 points: one XLA gather a staged table, no Mosaic kernel."""
    cfg = (paper().hash if field == "paper"
           else nerfacto().proposals[int(field[-1]) - 1])
    L, F = cfg.n_levels, cfg.n_features
    rows = tuple(cfg.level_entries(l) for l in range(L))
    direct = tuple(cfg.is_direct(l) for l in range(L))
    cell_rows = sum(r for r, d in zip(rows, direct) if d)
    text = _compiled_text(
        lambda i, w, t, c: hash_encode(i, w, t, rows, c, direct),
        spec((L, POINTS, 8), jnp.int32), spec((L, POINTS, 8), jnp.float32),
        spec((sum(rows), F), jnp.float32), spec((cell_rows, 8 * F), jnp.float32),
    )
    assert text.count(" gather(") == 2
    assert "tpu_custom_call" not in text


def test_ray_march_compiles_at_g32(spec):
    br, bs, bt = RAY_MARCH_DEFAULT
    text = _compiled_text(
        lambda o, ro, rd, t: ray_march(o, ro, rd, t, br=br, bs=bs, bt=bt,
                                       interpret=False),
        spec((32, 32, 32), jnp.float32), spec((512, 3), jnp.float32),
        spec((512, 3), jnp.float32), spec((64,), jnp.float32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("early_stop", [False, True])
def test_alpha_composite_compiles(spec, early_stop):
    _compile_composite(spec, 4096, 64, early_stop)


def test_alpha_composite_compiles_at_nerfacto_slot(spec):
    """One Nerfacto slot: 512 rays of 48 shaded samples."""
    _compile_composite(spec, 512, 48, True)


def _compile_composite(spec, r, s, early_stop):
    text = _compiled_text(
        lambda sg, rgb, d: alpha_composite(sg, rgb, d, interpret=False,
                                           early_stop=early_stop),
        spec((r, s), jnp.float32), spec((r, s, 3), jnp.float32),
        spec((r, s), jnp.float32),
    )
    assert "tpu_custom_call" in text
