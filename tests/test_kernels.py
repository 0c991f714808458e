"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.alpha_composite import alpha_composite
from repro.kernels.decode_attention_kernel import decode_attention
from repro.kernels.quant_matmul import quant_matmul


@pytest.mark.parametrize("m,k,n", [(8, 16, 8), (70, 200, 90), (128, 128, 128),
                                   (129, 257, 65)])
@pytest.mark.parametrize("zx", [0, 17, 128])
def test_quant_matmul_exact(m, k, n, zx):
    key = jax.random.PRNGKey(m * 1000 + k + n)
    k1, k2 = jax.random.split(key)
    x = jax.random.randint(k1, (m, k), 0, 256, jnp.int32).astype(jnp.int8)
    w = jax.random.randint(k2, (k, n), -127, 128, jnp.int32).astype(jnp.int8)
    got = quant_matmul(x, w, 0.037, 0.011, zx, bm=32, bn=32, bk=64)
    want = ref.quant_matmul_ref(x, w, 0.037, 0.011, zx)
    # integer accumulation is EXACT; the only float ops are two scalings
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_quant_matmul_bits_range():
    """Codes from any b in [1, 8] stay exact (bit-serial numerics claim)."""
    key = jax.random.PRNGKey(0)
    for bits in (1, 2, 4, 8):
        hi = 2 ** (bits - 1) - 1
        x = jax.random.randint(key, (33, 47), 0, 2 ** bits, jnp.int32).astype(jnp.int8)
        w = jax.random.randint(key, (47, 21), -hi, hi + 1, jnp.int32).astype(jnp.int8)
        got = quant_matmul(x, w, 1.0, 1.0, 2 ** (bits - 1), bm=16, bn=16, bk=16)
        want = ref.quant_matmul_ref(x, w, 1.0, 1.0, 2 ** (bits - 1))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("r,s", [(4, 8), (50, 70), (128, 128), (130, 33)])
def test_alpha_composite(r, s):
    key = jax.random.PRNGKey(r * 100 + s)
    k1, k2 = jax.random.split(key)
    sigma = jax.random.uniform(k1, (r, s)) * 4.0
    rgb = jax.random.uniform(k2, (r, s, 3))
    delta = jnp.full((r, s), 0.03)
    c1, a1 = alpha_composite(sigma, rgb, delta, br=16, bs=32)
    c2, a2 = ref.alpha_composite_ref(sigma, rgb, delta)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a2), atol=1e-5)
    assert float(jnp.max(a1)) <= 1.0 + 1e-5  # weights sum to <= 1


def test_alpha_composite_opaque_wall():
    """A very dense first sample should absorb everything."""
    sigma = jnp.zeros((4, 16)).at[:, 0].set(1e4)
    rgb = jnp.ones((4, 16, 3)) * jnp.arange(16)[None, :, None] / 16.0
    delta = jnp.full((4, 16), 1.0)
    c, a = alpha_composite(sigma, rgb, delta, br=4, bs=8)
    np.testing.assert_allclose(np.asarray(a), 1.0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(c), 0.0, atol=1e-5)  # rgb_0 = 0


@pytest.mark.parametrize("b,hkv,g,s,hd", [(1, 1, 1, 32, 16), (2, 4, 3, 100, 16),
                                          (2, 2, 8, 257, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(b, hkv, g, s, hd, dtype):
    key = jax.random.PRNGKey(b + s)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, hkv, g, hd), dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, hd), dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, hd), dtype)
    length = jnp.int32(s - 5)
    got = decode_attention(q, k, v, length, bs=64)
    want = ref.decode_attention_ref(q, k, v, length)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


def test_decode_attention_masks_future():
    """Entries beyond `length` must not affect the output."""
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (1, 2, 2, 16))
    k = jax.random.normal(key, (1, 2, 64, 16))
    v = jax.random.normal(key, (1, 2, 64, 16))
    base = decode_attention(q, k, v, jnp.int32(20), bs=16)
    k2 = k.at[:, :, 20:].set(99.0)
    v2 = v.at[:, :, 20:].set(-99.0)
    poisoned = decode_attention(q, k2, v2, jnp.int32(20), bs=16)
    np.testing.assert_allclose(np.asarray(base), np.asarray(poisoned), atol=1e-6)


@pytest.mark.parametrize("b,hkv,g,s,hd", [(1, 1, 1, 64, 16), (2, 2, 4, 96, 32),
                                          (1, 4, 2, 130, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_causal(b, hkv, g, s, hd, dtype):
    from repro.kernels.flash_attention_kernel import flash_attention
    key = jax.random.PRNGKey(s + hd)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, hkv, s, g, hd), dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, hd), dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, hd), dtype)
    got = flash_attention(q, k, v, causal=True, bq=32, bk=32)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


def test_flash_attention_noncausal():
    from repro.kernels.flash_attention_kernel import flash_attention
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, 2, 64, 2, 16))
    k = jax.random.normal(ks[1], (1, 2, 64, 16))
    v = jax.random.normal(ks[2], (1, 2, 64, 16))
    got = flash_attention(q, k, v, causal=False, bq=32, bk=32)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_flash_attention_matches_model_attention():
    """Cross-check the kernel against the model's chunked attention path."""
    from repro.kernels.flash_attention_kernel import flash_attention
    from repro.models.attention import _sdpa_chunked
    key = jax.random.PRNGKey(7)
    B, S, Hkv, G, hd = 2, 64, 2, 3, 16
    ks = jax.random.split(key, 3)
    q5 = jax.random.normal(ks[0], (B, Hkv, S, G, hd))
    k4 = jax.random.normal(ks[1], (B, Hkv, S, hd))
    v4 = jax.random.normal(ks[2], (B, Hkv, S, hd))
    got = flash_attention(q5, k4, v4, causal=True, bq=16, bk=16)
    # reshape to the model layout (B, S, H, hd), H grouped by kv head
    qm = jnp.moveaxis(q5, 1, 2).reshape(B, S, Hkv * G, hd)
    km = jnp.moveaxis(k4, 1, 2)
    vm = jnp.moveaxis(v4, 1, 2)
    want = _sdpa_chunked(qm, km, vm, causal=True, chunk=32)
    want5 = jnp.moveaxis(want.reshape(B, S, Hkv, G, hd), 2, 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want5, np.float32),
                               rtol=2e-4, atol=2e-4)
