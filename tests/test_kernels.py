"""Per-kernel correctness: sweep shapes/dtypes, assert_allclose vs ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.param_variance import mean_and_sqdev
from repro.kernels.qsgd_quant import dequantize, quantize

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("B,S,H,K,d", [
    (1, 128, 4, 4, 64),
    (2, 256, 4, 2, 32),
    (1, 384, 6, 3, 128),
    (2, 128, 8, 1, 64),       # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [0, 64])
def test_flash_attention(B, S, H, K, d, dtype, window):
    ks = jax.random.split(jax.random.fold_in(KEY, S * H + window), 3)
    q = jax.random.normal(ks[0], (B, S, H, d), dtype)
    k = jax.random.normal(ks[1], (B, S, K, d), dtype)
    v = jax.random.normal(ks[2], (B, S, K, d), dtype)
    out = flash_attention(q, k, v, causal=True, window=window, interpret=True)
    want = ref.attention_ref(q, k, v, causal=True, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("block_q,block_k", [(64, 64), (128, 64), (64, 128)])
def test_flash_attention_blocks(block_q, block_k):
    q = jax.random.normal(KEY, (1, 256, 4, 64))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 256, 2, 64))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (1, 256, 2, 64))
    out = flash_attention(q, k, v, causal=True, block_q=block_q,
                          block_k=block_k, interpret=True)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("n", [7, 1000, 1024, 4097])
@pytest.mark.parametrize("bits", [4, 8])
def test_qsgd_quantize(n, bits):
    x = jax.random.normal(jax.random.fold_in(KEY, n), (n,)) * 3.0
    u = jax.random.uniform(jax.random.fold_in(KEY, n + 1), (n,))
    lv, nm = quantize(x, u, bits=bits, interpret=True)
    lr, nr = ref.quantize_ref(x, u, bits=bits)
    np.testing.assert_array_equal(np.asarray(lv), np.asarray(lr))
    np.testing.assert_allclose(nm, nr, rtol=1e-6)
    dq = dequantize(lv, nm, bits=bits, interpret=True)
    np.testing.assert_allclose(dq, ref.dequantize_ref(lr, nr, bits=bits),
                               rtol=1e-6)
    # quantization error bound: |q - x| <= norm / s elementwise
    s = (1 << (bits - 1)) - 1
    assert float(jnp.max(jnp.abs(dq - x))) <= float(nm) / s + 1e-6


@pytest.mark.parametrize("shape", [(64, 256), (2, 32, 384), (3, 40, 1030)])
def test_qsgd_quantize_views(shape):
    """Lane-dense views (last dim a multiple of 128, rows of 32) and a
    padded flat view round like the oracle."""
    k = jax.random.fold_in(KEY, sum(shape))
    x = jax.random.normal(k, shape)
    u = jax.random.uniform(jax.random.fold_in(k, 1), shape)
    lv, nm = quantize(x, u, interpret=True)
    lr, nr = ref.quantize_ref(x, u)
    np.testing.assert_array_equal(np.asarray(lv), np.asarray(lr))
    np.testing.assert_allclose(nm, nr, rtol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(dequantize(lv, nm, interpret=True)),
        np.asarray(ref.dequantize_ref(lv, nm)))


def test_qsgd_multidim_and_zero():
    x = jax.random.normal(KEY, (33, 17))
    u = jax.random.uniform(jax.random.fold_in(KEY, 3), (33, 17))
    lv, nm = quantize(x, u, interpret=True)
    assert lv.shape == x.shape
    z = jnp.zeros((128,))
    lvz, nmz = quantize(z, jnp.zeros((128,)), interpret=True)
    assert float(nmz) == 0.0
    assert int(jnp.abs(lvz).max()) == 0


@pytest.mark.parametrize("R,shape", [
    (2, (100,)), (8, (33, 7)), (16, (1024,)), (4, (5, 4, 3)),
    (2, (40, 1030)),      # flattened: 41200 elements pad to a whole tile
    (16, (64, 2048)),     # lane-dense view, a (2, 2) grid of blocks
])
def test_param_variance(R, shape):
    w = jax.random.normal(jax.random.fold_in(KEY, R), (R,) + shape)
    m, sq = mean_and_sqdev(w, interpret=True)
    mr, sr = ref.mean_and_sqdev_ref(w)
    np.testing.assert_allclose(m, mr, atol=1e-6)
    np.testing.assert_allclose(sq, sr, rtol=1e-5, atol=1e-6)


def test_param_variance_identical_replicas():
    w = jnp.broadcast_to(jax.random.normal(KEY, (50,)), (8, 50))
    _, sq = mean_and_sqdev(w, interpret=True)
    assert float(sq) < 1e-10


def test_ops_wrappers_run_on_cpu():
    q = jax.random.normal(KEY, (1, 128, 2, 32))
    out = ops.flash_attention(q, q, q)
    assert out.shape == q.shape
    m, sq = ops.param_mean_and_sqdev(jnp.ones((4, 64)))
    assert float(sq) == 0.0
