"""Per-kernel correctness: sweep shapes/dtypes, assert_allclose vs ref.py."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ops, ref
from repro.kernels.param_variance import mean_and_sqdev
from repro.kernels.qsgd_quant import dequantize, quantize
from repro.models import layers

KEY = jax.random.PRNGKey(0)


def _attention_inputs(B, S, H, K, d, dtype):
    ks = jax.random.split(jax.random.fold_in(KEY, S * H * K + d), 4)
    q, k, v = (jax.random.normal(kk, (B, S, h, d), dtype)
               for kk, h in zip(ks, (H, K, K)))
    return q, k, v, jax.random.normal(ks[3], (B, S, H, d), dtype)


@functools.partial(jax.jit, static_argnums=0)
def _with_vjp(fn, q, k, v, g):
    """fn's output, then its dq, dk and dv for the cotangent g."""
    out, vjp = jax.vjp(fn, q, k, v)
    return (out,) + vjp(g.astype(out.dtype))


def _flash_with_vjp(*args):
    """The flash kernels' output and VJP on the CPU: traced and run in TPU
    interpret mode, which the model never enters (it takes the kernels on a
    TPU alone)."""
    with pltpu.force_tpu_interpret_mode():
        return _with_vjp(ops.flash_attention, *args)


def _oracle(q, k, v):
    return ref.attention_ref(*(x.astype(jnp.float32) for x in (q, k, v)))


def _sdpa_causal(q, k, v):
    B, S = q.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return layers._sdpa(q, k, v, layers._causal_mask(pos, pos, 0))


def _assert_close(got, want, dtype):
    """Output, dq, dk and dv, each by the norm of its gap over the norm of
    the reference.  bf16 bounds as measured in interpret mode: the output
    within 2.3e-3 (one bf16 ulp is 2**-8, 3.9e-3), the gradients within
    3.6e-3."""
    tols = (1e-5,) * 4 if dtype == jnp.float32 else (4e-3,) + (6e-3,) * 3
    for g, w, tol in zip(got, want, tols):
        assert g.dtype == dtype and g.shape == w.shape
        g, w = (np.asarray(x, np.float32) for x in (g, w))
        assert np.linalg.norm(g - w) <= tol * np.linalg.norm(w)


@pytest.mark.parametrize("S", [128, 256])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("H,K", [(2, 2), (4, 2)])      # MHA; GQA repeat
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(S, d, H, K, dtype):
    """The flash kernels' forward and VJP against the f32 oracle."""
    args = _attention_inputs(1, S, H, K, d, dtype)
    _assert_close(_flash_with_vjp(*args),
                  _with_vjp(_oracle, *args), dtype)


@pytest.mark.parametrize("B,S,H,K,d", [(2, 256, 2, 2, 128), (1, 128, 4, 1, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_sdpa(B, S, H, K, d, dtype):
    """The kernels against the model's own attention core, which the TPU
    path replaces, under the causal mask of the model's positions."""
    args = _attention_inputs(B, S, H, K, d, dtype)
    _assert_close(_flash_with_vjp(*args),
                  _with_vjp(_sdpa_causal, *args), dtype)


@pytest.mark.parametrize("S,d,block", [
    (2048, 128, 512), (384, 64, 128), (256, 128, 256), (8192, 256, 512),
    (1, 128, 0),          # decode
    (100, 64, 0),         # no whole tile
    (2048, 192, 0),       # a head the kernels cannot split into lanes
])
def test_flash_block(S, d, block):
    assert ops.flash_block(S, d) == block


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
    with pltpu.force_tpu_interpret_mode():
        out = ops.flash_attention(q, q, q)
    assert out.shape == q.shape
    m, sq = ops.param_mean_and_sqdev(jnp.ones((4, 64)))
    assert float(sq) == 0.0
