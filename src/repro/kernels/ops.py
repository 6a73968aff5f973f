"""Jit'd public wrappers for the Pallas kernels.

On TPU the kernels compile through Mosaic, and a kernel the compiler
refuses raises: there is no fallback to the ``kernels/ref.py`` oracles.
Off the TPU they run in interpret mode (the kernel body executed in
python) so the CPU tests exercise the same kernels; ``flash_attention``
runs there only inside ``pltpu.force_tpu_interpret_mode()``, which its
tests enter (the model takes it on a TPU alone).  The model code calls
these wrappers, never pallas_call directly."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import flash_attention as _fa

from repro.kernels import param_variance as _pv
from repro.kernels import qsgd_quant as _qq


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def flash_block(seq_len: int, head_dim: int) -> int:
    """The flash kernels' tile along the sequence: the largest of 512, 256
    and 128 that divides ``seq_len`` (their tiles are whole multiples of
    128 lanes), or 0 where the kernels cannot take the shape: no such tile,
    or a head wider than 128 that is not a multiple of it."""
    if head_dim > 128 and head_dim % 128:
        return 0
    return next((b for b in (512, 256, 128) if seq_len % b == 0), 0)


def flash_attention(q, k, v):
    """Causal self-attention, differentiable, with no (S, S) tensor in HBM.

    q: (B, S, H, d); k, v: (B, S, K, d) with H % K == 0 -> (B, S, H, d).
    JAX's TPU flash kernels (forward, dK/dV and dQ) over tiles of
    ``flash_block(S, d)``: QKᵀ on the inputs' dtype with f32 accumulation, the
    softmax statistics in f32, P cast to V's dtype for P·V.  The kernels
    want as many K/V heads as query heads, so grouped K/V heads are
    repeated to H first."""
    _, S, H, d = q.shape
    blk = flash_block(S, d)
    sizes = _fa.BlockSizes(
        block_q=blk, block_k_major=blk, block_k=blk, block_b=1,
        block_q_major_dkv=blk, block_k_major_dkv=blk, block_k_dkv=blk,
        block_q_dkv=blk, block_k_major_dq=blk, block_k_dq=blk, block_q_dq=blk)

    def heads_major(x):
        return jnp.repeat(x, H // x.shape[2], axis=2).transpose(0, 2, 1, 3)

    with jax.named_scope("flash_attention"):
        out = _fa.flash_attention(
            heads_major(q), heads_major(k), heads_major(v), causal=True,
            sm_scale=1.0 / math.sqrt(d), block_sizes=sizes)
    return out.transpose(0, 2, 1, 3)


def qsgd_quantize(x, u, *, bits: int = 8):
    return _qq.quantize(x, u, bits=bits, interpret=_interpret())


def qsgd_dequantize(levels, norm, *, bits: int = 8):
    return _qq.dequantize(levels, norm, bits=bits, interpret=_interpret())


def param_mean_and_sqdev(w):
    return _pv.mean_and_sqdev(w, interpret=_interpret())
