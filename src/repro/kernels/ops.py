"""Jit'd public wrappers for the Pallas kernels.

On TPU the kernels compile through Mosaic, and a kernel the compiler
refuses raises: there is no fallback to the ``kernels/ref.py`` oracles.
Off the TPU they run in interpret mode (the kernel body executed in
python) so the CPU tests exercise the same kernels.  The model code calls
these wrappers, never pallas_call directly."""
from __future__ import annotations

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import param_variance as _pv
from repro.kernels import qsgd_quant as _qq


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=_interpret())


def qsgd_quantize(x, u, *, bits: int = 8):
    return _qq.quantize(x, u, bits=bits, interpret=_interpret())


def qsgd_dequantize(levels, norm, *, bits: int = 8):
    return _qq.dequantize(levels, norm, bits=bits, interpret=_interpret())


def param_mean_and_sqdev(w):
    return _pv.mean_and_sqdev(w, interpret=_interpret())
