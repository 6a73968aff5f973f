"""QSGD stochastic quantization Pallas kernels.

The QSGD baseline's hot spot is a bandwidth-bound elementwise pass over
every gradient buffer (quantize before transmit, dequantize after).  The
kernels stream (rows, lanes) VMEM tiles of the buffer's lane-dense view
(``kernels/tiling.py``), with rows a multiple of 32 so the int8 levels tile
as well as the f32 inputs.  The tensor L2 norm comes from a first reduction
kernel that accumulates a lane-dense (rows, lanes) partial, reduced after
the call; the per-tensor scale then reaches the elementwise kernels as an
SMEM scalar.  The uniform randoms for stochastic rounding are supplied as
an input stream so the kernel is bit-exactly testable against the jnp
oracle (``kernels/ref.py``) at the same norm."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import lane_view

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _block(v):
    return pl.BlockSpec((v.rows, v.lanes), lambda i, j: (i, j))


def _sqsum_kernel(x_ref, o_ref):
    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)
    o_ref[...] += x * x


def _quant_kernel(scale_ref, x_ref, u_ref, lv_ref):
    x = x_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    scaled = jnp.abs(x) * scale_ref[0, 0]
    floor = jnp.floor(scaled)
    mag = floor + (u < (scaled - floor)).astype(jnp.float32)
    lv_ref[...] = (jnp.sign(x) * mag).astype(jnp.int8)


def _dequant_kernel(step_ref, lv_ref, o_ref):
    o_ref[...] = (lv_ref[...].astype(jnp.float32)
                  * step_ref[0, 0]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sqnorm(x: jnp.ndarray, *, interpret: bool = False) -> jnp.ndarray:
    v = lane_view(x)
    out = pl.pallas_call(
        _sqsum_kernel,
        grid=v.grid,
        in_specs=[_block(v)],
        out_specs=pl.BlockSpec((v.rows, v.lanes), lambda i, j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((v.rows, v.lanes), jnp.float32),
        interpret=interpret,
    )(v.x)
    return jnp.sum(out)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def quantize(x: jnp.ndarray, u: jnp.ndarray, *, bits: int = 8,
             interpret: bool = False):
    """x: any-shape tensor; u: uniforms of the same shape.  Returns
    (levels int8 of x.shape, norm scalar f32)."""
    s = (1 << (bits - 1)) - 1
    norm = jnp.sqrt(sqnorm(x, interpret=interpret))
    scale = jnp.where(norm > 0, s / norm, 0.0).reshape(1, 1)
    v, vu = lane_view(x), lane_view(u)
    lv = pl.pallas_call(
        _quant_kernel,
        grid=v.grid,
        in_specs=[_SMEM, _block(v), _block(v)],
        out_specs=_block(v),
        out_shape=jax.ShapeDtypeStruct(v.x.shape, jnp.int8),
        interpret=interpret,
    )(scale, v.x, vu.x)
    return v.restore(lv), norm


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def dequantize(levels: jnp.ndarray, norm: jnp.ndarray, *, bits: int = 8,
               interpret: bool = False) -> jnp.ndarray:
    s = (1 << (bits - 1)) - 1
    v = lane_view(levels)
    out = pl.pallas_call(
        _dequant_kernel,
        grid=v.grid,
        in_specs=[_SMEM, _block(v)],
        out_specs=_block(v),
        out_shape=jax.ShapeDtypeStruct(v.x.shape, jnp.float32),
        interpret=interpret,
    )((norm / s).astype(jnp.float32).reshape(1, 1), v.x)
    return v.restore(out)
