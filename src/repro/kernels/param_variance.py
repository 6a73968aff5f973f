"""Fused replica-mean + variance-probe Pallas kernel.

Algorithm 2 line 10–11 needs, at every sync, both the replica mean of every
parameter buffer and S_k = (1/n)·Σ_i ||w̄ − w_i||².  A naive implementation
reads each buffer twice (once for the mean, once for the deviations); this
kernel fuses both into one pass.  The buffer is tiled as (R, rows, lanes)
VMEM blocks of its lane-dense view (``kernels/tiling.py``); each block
writes its (rows, lanes) mean slice and adds its squared deviations, summed
over the replicas, into a lane-dense (rows, lanes) f32 accumulator that
stays resident across the grid.  The accumulator is reduced to the scalar
after the call (the TPU compiler refuses scalar stores to VMEM)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import lane_view


def _mean_sqdev_kernel(w_ref, mean_ref, sq_ref):
    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        sq_ref[...] = jnp.zeros_like(sq_ref)

    w = w_ref[...].astype(jnp.float32)            # (R, rows, lanes)
    mean = jnp.mean(w, axis=0)                    # (rows, lanes)
    mean_ref[...] = mean.astype(mean_ref.dtype)
    dev = w - mean[None]
    sq_ref[...] += jnp.sum(dev * dev, axis=0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mean_and_sqdev(w: jnp.ndarray, *, interpret: bool = False):
    """w: (R, ...) one stacked-replica buffer.  Returns (mean of shape
    w.shape[1:], Σ_i ||mean − w_i||² scalar f32).  Divide the scalar by R
    for the paper's S_k contribution."""
    R = w.shape[0]
    v = lane_view(w, lead=1)
    rows, lanes = v.rows, v.lanes
    mean, sq = pl.pallas_call(
        _mean_sqdev_kernel,
        grid=v.grid,
        in_specs=[pl.BlockSpec((R, rows, lanes), lambda i, j: (0, i, j))],
        out_specs=[
            pl.BlockSpec((rows, lanes), lambda i, j: (i, j)),
            pl.BlockSpec((rows, lanes), lambda i, j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(v.x.shape[1:], jnp.float32),
            jax.ShapeDtypeStruct((rows, lanes), jnp.float32),
        ],
        interpret=interpret,
    )(v.x)
    return v.restore(mean), jnp.sum(sq)
