"""QSGD baseline (Alistarh et al. 2017) — stochastic gradient quantization.

The paper compares ADPSGD against 8-bit QSGD (§IV: "QSGD uses 8 bits to
store each gradient component, its communication data size is 1/4 of
FULLSGD and 2x of our ADPSGD").  Every iteration each replica quantizes its
gradient, "transmits" it (simulated: quantize→dequantize round-trip), and
all replicas apply the averaged dequantized gradient — trajectories stay
identical, as with a parameter server.

``quantize``/``dequantize`` reference implementations live here; the
bandwidth-bound inner loop has a Pallas kernel (repro/kernels/qsgd_quant.py).
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp

from repro.optim.optimizers import Optimizer

Pytree = Any


def quantize(v: jnp.ndarray, key, bits: int = 8) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """QSGD stochastic quantization of one tensor.

    q_i = ||v||₂ · sgn(v_i) · ξ_i / s  with s = 2^(bits−1) − 1 levels and
    ξ_i ∈ {⌊|v_i|·s/‖v‖⌋, ⌈…⌉} chosen stochastically so E[q] = v.
    Returns (levels int8, norm scalar f32).
    """
    s = (1 << (bits - 1)) - 1
    vf = v.astype(jnp.float32)
    # reduce in the flat logical order: a leaf that arrives in another
    # physical layout (gathered from model shards) then rounds the same
    norm = jnp.sqrt(jnp.sum(jnp.square(vf.reshape(-1))))
    scaled = jnp.where(norm > 0, jnp.abs(vf) / norm * s, 0.0)
    floor = jnp.floor(scaled)
    prob = scaled - floor
    rnd = jax.random.uniform(key, v.shape)
    mag = floor + (rnd < prob).astype(jnp.float32)
    levels = (jnp.sign(vf) * mag).astype(jnp.int8)
    return levels, norm


def dequantize(levels: jnp.ndarray, norm: jnp.ndarray, bits: int = 8,
               dtype=jnp.float32) -> jnp.ndarray:
    s = (1 << (bits - 1)) - 1
    return (levels.astype(jnp.float32) * (norm / s)).astype(dtype)


def replica_keys(key, idx):
    """Per-replica RNG keys: ``fold_in`` on the *global* replica index.
    The single definition every backend shares — cross-backend/placement
    parity of the quantization noise depends on these streams matching
    bit-for-bit, so never derive per-replica keys any other way."""
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(idx)


def quantize_pytree(grads: Pytree, key, bits: int = 8) -> Pytree:
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, g in zip(keys, leaves):
        lv, nm = quantize(g, k, bits)
        out.append(dequantize(lv, nm, bits, g.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def quantize_split_pytree(grads: Pytree, key, bits: int = 8, *,
                          use_kernel: bool = False) -> Tuple[Pytree, Pytree]:
    """The byte-true wire representation: quantize every leaf but keep the
    payload split as (int8 levels tree, f32 per-tensor norms tree) instead
    of fusing the dequantize — this pair is what a byte-true exchange puts
    on the wire (``backends/ops.qsgd_wire``); the receiver dequantizes via
    ``dequantize_split_pytree``.  The RNG stream (one split per leaf, same
    uniforms) matches ``quantize_pytree`` exactly, so split+dequantize is
    bit-identical to the fused round-trip.  ``use_kernel`` routes the
    bandwidth-bound inner loop through the Pallas kernels
    (``kernels/qsgd_quant.py``) — profitable on TPU only."""
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    keys = jax.random.split(key, len(leaves))
    lvs, nms = [], []
    for k, g in zip(keys, leaves):
        if use_kernel:
            from repro.kernels import qsgd_quant
            u = jax.random.uniform(k, g.shape)
            lv, nm = qsgd_quant.quantize(g.astype(jnp.float32), u, bits=bits)
        else:
            lv, nm = quantize(g, k, bits)
        lvs.append(lv)
        nms.append(nm)
    return (jax.tree_util.tree_unflatten(treedef, lvs),
            jax.tree_util.tree_unflatten(treedef, nms))


def dequantize_split_pytree(levels: Pytree, norms: Pytree, bits: int = 8,
                            dtype=jnp.float32) -> Pytree:
    """Receiver side of the byte-true exchange.  Norm leaves may carry
    leading batch dims (a stacked replica axis from an all-gather) — they
    broadcast against the matching level leaves."""
    s = (1 << (bits - 1)) - 1

    def leaf(lv, nm):
        nm = nm.reshape(nm.shape + (1,) * (lv.ndim - nm.ndim))
        return (lv.astype(jnp.float32) * (nm / s)).astype(dtype)

    return jax.tree_util.tree_map(leaf, levels, norms)


def make_qsgd_step(loss_fn, optimizer: Optimizer, bits: int = 8):
    """Full-communication step with quantized gradients.  Signature matches
    the other steps plus an rng key: step(W, opt, batch, lr, key)."""
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def step(W, opt_state, batch, lr, key):
        (loss, aux), grads = jax.vmap(grad_fn)(W, batch)
        R = jax.tree_util.tree_leaves(W)[0].shape[0]
        keys = replica_keys(key, jnp.arange(R))
        q = jax.vmap(lambda g, k: quantize_pytree(g, k, bits))(grads, keys)
        g_mean = jax.tree_util.tree_map(
            lambda g: jnp.mean(g.astype(jnp.float32), axis=0, keepdims=True),
            q)
        g_bcast = jax.tree_util.tree_map(
            lambda g, w: jnp.broadcast_to(g, w.shape).astype(w.dtype), g_mean, W)
        new_W, new_state = jax.vmap(
            optimizer.update, in_axes=(0, 0, 0, None))(g_bcast, opt_state, W, lr)
        metrics = {"loss": jnp.mean(loss),
                   **{k: jnp.mean(v) for k, v in aux.items()}}
        return new_W, new_state, metrics

    return step
