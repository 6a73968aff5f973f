"""Pure-jnp oracles for every Pallas kernel (the correctness references the
kernel tests sweep against)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def attention_ref(q, k, v, *, causal: bool = True, window: int = 0):
    """q: (B,S,H,d); k,v: (B,S,K,d).  Exact softmax attention."""
    B, Sq, H, d = q.shape
    _, Sk, K, _ = k.shape
    G = H // K
    qh = q.reshape(B, Sq, K, G, d)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qh.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(d)
    qp = jnp.arange(Sq)[:, None]
    kp = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kp <= qp
    if window:
        mask &= kp > qp - window
    s = jnp.where(mask[None, None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", w, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, d).astype(q.dtype)


def quantize_ref(x, u, *, bits: int = 8, norm=None):
    """QSGD with externally-supplied uniforms (same contract as the kernel).
    ``norm`` fixes the tensor norm instead of recomputing it: a kernel's
    blocked norm reduction may round differently, and its levels are then
    checked bit-exactly at its own norm."""
    s = (1 << (bits - 1)) - 1
    xf = x.astype(jnp.float32)
    if norm is None:
        norm = jnp.sqrt(jnp.sum(jnp.square(xf)))
    scaled = jnp.abs(xf) * jnp.where(norm > 0, s / norm, 0.0)
    floor = jnp.floor(scaled)
    mag = floor + (u < (scaled - floor)).astype(jnp.float32)
    return (jnp.sign(xf) * mag).astype(jnp.int8), norm


def dequantize_ref(levels, norm, *, bits: int = 8):
    s = (1 << (bits - 1)) - 1
    return levels.astype(jnp.float32) * (norm / s)


def mean_and_sqdev_ref(w):
    """w: (R, ...) -> (mean over axis 0, Σ ||mean − w_i||²)."""
    wf = w.reshape(w.shape[0], -1).astype(jnp.float32)
    mean = jnp.mean(wf, axis=0)
    sq = jnp.sum(jnp.square(wf - mean[None]))
    return mean.reshape(w.shape[1:]), sq
