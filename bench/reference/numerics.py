"""Precision of the plain references and of their controls.

``Numerics()`` multiplies float32 operands at ``Precision.HIGHEST`` and
keeps weights and AdamW state in float32: the reference.  Its controls are
the reference one precision step below what a configuration states:

* ``Numerics(store="bfloat16", operands="bfloat16")``: weights and AdamW
  state rounded to bfloat16 after every update (the step below the stated
  float32 state), products of bfloat16 operands as the trainer computes.
* ``Numerics(operands="fp8")``: products of float8 e4m3 operands at a
  per-tensor scale (amax / 448), the step below the stated bfloat16
  compute.

Rounding passes gradients straight through, so the backward products see
the rounded forward operands."""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _straight_through(x, q):
    return x + jax.lax.stop_gradient(q - x)


def fp8_round(x):
    """x rounded to float8 e4m3 at a per-tensor scale, back in float32."""
    scale = jax.lax.stop_gradient(
        jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return _straight_through(x, q)


def bf16_round(x):
    return _straight_through(
        x, x.astype(jnp.bfloat16).astype(jnp.float32))


class Numerics:
    def __init__(self, operands: str = "float32", store: str = "float32"):
        self.operands = operands
        self.store = store

    def _q(self, x):
        x = x.astype(jnp.float32)
        if self.operands == "fp8":
            return fp8_round(x)
        if self.operands == "bfloat16":
            return bf16_round(x)
        return x

    def einsum(self, spec: str, a, b):
        return jnp.einsum(spec, self._q(a), self._q(b), precision=HIGHEST)

    def keep(self, tree):
        """The state as it is stored between steps."""
        if self.store == "float32":
            return tree
        return jax.tree_util.tree_map(
            lambda x: x.astype(self.store).astype(jnp.float32), tree)
