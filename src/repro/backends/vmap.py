"""Host-device backend: all replicas on one device, programs via ``vmap``.

This is the PR-1 execution model, bit-exact: the replica axis is an ordinary
array dimension on the default device, the local step vmaps over it, and the
"collectives" are ``jnp.mean(axis=0)`` reductions.  It is the right backend
for single-accelerator runs and for CI, and the reference the mesh backend
is tested against.

Programs are ``_lower_<op>`` builders resolved by
``ExecutionBackend.lower(CollectiveOp)`` (``backends/ops.py``); pricing
derives from the op descriptor, never from the builder.  The quantized
exchange is **byte-true**: the payload is staged as int8 levels plus
per-tensor norms (``core/qsgd.quantize_split_pytree``, Pallas kernels on
TPU) and dequantized at the receiver — on one host device the "wire" is a
representation boundary, but it is the same levels+norms payload the mesh
backend all-gathers, so results match the sharded path bit-for-bit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.backends.base import (ExecutionBackend, donated, named,
                                 register_backend)
from repro.core import averaging as avg
from repro.core import qsgd as qsgd_mod


@register_backend
class VmapBackend(ExecutionBackend):
    """All replicas on the default device; ``vmap`` + ``jnp.mean``."""

    name = "vmap"

    # placement is the identity: the engine's stacked pytree already lives
    # where the programs run (put_* inherited as no-ops)

    def init_opt_state(self, optimizer, W):
        return jax.vmap(optimizer.init)(W)

    def describe(self):
        d = super().describe()
        d["use_kernel"] = self.use_kernel
        return d

    # ------------------------------------------------------------ lowerings
    # resolved by ExecutionBackend.lower(op); every compiled program comes
    # back through timed(op, ...), so a bound clock prices each invocation
    # from the op descriptor (backends/base.py); each is named after its op
    # (named), so it compiles to the XLA module jit_<op.name>
    # W and the optimizer state are dead once a step or a sync returns
    # their successors: donating them keeps one copy of the replica stack
    # on the device, which full-width models need.  The sync donates the
    # state too though it mostly hands it back unchanged (sync_momentum
    # off): an undonated pass-through is a full copy of m and v per sync,
    # and allocating room for it makes the allocator defragment
    def _lower_replica_step(self, op, *, loss_fn, optimizer):
        return jax.jit(named(op.name, avg.make_local_step(loss_fn, optimizer)),
                       donate_argnums=donated(0, 1))

    def _lower_full_step(self, op, *, loss_fn, optimizer):
        return jax.jit(named(op.name, avg.make_full_step(loss_fn, optimizer)))

    def _lower_qsgd_step(self, op, *, loss_fn, optimizer):
        return jax.jit(named(op.name, qsgd_mod.make_qsgd_step(
            loss_fn, optimizer, op.wire.bits)))

    def _lower_all_mean(self, op, *, sync_momentum=False):
        use_kernel = self.use_kernel
        return jax.jit(named(op.name, lambda W, o: avg.sync_replicas(
            W, o, sync_momentum=sync_momentum, use_kernel=use_kernel)),
            donate_argnums=donated(0, 1))

    def _lower_inner_mean(self, op):
        g = op.group
        return jax.jit(named(op.name, lambda W: avg.group_sync(W, g)))

    def _lower_opt_mean(self, op):
        return jax.jit(named(op.name, avg.sync_opt_state))

    def _lower_quantized_all_mean(self, op):
        """Byte-true QSGD-quantized parameter deltas from a shared
        full-precision anchor: each replica contributes (int8 levels,
        per-tensor norm); the receiver dequantizes and every replica adopts
        anchor + mean(dequantized deltas).  The quantize kernel routing is
        *platform*-keyed (TPU -> Pallas, else reference math), NOT
        ``use_kernel``-keyed: every backend must pick the same path or the
        exchange's cross-backend bit-match breaks on TPU (the kernel's
        blocked norm reduction rounds differently)."""
        bits = op.wire.bits
        use_kernel = jax.default_backend() == "tpu"

        def qsync(W, anchor, key):
            R = jax.tree_util.tree_leaves(W)[0].shape[0]
            delta = jax.tree_util.tree_map(
                lambda w, a: w.astype(jnp.float32) - a[None], W, anchor)
            keys = qsgd_mod.replica_keys(key, jnp.arange(R))
            levels, norms = jax.vmap(
                lambda d, k: qsgd_mod.quantize_split_pytree(
                    d, k, bits, use_kernel=use_kernel))(delta, keys)
            # the wire payload ends here; receiver-side dequantize
            dq = qsgd_mod.dequantize_split_pytree(levels, norms, bits)
            mean_d = jax.tree_util.tree_map(
                lambda d: jnp.mean(d, axis=0), dq)
            s_k = sum(
                jnp.sum(jnp.square(d - m[None])) / d.shape[0]
                for d, m in zip(jax.tree_util.tree_leaves(dq),
                                jax.tree_util.tree_leaves(mean_d)))
            new_anchor = jax.tree_util.tree_map(
                lambda a, m: a + m, anchor, mean_d)
            W_new = jax.tree_util.tree_map(
                lambda w, a: jnp.broadcast_to(a[None], w.shape).astype(w.dtype),
                W, new_anchor)
            return W_new, new_anchor, s_k

        return jax.jit(named(op.name, qsync))

    def _lower_mean_delta(self, op):
        def delta(W):
            means = jax.tree_util.tree_map(
                lambda x: jnp.mean(x.astype(jnp.float32), axis=0,
                                   keepdims=True), W)
            s_k = sum(
                jnp.sum(jnp.square(x.astype(jnp.float32) - m)) / x.shape[0]
                for x, m in zip(jax.tree_util.tree_leaves(W),
                                jax.tree_util.tree_leaves(means)))
            d = jax.tree_util.tree_map(
                lambda x, m: m - x.astype(jnp.float32), W, means)
            return d, s_k

        return jax.jit(named(op.name, delta))
