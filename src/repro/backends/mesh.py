"""Multi-device backend: replica axis sharded over a real device mesh.

The replica axis of every stacked pytree is laid out over the mesh's
``data`` (and, multi-pod, ``pod``) axes using the stacked PartitionSpecs
from ``launch/sharding.py``; programs are built with ``shard_map`` so each
device advances its local replica chunk independently, and the strategy
syncs lower to real collectives — ``jax.lax.pmean``/``psum`` over the
replica mesh axes.  This is where the paper's communication savings become
physical: between syncs no *parameter* tensor ever crosses the replica
axes, and the local step's HLO carries **zero replica-axis collectives** —
per-replica scalar metrics (loss/grad-norm telemetry) come back stacked and
are reduced by a separate tiny program off the step path, so skipping a
sync genuinely skips every cross-replica round.

Two **placements** decide what one replica is (DESIGN.md §5):

* ``replica_ddp`` (default) — each replica is a whole-model copy; the
  leading replica axis is the only sharded dim and every program is a
  fully-manual ``jax.shard_map`` over every mesh axis.
* ``replica_tp``  — one replica *spans* the mesh's ``model`` axis: inner
  parameter dims shard with the megatron-style ``base_spec`` rules from
  ``launch/sharding.py`` (column/row-parallel matmuls, vocab-parallel
  embeddings), threaded through ``put_params``/``put_opt`` and pinned on
  program outputs.  Programs become *partial-manual* ``shard_map``s:
  ``axis_names`` holds only the replica axes (``data``/``pod``), so the
  replica-axis collectives stay explicit ``lax.pmean``/``psum``, while the
  ``model`` axis is left to GSPMD, which inserts the intra-replica
  tensor-parallel collectives where the matmuls need them.  The two QSGD
  programs are the exception: they run fully manual under both placements
  (see ``_lower_qsgd_step``).

Cross-replica syncs are identical under both placements — the replica mean
is elementwise, so it never needs a model-axis exchange.  Checkpoints are
placement-neutral: ``device_get`` gathers to host arrays and the restoring
backend re-``put``s them under its own placement.

Every mesh axis must be in GSPMD's ``Auto`` mode; a mesh built with
``jax.make_mesh``'s default ``Explicit`` axes is re-declared ``Auto`` on
the same devices (``launch/mesh.auto_axes``).  On the CPU the mesh is
whatever ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` provides
(tests force 8, split 4 data x 2 model for ``replica_tp``); on TPU the
same code takes the chips JAX sees, or ``launch/mesh.py``'s production
mesh.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.backends.base import ExecutionBackend, named, register_backend
from repro.configs.base import ModelConfig, ParallelismPlan
from repro.core import averaging as avg
from repro.core import qsgd as qsgd_mod
from repro.launch import mesh as mesh_mod
from repro.launch import sharding as shard_rules

Pytree = Any

_tm = jax.tree_util.tree_map
_leaves = jax.tree_util.tree_leaves

PLACEMENTS = ("replica_ddp", "replica_tp")


@register_backend
class MeshBackend(ExecutionBackend):
    """Replica axis over the mesh's ``data``/``pod`` axes, ``shard_map``
    programs, ``lax.pmean`` syncs; ``placement`` picks whole-copy replicas
    (``replica_ddp``) or model-axis-spanning ones (``replica_tp``)."""

    name = "mesh"

    def __init__(self, mesh: Optional[Mesh] = None, *,
                 model_cfg: Optional[ModelConfig] = None,
                 placement: str = "replica_ddp",
                 model_parallel: Optional[int] = None,
                 multi_pod: bool = False,
                 use_kernel: Optional[bool] = None):
        if use_kernel:
            # the fused mean+sqdev kernel is a per-device program over the
            # full replica axis; mesh syncs lower to pmean over chunks —
            # refuse rather than silently ignore --sync-kernel on
            raise NotImplementedError(
                "use_kernel is a VmapBackend option; MeshBackend lowers "
                "syncs to lax.pmean (use --sync-kernel auto/off with "
                "--backend mesh)")
        super().__init__(use_kernel=False)
        if placement not in PLACEMENTS:
            raise ValueError(
                f"unknown placement '{placement}'; available: {PLACEMENTS}")
        if mesh is None:
            if model_parallel is None:
                # replica_tp wants a nontrivial model axis when the device
                # count allows one; replica_ddp keeps every device a replica
                n = len(jax.devices())
                model_parallel = 2 if (placement == "replica_tp"
                                       and n > 1 and n % 2 == 0) else 1
            mesh = mesh_mod.make_host_mesh(model_parallel)
        if isinstance(mesh, Mesh):
            mesh = mesh_mod.auto_axes(mesh)
        self.mesh = mesh
        self.placement = placement
        sizes = dict(mesh.shape)
        self.replica_axes: Tuple[str, ...] = tuple(
            a for a in ("pod", "data") if a in mesh.axis_names)
        if not self.replica_axes:
            raise ValueError(
                f"mesh {mesh.axis_names} has no replica axis "
                "('data' or 'pod'); see launch/mesh.py")
        if placement == "replica_tp" and "model" not in mesh.axis_names:
            raise ValueError(
                f"placement 'replica_tp' needs a 'model' mesh axis, "
                f"got {mesh.axis_names}")
        self.n_replica_devices = int(
            np.prod([sizes[a] for a in self.replica_axes]))
        self._entry = (self.replica_axes if len(self.replica_axes) > 1
                       else self.replica_axes[0])
        self._model_cfg = model_cfg or ModelConfig()
        # replica_ddp: each replica is a full model copy, the replica axis
        # is the only sharded dim; replica_tp: inner dims additionally take
        # the megatron base_spec rules over 'model' (launch/sharding.py)
        self._plan = ParallelismPlan(
            plan="replica_dp" if placement == "replica_tp" else "replica_ddp",
            placement=placement)
        # shard_map's manual axes: replica_tp is manual over the replica
        # axes only and leaves every other mesh axis ('model') to GSPMD;
        # replica_ddp is manual over the whole mesh
        self._manual = frozenset(self.replica_axes if placement == "replica_tp"
                                 else mesh.axis_names)
        self._cache: Dict[Any, Any] = {}

    # ------------------------------------------------------------- topology
    def bind(self, n_replicas: int) -> None:
        if n_replicas % self.n_replica_devices:
            raise ValueError(
                f"n_replicas={n_replicas} not divisible by the mesh's "
                f"{self.n_replica_devices} replica devices "
                f"(axes {self.replica_axes} of {dict(self.mesh.shape)})")
        super().bind(n_replicas)

    def describe(self):
        return {"backend": self.name, "n_replicas": self.n_replicas,
                "n_devices": len(self.mesh.devices.reshape(-1)),
                "mesh": dict(self.mesh.shape),
                "placement": self.placement,
                "replica_axes": list(self.replica_axes)}

    def default_group_size(self) -> Optional[int]:
        """Replicas per pod, read off the mesh — the natural hierarchical
        group boundary (ROADMAP multi-pod item): inner syncs then ride the
        fast in-pod ICI and never the cross-pod link."""
        sizes = dict(self.mesh.shape)
        pods = sizes.get("pod", 1)
        if pods > 1 and self.n_replicas:
            return max(1, self.n_replicas // pods)
        return None

    # ------------------------------------------------------------ placement
    def _param_shardings(self, W: Pytree) -> Pytree:
        specs = shard_rules.param_specs(
            self._model_cfg, W, self.mesh, self._plan,
            replica_axes=self.replica_axes, stacked=True)
        return shard_rules.named(self.mesh, specs)

    def _opt_shardings(self, opt_state: Pytree, W: Pytree) -> Pytree:
        pspecs = shard_rules.param_specs(
            self._model_cfg, W, self.mesh, self._plan,
            replica_axes=self.replica_axes, stacked=True)
        ospecs = shard_rules.opt_specs(
            self._model_cfg, opt_state, pspecs, self.mesh, self._plan,
            replica_axes=self.replica_axes, stacked=True)
        return shard_rules.named(self.mesh, ospecs)

    def put_params(self, W: Pytree) -> Pytree:
        return jax.device_put(W, self._param_shardings(W))

    def put_opt(self, opt_state: Pytree, W: Pytree) -> Pytree:
        if not _leaves(opt_state):
            return opt_state
        return jax.device_put(opt_state, self._opt_shardings(opt_state, W))

    def put_replicated(self, tree: Pytree) -> Pytree:
        return jax.device_put(tree, NamedSharding(self.mesh, P()))

    def init_opt_state(self, optimizer, W: Pytree) -> Pytree:
        return self.put_opt(jax.vmap(optimizer.init)(W), W)

    # ----------------------------------------------------------- internals
    def _stacked(self, tree: Pytree) -> Pytree:
        """Per-leaf shard_map spec: leading replica dim over the replica
        axes.  Only the *manual* axes appear here — under ``replica_tp``
        the inner-dim 'model' sharding is GSPMD's (seeded by the operands'
        shardings, pinned on outputs via ``out_shardings``)."""
        return _tm(lambda x: P(self._entry), tree)

    def _replicated(self, tree: Pytree) -> Pytree:
        return _tm(lambda x: P(), tree)

    def _pin(self, *shardings):
        """jit ``out_shardings`` pinning the placement's parameter layout on
        program outputs (None = let GSPMD choose).  Only ``replica_tp``
        needs it — without the pin GSPMD tends to rematerialize outputs
        replicated over 'model', silently losing the TP layout.  Entries
        may be thunks so replica_ddp builds never pay the spec walk."""
        if self.placement != "replica_tp":
            return None
        return tuple(s() if callable(s) else s for s in shardings)

    def _cached(self, kind: str, trees, build):
        key = (kind, tuple(
            (jax.tree_util.tree_structure(t),
             tuple(np.shape(x) for x in _leaves(t)))
            for t in trees))
        fn = self._cache.get(key)
        if fn is None:
            fn = self._cache[key] = build()
        return fn

    def _shmap(self, name, chunk, in_specs, out_specs, out_shardings=None,
               *, manual=None):
        """The jitted ``shard_map`` of ``chunk``, compiled to the XLA module
        ``jit_<name>``.  ``manual=None`` takes the placement's manual axes
        (the replica axes under replica_tp, GSPMD owning 'model'); pass the
        full axis set to force a fully-manual region."""
        fn = named(name, jax.shard_map(
            chunk, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
            axis_names=self._manual if manual is None else manual))
        if out_shardings is not None:
            return jax.jit(fn, out_shardings=out_shardings)
        return jax.jit(fn)

    def _pmean(self, x):
        return jax.lax.pmean(x, self.replica_axes)

    def _leaf_mean(self, x):
        """Global replica mean of one stacked leaf chunk, keepdims —
        chunk means are equal-weight, so mean-of-chunk-means is exact."""
        return self._pmean(jnp.mean(x.astype(jnp.float32), axis=0,
                                    keepdims=True))

    def _probe(self, W_chunk, means):
        """S_k = (1/R) Σ_i ||w̄ − w_i||² from local partials + one psum.
        Under replica_tp the per-leaf sums run over model-sharded dims —
        GSPMD supplies the intra-replica reduction; the replica-axis psum
        stays the only manual collective."""
        s_loc = sum(jnp.sum(jnp.square(x.astype(jnp.float32) - m))
                    for x, m in zip(_leaves(W_chunk), _leaves(means)))
        return jax.lax.psum(s_loc, self.replica_axes) / self.n_replicas

    def _local_keys(self, key, n_local: int):
        """Per-replica RNG keys from the chunk's *global* replica indices
        (its position on the replica axes times its replica count) — the
        shared ``qsgd.replica_keys`` stream, so it is independent of how
        replicas map to devices and matches VmapBackend bit-for-bit."""
        base = jax.lax.axis_index(self.replica_axes) * n_local
        return qsgd_mod.replica_keys(key, base + jnp.arange(n_local))

    def _metrics_mean(self, metrics: Pytree) -> Pytree:
        """Replica mean of stacked per-replica metrics — a separate tiny
        program, so the cross-replica round never rides the step's HLO
        (the engine reads the scalar back each iteration anyway)."""
        fn = self._cached("metrics_mean", (metrics,), lambda: jax.jit(named(
            "metrics_mean", lambda m: _tm(lambda x: jnp.mean(x, axis=0), m))))
        return fn(metrics)

    # ------------------------------------------------------------ lowerings
    # resolved by ExecutionBackend.lower(op) and wrapped by timed(op, ...):
    # with a bound clock each invocation is priced from the op descriptor
    # (backends/ops.py) — the builders only decide *how* the exchange runs
    def _lower_replica_step(self, op, *, loss_fn, optimizer):
        one_replica = avg.make_replica_step(loss_fn, optimizer)

        def chunk(Wc, oc, bc, lr):
            # per-chunk metrics stay stacked: the step program carries zero
            # replica-axis collectives (tested on its lowered HLO)
            return jax.vmap(one_replica, in_axes=(0, 0, 0, None))(
                Wc, oc, bc, lr)

        def prog(W, opt_state, batch, lr):
            fn = self._cached("step", (W, opt_state, batch), lambda: self._shmap(
                op.name, chunk,
                (self._stacked(W), self._stacked(opt_state),
                 self._stacked(batch), P()),
                (self._stacked(W), self._stacked(opt_state), P(self._entry)),
                out_shardings=self._pin(
                    lambda: self._param_shardings(W),
                    lambda: self._opt_shardings(opt_state, W), None)))
            W, opt_state, m = fn(W, opt_state, batch, lr)
            return W, opt_state, self._metrics_mean(m)

        return prog

    def _lower_full_step(self, op, *, loss_fn, optimizer):
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

        def chunk(Wc, oc, bc, lr):
            (loss, aux), grads = jax.vmap(grad_fn)(Wc, bc)
            g_mean = _tm(self._leaf_mean, grads)
            g_bcast = _tm(lambda g, w: jnp.broadcast_to(g, w.shape), g_mean, Wc)
            Wn, on = jax.vmap(optimizer.update, in_axes=(0, 0, 0, None))(
                g_bcast, oc, Wc, lr)
            metrics = {"loss": self._pmean(jnp.mean(loss)),
                       **{k: self._pmean(jnp.mean(v)) for k, v in aux.items()}}
            return Wn, on, metrics

        def prog(W, opt_state, batch, lr):
            fn = self._cached("full", (W, opt_state, batch), lambda: self._shmap(
                op.name, chunk,
                (self._stacked(W), self._stacked(opt_state),
                 self._stacked(batch), P()),
                (self._stacked(W), self._stacked(opt_state), P()),
                out_shardings=self._pin(
                    lambda: self._param_shardings(W),
                    lambda: self._opt_shardings(opt_state, W), None)))
            return fn(W, opt_state, batch, lr)

        return prog

    def _lower_qsgd_step(self, op, *, loss_fn, optimizer):
        bits = op.wire.bits
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

        def chunk(Wc, oc, bc, lr, key):
            (loss, aux), grads = jax.vmap(grad_fn)(Wc, bc)
            keys = self._local_keys(key, _leaves(Wc)[0].shape[0])
            q = jax.vmap(lambda g, k: qsgd_mod.quantize_pytree(g, k, bits))(
                grads, keys)
            g_mean = _tm(self._leaf_mean, q)
            g_bcast = _tm(lambda g, w: jnp.broadcast_to(g, w.shape)
                          .astype(w.dtype), g_mean, Wc)
            Wn, on = jax.vmap(optimizer.update, in_axes=(0, 0, 0, None))(
                g_bcast, oc, Wc, lr)
            metrics = {"loss": self._pmean(jnp.mean(loss)),
                       **{k: self._pmean(jnp.mean(v)) for k, v in aux.items()}}
            return Wn, on, metrics

        def prog(W, opt_state, batch, lr, key):
            # fully manual under replica_tp too: stochastic rounding is
            # discontinuous, so a norm or gradient summed across model
            # shards (rounding unlike the unsharded sum) flips levels and
            # the trajectory leaves the vmap reference.  Each replica
            # computes whole on its model devices; the TP layout is pinned
            # back on the outputs.
            fn = self._cached("qsgd", (W, opt_state, batch), lambda: self._shmap(
                op.name, chunk,
                (self._stacked(W), self._stacked(opt_state),
                 self._stacked(batch), P(), P()),
                (self._stacked(W), self._stacked(opt_state), P()),
                out_shardings=self._pin(
                    lambda: self._param_shardings(W),
                    lambda: self._opt_shardings(opt_state, W), None),
                manual=frozenset(self.mesh.axis_names)))
            return fn(W, opt_state, batch, lr, key)

        return prog

    def _lower_all_mean(self, op, *, sync_momentum: bool = False):
        def chunk(Wc, oc):
            means = _tm(self._leaf_mean, Wc)
            s_k = self._probe(Wc, means)
            Wn = _tm(lambda x, m: jnp.broadcast_to(m, x.shape).astype(x.dtype),
                     Wc, means)
            if sync_momentum:
                oc = _tm(lambda x: jnp.broadcast_to(
                    self._leaf_mean(x), x.shape).astype(x.dtype), oc)
            return Wn, oc, s_k

        def prog(W, opt_state):
            fn = self._cached(
                f"all_mean{int(sync_momentum)}", (W, opt_state),
                lambda: self._shmap(
                    op.name, chunk,
                    (self._stacked(W), self._stacked(opt_state)),
                    (self._stacked(W), self._stacked(opt_state), P()),
                    out_shardings=self._pin(
                        lambda: self._param_shardings(W),
                        lambda: self._opt_shardings(opt_state, W), None)))
            return fn(W, opt_state)

        return prog

    def _lower_opt_mean(self, op):
        def chunk(oc):
            return _tm(lambda x: jnp.broadcast_to(
                self._leaf_mean(x), x.shape).astype(x.dtype), oc)

        def prog(opt_state):
            if not _leaves(opt_state):
                return opt_state
            # the pin reuses the parameter rules directly on the optimizer
            # tree — its paths are the param paths under a state-key prefix
            # and the rules are suffix-anchored, so buffers land on the
            # same TP layout put_opt gave them
            fn = self._cached("opt_mean", (opt_state,), lambda: self._shmap(
                op.name, chunk, (self._stacked(opt_state),),
                self._stacked(opt_state),
                out_shardings=(self._param_shardings(opt_state)
                               if self.placement == "replica_tp" else None)))
            return fn(opt_state)

        return prog

    def _lower_inner_mean(self, op):
        g = int(op.group)

        def build(W):
            r_local = _leaves(W)[0].shape[0] // self.n_replica_devices
            if r_local and r_local % g == 0:
                # groups fall inside one device's chunk: pure local reshape
                def chunk(Wc):
                    return avg.group_sync(Wc, g)
            elif r_local and g % r_local == 0:
                groups = self._device_groups(g // r_local)
                ax = self.replica_axes[-1]

                def chunk(Wc):
                    def leaf(x):
                        m = jax.lax.pmean(
                            jnp.mean(x.astype(jnp.float32), 0, keepdims=True),
                            ax, axis_index_groups=groups)
                        return jnp.broadcast_to(m, x.shape).astype(x.dtype)
                    return _tm(leaf, Wc)
            else:
                raise NotImplementedError(
                    f"group_size={g} does not align with {r_local} local "
                    f"replicas per device")
            return self._shmap(
                op.name, chunk, (self._stacked(W),), self._stacked(W),
                out_shardings=(self._param_shardings(W)
                               if self.placement == "replica_tp" else None))

        def prog(W):
            return self._cached(f"inner{g}", (W,), lambda: build(W))(W)

        return prog

    def _device_groups(self, devices_per_group: int):
        """Contiguous device groups along the innermost replica axis.
        Groups crossing the pod boundary are not supported — the point of
        the hierarchy is that they never should."""
        sizes = dict(self.mesh.shape)
        inner = sizes[self.replica_axes[-1]]
        if devices_per_group > inner or inner % devices_per_group:
            raise NotImplementedError(
                f"replica groups spanning {devices_per_group} devices do "
                f"not tile the '{self.replica_axes[-1]}' axis (size {inner})")
        return [list(range(i, i + devices_per_group))
                for i in range(0, inner, devices_per_group)]

    def _lower_quantized_all_mean(self, op):
        """Byte-true QSGD anchor-delta exchange: each device quantizes its
        replica chunk's deltas to (int8 levels, per-tensor f32 norms) and
        the **levels+norms pair is what crosses the replica axes** — one
        tiled all-gather of ~bits/32 of the f32 volume plus the norm
        side-channel, exactly the payload ``op.wire_bytes`` prices.  Every
        device dequantizes at the receiver and reduces the full stacked
        deltas locally, which makes the mean (and the probe S_k) the same
        reduction the vmap backend runs — the quantized path is
        bit-matched across backends and placements, not merely close.  The
        old path moved *dequantized f32* over the mesh (ROADMAP item).
        Kernel routing is platform-keyed (TPU -> Pallas), matching the
        vmap backend's choice exactly — see the note there."""
        bits = op.wire.bits
        use_kernel = jax.default_backend() == "tpu"

        def chunk(Wc, anchor, key):
            delta = _tm(lambda w, a: w.astype(jnp.float32) - a[None],
                        Wc, anchor)
            keys = self._local_keys(key, _leaves(Wc)[0].shape[0])
            levels, norms = jax.vmap(
                lambda d, k: qsgd_mod.quantize_split_pytree(
                    d, k, bits, use_kernel=use_kernel))(delta, keys)
            # the wire: int8 levels + norms, gathered over the replica axes
            def gather(x):
                return jax.lax.all_gather(x, self.replica_axes, axis=0,
                                          tiled=True)
            levels = _tm(gather, levels)
            norms = _tm(gather, norms)
            dq = qsgd_mod.dequantize_split_pytree(levels, norms, bits)
            mean_d = _tm(lambda d: jnp.mean(d, axis=0), dq)
            s_k = sum(jnp.sum(jnp.square(d - m[None])) / d.shape[0]
                      for d, m in zip(_leaves(dq), _leaves(mean_d)))
            new_anchor = _tm(lambda a, m: a + m, anchor, mean_d)
            Wn = _tm(lambda w, a: jnp.broadcast_to(a[None], w.shape)
                     .astype(w.dtype), Wc, new_anchor)
            return Wn, new_anchor, s_k

        def prog(W, anchor, key):
            # fully-manual region even under replica_tp, for the reason in
            # _lower_qsgd_step: per-tensor norms summed across model shards
            # round unlike vmap's and break the bit-match.  The model
            # shards re-materialize at region entry over the fast
            # intra-replica ICI — the *cross-replica* wire (the link the
            # paper prices) still carries only int8 levels + norms, and
            # out_shardings pins the TP layout right back
            fn = self._cached("qam", (W, anchor), lambda: self._shmap(
                op.name, chunk,
                (self._stacked(W), self._replicated(anchor), P()),
                (self._stacked(W), self._replicated(anchor), P()),
                out_shardings=self._pin(
                    lambda: self._param_shardings(W), None, None),
                manual=frozenset(self.mesh.axis_names)))
            return fn(W, anchor, key)

        return prog

    def _lower_mean_delta(self, op):
        def chunk(Wc):
            means = _tm(self._leaf_mean, Wc)
            s_k = self._probe(Wc, means)
            delta = _tm(lambda x, m: m - x.astype(jnp.float32), Wc, means)
            return delta, s_k

        def prog(W):
            # the delta is parameter-shaped strategy state held for `delay`
            # steps (DaSGD) — pin it to the TP layout so it never sits
            # model-replicated on the mesh
            fn = self._cached("mean_delta", (W,), lambda: self._shmap(
                op.name, chunk, (self._stacked(W),), (self._stacked(W), P()),
                out_shardings=self._pin(lambda: self._param_shardings(W), None)))
            return fn(W)

        return prog

    def collapse(self, W: Pytree) -> Pytree:
        # eager global mean works on sharded arrays; result is unsharded
        return avg.replica_mean(W)
