"""The pluggable execution-backend API.

A ``CommunicationStrategy`` decides *when* and *what* replicas exchange; an
``ExecutionBackend`` decides *where the replicas live* and *how the exchange
is executed*.  The backend owns device placement, the layout of the leading
replica axis, and the collective primitives, so a strategy compiles the same
policy against any topology:

* ``VmapBackend``  — all R replicas on the host's default device, programs
  built with ``vmap`` + ``jnp.mean`` (the PR-1 behavior, bit-exact).
* ``MeshBackend``  — the replica axis sharded over the ``data``/``pod`` axes
  of a real ``jax.sharding.Mesh`` (``launch/mesh.py``), programs built with
  ``shard_map`` and syncs lowered to real collectives.

Strategies never hand-roll ``vmap`` or ``jnp.mean(axis=0)``; they emit
**``CollectiveOp`` descriptors** (``backends/ops.py``) and ask the backend
to lower them to compiled device programs:

    program = backend.lower(op, loss_fn=..., optimizer=...)

The descriptor carries the collective kind, wire format, group, and overlap
hint; lowering resolves ``op.name`` to the backend's ``_lower_<name>``
builder and wraps the compiled program so every invocation is priced *from
the descriptor itself* (``op.wire_bytes``) into the bound telemetry clock —
the old hand-synchronized ``PROGRAM_COMM`` table is gone.  Every call also
runs inside the profiler span ``repro.program.<op.name>`` (stats ``step``
and ``bytes``), and every program compiles to the XLA module
``jit_<op.name>``, so a profile names each device run after its op.  Ops with
``overlap=True`` dispatch asynchronously and return an ``InFlightOp``
handle fetched later (DaSGD's delayed correction).

The named convenience builders (``replica_step`` / ``all_mean`` /
``inner_mean`` / ``quantized_all_mean`` / ``mean_delta`` / ``apply_delta``
/ ``full_step`` / ``qsgd_step`` / ``opt_mean``) remain as thin sugar over
``lower(<canonical op>)`` for tests and benchmarks.

Placement hooks (``put_params`` / ``put_opt`` / ``put_replicated`` /
``init_opt_state``) let the engine and the checkpoint layer stay
backend-agnostic: a checkpoint saved under one backend restores under any
other (``checkpoint/io.py`` saves host arrays; the engine re-``put``s them
through the active backend).

Backends register by name (``@register_backend``); ``--backend=vmap|mesh``
on the train driver selects one.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Type

import jax
from jax.profiler import TraceAnnotation

from repro.backends import ops as collective_ops
from repro.backends.ops import CollectiveOp, InFlightOp
from repro.core import averaging as avg

Pytree = Any


class ExecutionBackend:
    """Base class; concrete backends override placement + ``_lower_*``
    program builders.

    ``use_kernel`` selects the fused Pallas mean+sqdev kernel inside
    ``all_mean`` where the backend supports it: ``True``/``False`` force
    it, ``None`` (default) enables it only where profitable — on TPU; on
    CPU interpret-mode it loses badly (see ``benchmarks/kernel_bench.py``).
    The QSGD *quantization* kernels are deliberately NOT governed by this
    flag: their routing is platform-keyed (TPU -> Pallas, else reference
    math) identically on every backend, because the byte-true exchange's
    cross-backend bit-match requires all backends to round the same way
    (see ``_lower_quantized_all_mean`` on vmap/mesh).
    """

    name = "base"

    def __init__(self, *, use_kernel: Optional[bool] = None):
        if use_kernel is None:
            use_kernel = jax.default_backend() == "tpu"
        self.use_kernel = bool(use_kernel)
        self.n_replicas: Optional[int] = None
        self.clock = None              # telemetry clock (runtime/clock.py)
        self.step = 0                  # engine iteration, stamped on spans

    # ------------------------------------------------------------- topology
    def bind(self, n_replicas: int) -> None:
        """Fix the replica count this backend will lay out.  Called once by
        the engine before any placement; backends validate divisibility
        against their device topology here."""
        self.n_replicas = int(n_replicas)

    def describe(self) -> Dict[str, Any]:
        """Telemetry: where the replicas live (benchmarks record this)."""
        return {"backend": self.name, "n_replicas": self.n_replicas,
                "n_devices": 1}

    # ------------------------------------------------------------ telemetry
    def set_clock(self, clock) -> None:
        """Bind a ``runtime/clock.py`` Clock.  Every program lowered by this
        backend is wrapped by ``timed``; the wrapper consults ``self.clock``
        at call time, so binding before or after compilation both work and
        ``None`` (the default) keeps dispatch entirely un-instrumented."""
        self.clock = clock

    def timed(self, op: CollectiveOp, fn: Callable) -> Callable:
        """Wrap a compiled program so each invocation runs inside the
        profiler span ``repro.program.<op.name>`` and, with a clock bound,
        reports one ``(compute_s, comm_s, bytes)`` record into its
        ``Timeline``.  The span carries the stats ``step`` (the engine
        iteration, ``self.step``) and ``bytes``: ``op.wire_bytes`` of the
        per-replica parameter count read off the stacked first operand —
        the exchange's communication cost, 0 for collective-free ops —
        worked out once per operand structure.  The collective kind and
        group ride the op, and ``overlap=True`` ops dispatch
        asynchronously: the wrapper returns an ``InFlightOp`` whose
        ``fetch()`` settles the exchange (and the clock) later."""
        span = f"repro.program.{op.name}"
        priced: Dict[Any, tuple] = {}

        def wire(tree):
            """(bytes, nodes) of one invocation on operand ``tree``."""
            n = self.n_replicas or 1
            if op.collective is None:
                return 0.0, n
            leaves, treedef = jax.tree_util.tree_flatten(tree)
            key = (treedef, tuple(x.shape for x in leaves), n)
            got = priced.get(key)
            if got is None:
                if op.group:
                    n = int(op.group)
                n_params = (sum(x.size for x in leaves)
                            // max(1, self.n_replicas or 1))
                got = priced[key] = (
                    op.wire_bytes(n_params, n, n_tensors=len(leaves)), n)
            return got

        def wrapped(*args):
            nbytes, n = wire(args[0])
            with TraceAnnotation(span, step=self.step, bytes=nbytes):
                clock = self.clock
                if clock is None:
                    out = fn(*args)
                    return (InFlightOp(op, out, step=lambda: self.step)
                            if op.overlap else out)
                if op.overlap:
                    out, rec = clock.dispatch_async(
                        op.name, fn, args, comm_bytes=nbytes,
                        collective=op.collective, n_nodes=n)
                    return InFlightOp(op, out, clock, rec,
                                      step=lambda: self.step)
                return clock.measure(op.name, fn, args, is_step=op.is_step,
                                     comm_bytes=nbytes,
                                     collective=op.collective, n_nodes=n)

        wrapped.__wrapped__ = fn       # the compiled program, for inspection
        return wrapped

    # ------------------------------------------------------------- lowering
    def lower(self, op: CollectiveOp, **builder_kw) -> Callable:
        """Lower one ``CollectiveOp`` descriptor to a compiled, timed
        program.  ``op.name`` resolves to this backend's ``_lower_<name>``
        builder; parameters the op itself carries (wire bits, group size,
        overlap) are read off the descriptor, anything host-side (loss_fn,
        optimizer, sync_momentum) arrives as builder kwargs."""
        build = getattr(self, f"_lower_{op.name}", None)
        if build is None:
            raise KeyError(
                f"backend '{self.name}' cannot lower op '{op.name}'")
        return self.timed(op, build(op, **builder_kw))

    # ---------------------------------------------- named-op sugar
    # Thin wrappers over lower(<canonical op>) — tests and benchmarks call
    # these; strategies emit the descriptors directly.

    def replica_step(self, loss_fn, optimizer) -> Callable:
        """(W, opt_state, batch, lr) -> (W, opt_state, metrics); no
        replica-axis collectives."""
        return self.lower(collective_ops.replica_step_op(),
                          loss_fn=loss_fn, optimizer=optimizer)

    def full_step(self, loss_fn, optimizer) -> Callable:
        """(W, opt_state, batch, lr) -> (W, opt_state, metrics); gradients
        all-reduced every call (FULLSGD)."""
        return self.lower(collective_ops.full_step_op(),
                          loss_fn=loss_fn, optimizer=optimizer)

    def qsgd_step(self, loss_fn, optimizer, bits: int) -> Callable:
        """(W, opt_state, batch, lr, key) -> (W, opt_state, metrics);
        quantized gradient exchange every call (QSGD)."""
        return self.lower(collective_ops.qsgd_step_op(bits),
                          loss_fn=loss_fn, optimizer=optimizer)

    def all_mean(self, *, sync_momentum: bool = False) -> Callable:
        """(W, opt_state) -> (W, opt_state, s_k): the replica average and
        the paper's variance probe."""
        return self.lower(collective_ops.all_mean_op(),
                          sync_momentum=sync_momentum)

    def inner_mean(self, group_size: int) -> Callable:
        """(W) -> W averaged within contiguous replica groups of
        ``group_size`` (hierarchical in-pod sync)."""
        return self.lower(collective_ops.inner_mean_op(group_size))

    def quantized_all_mean(self, bits: int) -> Callable:
        """(W, anchor, key) -> (W, new_anchor, s_k): byte-true QSGD deltas
        from the full-precision anchor — int8 levels + norms on the wire,
        dequantized at the receiver, averaged and re-applied."""
        return self.lower(collective_ops.quantized_all_mean_op(bits))

    def opt_mean(self) -> Callable:
        """(opt_state) -> opt_state averaged across replicas."""
        return self.lower(collective_ops.opt_mean_op())

    def mean_delta(self, *, overlap: bool = False) -> Callable:
        """(W) -> (delta, s_k) with ``delta_i = mean(W) - W_i`` (stacked):
        the correction DaSGD applies ``delay`` steps later.  With
        ``overlap=True`` the call returns an ``InFlightOp`` immediately."""
        return self.lower(collective_ops.mean_delta_op(overlap=overlap))

    def apply_delta(self) -> Callable:
        """(W, delta) -> W + delta, elementwise (no collectives — the
        collective already happened in ``mean_delta``)."""
        return self.lower(collective_ops.apply_delta_op())

    # ------------------------------------------------------------ placement
    def put_params(self, W: Pytree) -> Pytree:
        """Place a replica-stacked parameter pytree on this backend's
        devices (identity for the host backend)."""
        return W

    def put_opt(self, opt_state: Pytree, W: Pytree) -> Pytree:
        """Place a replica-stacked optimizer state (mirrors ``W``'s
        layout; scalar counters replicate)."""
        return opt_state

    def put_replicated(self, tree: Pytree) -> Pytree:
        """Place an *unstacked* pytree replicated on every device (e.g. the
        qsgd_periodic full-precision anchor)."""
        return tree

    def get(self, tree: Pytree) -> Pytree:
        """Fetch to host numpy (checkpoint save path)."""
        return jax.device_get(tree)

    def init_opt_state(self, optimizer, W: Pytree) -> Pytree:
        return self.put_opt(jax.vmap(optimizer.init)(W), W)

    def collapse(self, W: Pytree) -> Pytree:
        """Replica mean without the probe — a host-side convenience (anchor
        seeding, export checkpoints)."""
        return avg.replica_mean(W)

    def default_group_size(self) -> Optional[int]:
        """Topology-derived hierarchical group size (replicas per pod on a
        multi-pod mesh), or None when the backend has no natural group
        boundary — the hierarchical strategy then falls back to its
        config/heuristic choice."""
        return None

    # ------------------------------------------------- shared lowerings
    def _lower_apply_delta(self, op: CollectiveOp):
        """Elementwise add, shared by every backend.  Buffers are donated
        where donation is real (TPU/GPU): the pre-correction W and the
        fetched delta are both dead after the add, so the overlap window
        never holds a third parameter-sized buffer."""
        import jax.numpy as jnp

        def apply(W, delta):
            return jax.tree_util.tree_map(
                lambda w, d: (w.astype(jnp.float32) + d).astype(w.dtype),
                W, delta)

        return jax.jit(named(op.name, apply), donate_argnums=donated(0, 1))


def named(name: str, fn: Callable) -> Callable:
    """``fn`` under the name ``name``: ``jax.jit`` calls the XLA module it
    compiles ``jit_<name>``, so a profile shows which program ran.  The
    traced computation is ``fn``'s own."""

    @functools.wraps(fn)
    def program(*args):
        return fn(*args)

    program.__name__ = program.__qualname__ = name
    return program


def donated(*argnums: int):
    """``donate_argnums`` where donation is real (TPU/GPU); the CPU
    backend ignores donation and warns, so it gets none."""
    return argnums if jax.default_backend() in ("tpu", "gpu") else ()


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_BACKENDS: Dict[str, Type[ExecutionBackend]] = {}


def register_backend(cls: Type[ExecutionBackend]):
    """Class decorator: register under ``cls.name``."""
    if not cls.name or cls.name == "base":
        raise ValueError(f"{cls.__name__} needs a unique .name")
    _BACKENDS[cls.name] = cls
    return cls


def get_backend_cls(name: str) -> Type[ExecutionBackend]:
    if name not in _BACKENDS:
        raise KeyError(
            f"unknown backend '{name}'; available: {available_backends()}")
    return _BACKENDS[name]


def make_backend(name: str, **kw) -> ExecutionBackend:
    return get_backend_cls(name)(**kw)


def available_backends() -> List[str]:
    return sorted(_BACKENDS)


def resolve_backend(backend) -> ExecutionBackend:
    """None -> default VmapBackend; str -> registry; instance -> itself."""
    if backend is None:
        backend = "vmap"
    if isinstance(backend, str):
        return make_backend(backend)
    if not isinstance(backend, ExecutionBackend):
        raise TypeError(f"expected backend name or ExecutionBackend, "
                        f"got {type(backend).__name__}")
    return backend
