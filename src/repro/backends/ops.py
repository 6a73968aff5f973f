"""CollectiveOp IR — the declarative communication layer (DESIGN.md §8).

What a sync actually *sends* used to live in three hand-synchronized
places: the backend's named program builders, the ``PROGRAM_COMM`` table in
``backends/base.py``, and the per-collective latency hops in
``core/comm_model.py``.  This module replaces the first two with one typed
descriptor: a ``CollectiveOp`` names the collective kind, the wire format
of the payload, the participating group, and whether the exchange may
*overlap* compute.  Everything downstream derives from the descriptor:

* **lowering** — ``ExecutionBackend.lower(op, ...)`` turns a descriptor
  into a compiled device program (``_lower_<name>`` builders on each
  backend), wrapped so every invocation is priced;
* **pricing**  — ``op.wire_bytes(n_params, n_nodes, n_tensors)`` is the
  single source of bytes for ``SimulatedClock`` / ``comm_model``: a ring
  exchange of the wire-format payload, ``2(n−1)/n × payload`` per node
  (``f32``: 4 bytes/component; ``qsgd_int8{bits}``: ``bits/8`` per
  component plus the per-tensor norm side-channel);
* **latency**  — ``op.collective`` keys ``comm_model.COLLECTIVE_HOPS``
  (all_reduce = 2(n−1) hops, gather_bcast unreduced, inner_mean prices
  the group);
* **overlap**  — ``overlap=True`` ops dispatch asynchronously and return
  an ``InFlightOp`` handle; the caller fetches the results later (DaSGD's
  delayed correction), and the clock records the exchange off the step
  path.

Strategies emit these descriptors (``CommunicationStrategy.sync_op`` /
``step_op``) and hand them to the backend; accounting hooks price the same
descriptors, so the bytes a benchmark reports are the bytes the lowered
program models — one truth, not three tables.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Optional

from jax.profiler import TraceAnnotation

# ---------------------------------------------------------------------------
# Wire formats
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class WireFormat:
    """How one parameter component travels: ``f32`` (4 bytes) or
    ``qsgd_int8`` (``bits/8`` bytes of stochastic-quantization levels plus a
    per-tensor f32 norm side-channel — ``norm_bytes_per_tensor = 0`` keeps
    the paper's §IV accounting, which treats the norms as negligible)."""

    kind: str = "f32"               # "f32" | "qsgd_int8"
    bits: int = 32                  # bits per component on the wire
    norm_bytes_per_tensor: int = 0  # side-channel bytes (qsgd norms)


F32 = WireFormat()


def qsgd_wire(bits: int, *, norms: bool = True) -> WireFormat:
    """QSGD levels: ``bits``-bit components (+ 4-byte per-tensor norms when
    ``norms`` — the byte-true anchor-delta exchange counts them; the
    every-step gradient baseline keeps the paper's levels-only charge)."""
    return WireFormat("qsgd_int8", int(bits), 4 if norms else 0)


# ---------------------------------------------------------------------------
# The op descriptor
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class CollectiveOp:
    """One backend program, declaratively.

    ``name`` doubles as the Timeline/program key; ``collective`` is a
    ``comm_model.COLLECTIVE_HOPS`` kind (None = no cross-replica exchange);
    ``is_step`` programs charge per-step compute on a ``SimulatedClock``;
    ``group`` restricts the exchange to that many replicas (hierarchical
    inner mean — pricing then sees the group, never the world); ``overlap``
    ops dispatch without blocking the step path and return an
    ``InFlightOp``."""

    name: str
    collective: Optional[str] = None
    is_step: bool = False
    wire: WireFormat = field(default_factory=WireFormat)
    group: Optional[int] = None
    overlap: bool = False

    # ------------------------------------------------------------- pricing
    def payload_bytes(self, n_params: int, n_tensors: int = 0) -> float:
        """Bytes one node puts on the wire per event."""
        return (n_params * self.wire.bits / 8.0
                + n_tensors * self.wire.norm_bytes_per_tensor)

    def wire_bytes(self, n_params: int, n_nodes: int,
                   n_tensors: int = 0) -> float:
        """Per-node bytes moved by one invocation over ``n_nodes`` — a
        bandwidth-optimal ring moves ``2(n−1)/n`` of the payload per node
        (Patarasuk-Yuan; ``comm_model.ring_allreduce_bytes`` is the f32
        special case).  0 for collective-free ops."""
        if self.collective is None or n_nodes <= 1:
            return 0.0
        return (2.0 * (n_nodes - 1) / n_nodes
                * self.payload_bytes(n_params, n_tensors))


# ---------------------------------------------------------------------------
# Canonical ops — the vocabulary strategies emit
# ---------------------------------------------------------------------------


def replica_step_op() -> CollectiveOp:
    """Independent local SGD step per replica; zero replica-axis
    collectives (Algorithm 1 lines 3-4)."""
    return CollectiveOp("replica_step", None, is_step=True)


def full_step_op() -> CollectiveOp:
    """FULLSGD: gradients ring-all-reduced every step."""
    return CollectiveOp("full_step", "all_reduce", is_step=True)


def qsgd_step_op(bits: int) -> CollectiveOp:
    """QSGD baseline: quantized gradients every step.  Levels are not
    ring-reducible -> gather+broadcast, latency NOT reduced (paper §IV);
    the paper's accounting charges bits/32 of the volume, norms excluded."""
    return CollectiveOp("qsgd_step", "gather_bcast", is_step=True,
                        wire=qsgd_wire(bits, norms=False))


def all_mean_op() -> CollectiveOp:
    """The replica parameter mean + variance probe S_k (Algorithm 2
    lines 10-11) — one full-precision ring all-reduce."""
    return CollectiveOp("all_mean", "all_reduce")


def opt_mean_op() -> CollectiveOp:
    """Optimizer-state mean across replicas (sync_momentum knob)."""
    return CollectiveOp("opt_mean", "all_reduce")


def quantized_all_mean_op(bits: int) -> CollectiveOp:
    """Byte-true QSGD anchor-delta exchange: int8 levels + per-tensor
    norms are all-gathered and dequantized at the receiver, so the wire
    carries ~bits/32 of the f32 volume plus the norm side-channel."""
    return CollectiveOp("quantized_all_mean", "gather_bcast",
                        wire=qsgd_wire(bits))


def inner_mean_op(group_size: int) -> CollectiveOp:
    """Hierarchical in-group (in-pod) partial average: a ring within one
    group of ``group_size`` replicas — priced on the group, never the
    world, and on the fast intra-pod link."""
    return CollectiveOp("inner_mean", "inner_mean", group=int(group_size))


def mean_delta_op(*, overlap: bool = False) -> CollectiveOp:
    """DaSGD correction snapshot ``w̄ − w_i`` (the only collective of the
    pair).  ``overlap=True`` dispatches it asynchronously: the caller gets
    an ``InFlightOp`` and fetches ``delay`` steps later."""
    return CollectiveOp("mean_delta", "all_reduce", overlap=overlap)


def apply_delta_op() -> CollectiveOp:
    """Collective-free elementwise add of a previously fetched delta."""
    return CollectiveOp("apply_delta", None)


# ---------------------------------------------------------------------------
# In-flight handle for overlap ops
# ---------------------------------------------------------------------------


class InFlightOp:
    """A dispatched ``overlap=True`` collective whose results have not been
    fetched.  ``fetch()`` returns the program outputs, charging any
    remaining (un-overlapped) communication to the bound clock exactly
    once; jax's async dispatch keeps the device busy in between, so the
    step path never blocked on the exchange.  Each fetch runs inside the
    profiler span ``repro.program.<op.name>.fetch``, its ``step`` stat the
    iteration that ``step()`` reports at fetch time."""

    def __init__(self, op: CollectiveOp, outputs, clock=None, record=None,
                 step: Callable[[], int] = lambda: 0):
        self.op = op
        self._outputs = outputs
        self._clock = clock
        self._record = record
        self._step = step
        self.fetched = False

    def fetch(self):
        with TraceAnnotation(f"repro.program.{self.op.name}.fetch",
                             step=self._step()):
            if not self.fetched:
                self.fetched = True
                if self._clock is not None:
                    self._clock.complete_async(self.op.name, self._record,
                                               self._outputs)
            return self._outputs
