"""Strategy-agnostic training engine.

``TrainerEngine`` owns the training state (replica-stacked parameters W,
optimizer state, history) and the iteration loop; *everything*
method-specific lives in the ``CommunicationStrategy`` it is given (see
``repro/strategies/base.py``), and everything device-specific in the
``ExecutionBackend`` the strategy compiles against
(``repro/backends/base.py`` — vmap on one host device, or shard_map over a
real mesh).  Per iteration the engine asks the strategy which pre-compiled
programs to dispatch (``strategy.actions(k)``), runs them, and routes their
outputs:

* ``info["loss"]``       -> training-loss sample
* ``info["s_k"]``        -> a sync happened: feed ``strategy.observe`` and
                            record the probe / period trajectory
* ``info["s_k_at"]``     -> ``(step, s_k)``: a sync whose probe was fetched
                            *later* than it was measured (DaSGD's overlapped
                            snapshot) — recorded against its snapshot step
* ``info["inner_sync"]`` -> hierarchical inner-sync marker

A small callback bus hangs off the loop (variance probing, periodic eval,
checkpointing); callbacks never influence the dispatch decision, so the
control path stays as lean as the seed loop's.

RNG keys are derived statelessly (``fold_in(base, k); fold_in(·, j)``), so a
checkpoint-resumed run replays the identical key stream from any step.

Every iteration is a profiler step span ``repro.iteration`` holding the spans
``repro.input`` (the ``data_fn`` call), ``repro.keys`` (the key derivation),
``repro.program.<op>`` (each program call, ``backends/base.py``),
``repro.readback.loss`` / ``repro.readback.s_k`` (the host reads of the loss
and the sync probe) and ``repro.callback.<class>`` (each callback call); each
carries the stat ``step=k``.  They cost about a microsecond each when no
profiler trace is active (DESIGN.md §6, "Tracing").
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.backends import ExecutionBackend, resolve_backend
from repro.configs.base import AveragingConfig
from repro.core import averaging as avg
from repro.runtime.clock import Clock, Timeline
from repro.strategies import CommunicationStrategy, make_strategy

Pytree = Any


@dataclass
class TrainHistory:
    method: str
    losses: List[float] = field(default_factory=list)
    variances: List[float] = field(default_factory=list)       # Var[W_k] samples
    variance_steps: List[int] = field(default_factory=list)
    s_k: List[float] = field(default_factory=list)             # probe at syncs
    sync_steps: List[int] = field(default_factory=list)
    period_history: List[int] = field(default_factory=list)
    inner_sync_steps: List[int] = field(default_factory=list)  # hierarchical
    lrs: List[float] = field(default_factory=list)
    lr_start_step: int = 0        # absolute step of lrs[0] (resumed runs)
    evals: List[Dict[str, float]] = field(default_factory=list)
    eval_steps: List[int] = field(default_factory=list)
    n_syncs: int = 0
    # telemetry (runtime/clock.py): Timeline.summary() of the run when the
    # engine carried a clock — measured (wall) or simulated per-program
    # compute/comm seconds and modeled bytes; None on un-clocked runs
    timing: Optional[Dict[str, Any]] = None
    final_W: Optional[Pytree] = None
    final_opt: Optional[Pytree] = None

    def weighted_avg_variance(self) -> float:
        """Paper Eq. 9: Σ γ_k Var[W_k] / Σ γ_j over the sampled steps."""
        if not self.variances:
            return 0.0
        idx = np.clip(np.array(self.variance_steps) - self.lr_start_step,
                      0, len(self.lrs) - 1)
        g = np.array(self.lrs)[idx]
        return float(np.sum(g * np.array(self.variances)) / np.sum(g))


# ---------------------------------------------------------------------------
# Callback bus
# ---------------------------------------------------------------------------


class Callback:
    """Hook points on the engine loop.  Override what you need.

    ``on_step_end`` fires after the step program but *before* any sync of
    the same iteration — the place to observe pre-sync replica drift (paper
    Fig 1/2).  ``on_iteration_end`` fires once all of iteration k's
    programs ran — the place for anything that must see a consistent
    (post-sync) snapshot, e.g. checkpointing or eval."""

    def on_step_end(self, engine: "TrainerEngine", k: int,
                    metrics: Dict[str, Any]) -> None:
        """On clocked runs ``metrics["timing"]`` carries the step program's
        ``ProgramTiming`` (compute_s/comm_s/bytes — runtime/clock.py)."""
        pass

    def on_sync(self, engine: "TrainerEngine", k: int, s_k: float,
                timing=None) -> None:
        """``timing`` is the sync program's ``ProgramTiming`` on clocked
        runs (None otherwise) — comm_s/bytes of this exchange."""
        pass

    def on_iteration_end(self, engine: "TrainerEngine", k: int,
                         metrics: Dict[str, Any]) -> None:
        pass

    def on_run_end(self, engine: "TrainerEngine") -> None:
        pass


class VarianceProbe(Callback):
    """Sample Var[W_k] (paper Eq. 7 / Fig 1-2) every ``every`` steps."""

    def __init__(self, every: int):
        self.every = max(1, every)
        self._fn = jax.jit(avg.parameter_variance)

    def on_step_end(self, engine, k, metrics):
        if k % self.every == 0:
            engine.history.variances.append(float(self._fn(engine.W)))
            engine.history.variance_steps.append(k)


class PeriodicEval(Callback):
    """Evaluate the replica-averaged model every ``every`` steps."""

    def __init__(self, loss_fn, batches_fn: Callable[[], Iterable],
                 every: int):
        self.loss_fn = loss_fn
        self.batches_fn = batches_fn
        self.every = max(1, every)

    def on_iteration_end(self, engine, k, metrics):
        if (k + 1) % self.every == 0:
            ev = evaluate(self.loss_fn, engine.W, self.batches_fn())
            engine.history.evals.append(ev)
            engine.history.eval_steps.append(k)


class Checkpointer(Callback):
    """Save (W, opt_state, strategy state) every ``every`` steps, so a
    restored run continues the identical sync schedule (DESIGN.md §4).

    ``keep_replicas=False`` collapses W to the replica mean — an *export*
    checkpoint for serving/eval, not resumable through
    ``TrainerEngine.load_state`` (which needs the stacked replica axis)."""

    def __init__(self, path: str, every: int, keep_replicas: bool = True):
        self.path = path
        self.every = max(1, every)
        self.keep_replicas = keep_replicas

    def on_iteration_end(self, engine, k, metrics):
        # must run after any sync of iteration k: the saved W has to be
        # consistent with the saved (post-observe) strategy state
        if (k + 1) % self.every == 0:
            self.save(engine, k + 1)

    def save(self, engine: "TrainerEngine", step: int) -> None:
        from repro.checkpoint.io import save_checkpoint, strategy_state
        W = engine.W if self.keep_replicas else avg.replica_mean(engine.W)
        # export checkpoints drop the (replica-stacked) optimizer state too
        opt = engine.opt_state if self.keep_replicas else None
        save_checkpoint(self.path, W, opt_state=opt, step=step,
                        controller_state=strategy_state(engine.strategy),
                        clock_state=(engine.clock.state_dict()
                                     if engine.clock else None))


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


class TrainerEngine:
    """Owns state + loop; the strategy owns policy + programs."""

    def __init__(self, *,
                 loss_fn,
                 optimizer,
                 params0: Optional[Pytree] = None,
                 n_replicas: int = 1,
                 data_fn: Callable[[int], Dict[str, jnp.ndarray]],
                 lr_fn: Callable[[int], float],
                 total_steps: int,
                 avg_cfg: Optional[AveragingConfig] = None,
                 strategy: Optional[CommunicationStrategy] = None,
                 backend: Optional[ExecutionBackend] = None,
                 clock: Optional[Clock] = None,
                 callbacks: Sequence[Callback] = (),
                 track_variance_every: int = 0,
                 seed: int = 0):
        if strategy is None:
            if avg_cfg is None:
                raise ValueError("need avg_cfg or strategy")
            strategy = make_strategy(avg_cfg, total_steps)
        elif avg_cfg is not None and avg_cfg != strategy.cfg:
            # a conflicting avg_cfg would retune the programs but not the
            # already-constructed schedule state — refuse the drift
            raise ValueError(
                "avg_cfg conflicts with the explicit strategy's config; "
                "pass one or the other (or matching configs)")
        self.backend = resolve_backend(backend)   # name, instance, or None
        self.backend.bind(n_replicas)
        # telemetry: the clock rides the backend (every program the backend
        # builds is a timed wrapper) and its Timeline rides the engine
        self.clock = clock
        self.timeline: Optional[Timeline] = clock.timeline if clock else None
        # unconditional: clock=None must also *clear* any clock a previous
        # engine left bound on a reused backend instance
        self.backend.set_clock(clock)
        self.strategy = strategy
        self.strategy.compile(loss_fn, optimizer, backend=self.backend)
        self.strategy.bind_clock(clock)
        self._optimizer = optimizer
        self._n_replicas = n_replicas
        self.loss_fn = loss_fn
        self.data_fn = data_fn
        self.lr_fn = lr_fn
        self.total_steps = total_steps
        self.callbacks: List[Callback] = list(callbacks)
        if track_variance_every:
            self.callbacks.append(VarianceProbe(track_variance_every))
        self._base_key = jax.random.PRNGKey(seed + 17)
        self._comm_event_base = 0      # restored events don't count in
        self.history = TrainHistory(method=self.strategy.name)  # this history
        self.W: Optional[Pytree] = None
        self.opt_state: Optional[Pytree] = None
        if params0 is not None:
            self.W = self.backend.put_params(
                avg.stack_replicas(params0, n_replicas))
            self.opt_state = self.backend.init_opt_state(optimizer, self.W)

    # ------------------------------------------------------------------
    def load_state(self, W: Pytree, opt_state: Optional[Pytree] = None,
                   strategy_state: Optional[Dict] = None,
                   clock_state: Optional[Dict] = None) -> None:
        """Install checkpointed state (replica-stacked W) for resume.
        Export checkpoints (``Checkpointer(keep_replicas=False)``) lack the
        replica axis and are rejected.  State is re-``put`` through the
        active backend, so a checkpoint saved under one backend (vmap)
        resumes under another (mesh) and vice versa — ``checkpoint/io.py``
        always saves host arrays.  ``opt_state=None`` keeps the engine's
        freshly-initialized optimizer state — the schedule still resumes
        exactly, but stateful optimizers (momentum/adamw) restart from
        zero, so the loss trajectory is not bit-identical."""
        got = [tuple(np.shape(x)) for x in jax.tree_util.tree_leaves(W)]
        if self.W is not None:
            want = [x.shape for x in jax.tree_util.tree_leaves(self.W)]
        else:
            # no params0 reference: every leaf must still lead with the
            # replica axis this engine was constructed for
            want = [(self._n_replicas,) + s[1:] for s in got]
        if want != got:
            raise ValueError(
                "checkpoint does not match the engine's replica-stacked "
                "state (was it saved with keep_replicas=False? such "
                f"checkpoints are export-only): {got[:1]} vs {want[:1]}")
        self.W = self.backend.put_params(W)
        if opt_state is not None:
            self.opt_state = self.backend.put_opt(opt_state, self.W)
        elif self.opt_state is None:
            # checkpoint without opt_state on a params0-less engine: give
            # the run a fresh optimizer state (see docstring caveat)
            self.opt_state = self.backend.init_opt_state(
                self._optimizer, self.W)
        # clock before strategy: the restored controller's block-start is in
        # clock coordinates, so the clock must already tick from the saved
        # time when time-driven policies resume (mid-block schedules)
        if clock_state is not None and self.clock is not None:
            self.clock.load_state_dict(clock_state)
        if strategy_state is not None:
            from repro.checkpoint.io import restore_strategy
            restore_strategy(self.strategy, strategy_state)
        # keep n_syncs per-history: syncs before the restore belong to the
        # saved run's history, not this one
        self._comm_event_base = self.strategy.n_comm_events

    # ------------------------------------------------------------------
    def run(self, start_step: int = 0,
            num_steps: Optional[int] = None) -> TrainHistory:
        """Run iterations [start_step, start_step + num_steps).  Call again
        with the next ``start_step`` to continue (or resume after a
        restore) — the strategy's schedule state carries across calls."""
        if self.W is None:
            raise RuntimeError("no parameters: pass params0 or load_state()")
        stop = self.total_steps if num_steps is None \
            else min(self.total_steps, start_step + num_steps)
        hist = self.history
        if not hist.lrs:
            hist.lr_start_step = start_step
        tl = self.timeline
        # a sampled WallClock asks to keep the dispatch pipeline async:
        # per-step float(loss) read-back would re-sync it every iteration,
        # so losses stay device scalars until run end (values identical)
        defer_loss = bool(getattr(self.clock, "defer_loss_readback", False))

        def callback(hook: str, k: int, *args):
            for cb in self.callbacks:
                with TraceAnnotation(f"repro.callback.{type(cb).__name__}",
                                     step=k):
                    getattr(cb, hook)(self, *args)

        def record_sync(k, at, lr_at, s_val, timing):
            """One sync event into history + controller + callbacks —
            shared by the immediate ("s_k") and the overlapped-settlement
            ("s_k_at") paths so they can never drift apart."""
            with TraceAnnotation("repro.readback.s_k", step=k):
                s_k = float(s_val)
            self.strategy.observe(at, lr_at, s_k)
            hist.s_k.append(s_k)
            hist.sync_steps.append(at)
            hist.period_history.append(self.strategy.period)
            callback("on_sync", k, at, s_k, timing)

        for k in range(start_step, stop):
            with StepTraceAnnotation("repro.iteration", step_num=k, step=k):
                lr = self.lr_fn(k)
                hist.lrs.append(lr)
                with TraceAnnotation("repro.input", step=k):
                    batch = self.data_fn(k)
                with TraceAnnotation("repro.keys", step=k):
                    step_key = jax.random.fold_in(self._base_key, k)
                step_info: Dict[str, Any] = {}
                if tl is not None:
                    tl.step = k      # dispatches below stamp this iteration
                self.backend.step = k
                for j, action in enumerate(self.strategy.actions(k)):
                    with TraceAnnotation("repro.keys", step=k):
                        key = jax.random.fold_in(step_key, j)
                    self.W, self.opt_state, info = self.strategy.dispatch(
                        action, self.W, self.opt_state, batch, lr, key)
                    timing = tl.last if tl is not None else None
                    if "loss" in info:
                        step_info = info
                        if defer_loss:
                            loss_val = info["loss"]
                        else:
                            with TraceAnnotation("repro.readback.loss",
                                                 step=k):
                                loss_val = float(info["loss"])
                        hist.losses.append(loss_val)
                        self.strategy.observe_loss(k, loss_val)
                        if timing is not None:
                            info["timing"] = timing
                        callback("on_step_end", k, k, info)
                    if "s_k" in info:
                        record_sync(k, k, lr, info["s_k"], timing)
                    if "s_k_at" in info:
                        # an overlapped sync settled: the probe belongs to
                        # the snapshot iteration, not the fetch iteration —
                        # there is at most one exchange in flight (delay <
                        # period), so ordering within the history is
                        # preserved
                        at, s_val = info["s_k_at"]
                        at = int(at)
                        if tl is not None:
                            # on_sync's contract is the *exchange's* record
                            # (comm_s/bytes), which was written at dispatch
                            # — not the apply program's that tl.last holds
                            timing = next(
                                (r for r in reversed(tl.records)
                                 if r.overlap and r.step == at), timing)
                        record_sync(k, at, self.lr_fn(at), s_val, timing)
                    if info.get("inner_sync"):
                        hist.inner_sync_steps.append(k)
                callback("on_iteration_end", k, k, step_info)
        if defer_loss:
            with TraceAnnotation("repro.readback.loss", step=stop - 1):
                hist.losses[:] = [float(v) for v in hist.losses]
        hist.n_syncs = self.strategy.n_comm_events - self._comm_event_base
        if tl is not None:
            hist.timing = dict(tl.summary(), clock=self.clock.kind,
                               sim_wall_s=self.clock.now())
        hist.final_W = self.W
        hist.final_opt = self.opt_state
        callback("on_run_end", stop - 1)
        return hist


def evaluate(loss_fn, W: Pytree, batches) -> Dict[str, float]:
    """Evaluate the replica-averaged model."""
    params = avg.replica_mean(W)
    f = jax.jit(loss_fn)
    tot: Dict[str, float] = {}
    n = 0
    for b in batches:
        _, aux = f(params, b)
        for kk, v in aux.items():
            tot[kk] = tot.get(kk, 0.0) + float(v)
        n += 1
    return {k: v / max(n, 1) for k, v in tot.items()}
