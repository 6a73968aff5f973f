"""The trainer's profiler spans and program names (DESIGN.md §6,
"Tracing"): the span tree of an engine run, the ``bytes`` counter on each
program span, the numbers left untouched by an active trace, and the XLA
module ``jit_<op.name>`` of every lowered program on vmap and on a
4-device mesh.

The profiler is one per process, so every test that records a trace lives
in this file."""
import glob
import os
import subprocess
import sys
from collections import Counter, defaultdict

import jax
import numpy as np
import pytest

from repro.backends.ops import all_mean_op
from repro.configs import AveragingConfig
from repro.data.pipeline import SyntheticImages
from repro.models.cnn import cnn_loss, init_cnn
from repro.optim import get_optimizer, make_lr_schedule
from repro.runtime.clock import SimulatedClock, WallClock
from repro.runtime.engine import Callback, TrainerEngine

STEPS = 10
REPLICAS = 4


class Marker(Callback):
    """A callback that does nothing: only its spans are looked at."""


@pytest.fixture(scope="module")
def setup():
    data = SyntheticImages(n_samples=128, seed=0)
    params0 = init_cnn(jax.random.PRNGKey(0), widths=(4, 8))
    lr_fn = make_lr_schedule("step", 0.05, STEPS, decay_steps=(6,))
    return data, params0, lr_fn


def make_engine(setup, method="adpsgd", clock=None, callbacks=(), **cfg_kw):
    data, params0, lr_fn = setup
    cfg = AveragingConfig(**dict(dict(
        method=method, p_init=2, p_const=4, k_sample_frac=0.25,
        warmup_full_sync_steps=2), **cfg_kw))
    return TrainerEngine(
        loss_fn=cnn_loss, optimizer=get_optimizer("momentum"),
        params0=params0, n_replicas=REPLICAS,
        data_fn=data.batches(n_replicas=REPLICAS, per_replica_batch=4),
        lr_fn=lr_fn, avg_cfg=cfg, total_steps=STEPS, clock=clock,
        callbacks=callbacks)


def traced(engine, tmp_path):
    """Run ``engine`` under a profiler trace; return its history and the
    ``repro.*`` host spans as (name, start_ns, end_ns, stats)."""
    with jax.profiler.trace(str(tmp_path)):
        hist = engine.run()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    spans = []
    for line in pd.find_plane_with_name("/host:CPU").lines:
        for e in line.events:
            if e.name.startswith("repro."):
                spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                              dict(e.stats)))
    return hist, sorted(spans, key=lambda s: s[1])


def _numbers(hist):
    return (hist.losses, hist.s_k, hist.sync_steps, hist.period_history)


def test_span_tree_of_an_adpsgd_run(setup, tmp_path):
    engine = make_engine(setup, callbacks=[Marker()])
    hist, spans = traced(engine, tmp_path)
    iters = [s for s in spans if s[0] == "repro.iteration"]
    assert [s[3]["step_num"] for s in iters] == list(range(STEPS))
    assert [s[3]["step"] for s in iters] == list(range(STEPS))
    # on_run_end's span follows the last iteration; every other span lies
    # inside its iteration and carries its step
    run_end = spans[-1]
    assert run_end[0] == "repro.callback.Marker"
    assert run_end[1] >= iters[-1][2]
    children = defaultdict(Counter)
    for name, s, e, st in spans[:-1]:
        if name == "repro.iteration":
            continue
        _, s0, e0, _ = iters[st["step"]]
        assert s0 <= s and e <= e0, name
        children[st["step"]][name] += 1
    sync = set(hist.sync_steps)
    for k in range(STEPS):
        # the iteration's key, then one per dispatched program
        want = {"repro.input": 1, "repro.keys": 2 + (k in sync),
                "repro.program.replica_step": 1, "repro.readback.loss": 1,
                # on_step_end and on_iteration_end, and on_sync at a sync
                "repro.callback.Marker": 2 + (k in sync)}
        if k in sync:
            want.update({"repro.program.all_mean": 1,
                         "repro.readback.s_k": 1})
        assert dict(children[k]) == want, k
    # one program span per dispatch, one all_mean per recorded sync
    means = [s for s in spans if s[0] == "repro.program.all_mean"]
    assert [s[3]["step"] for s in means] == hist.sync_steps
    assert len(means) == hist.n_syncs and hist.n_syncs > 2
    # the bytes stat is the op's own price of the exchange
    leaves = jax.tree_util.tree_leaves(engine.W)
    n_params = sum(x.size for x in leaves) // REPLICAS
    want = all_mean_op().wire_bytes(n_params, REPLICAS,
                                    n_tensors=len(leaves))
    assert want > 0
    assert {s[3]["bytes"] for s in means} == {want}
    steps = [s for s in spans if s[0] == "repro.program.replica_step"]
    assert {s[3]["bytes"] for s in steps} == {0}


def test_numbers_identical_with_and_without_a_trace(setup, tmp_path):
    plain = make_engine(setup).run()
    hist, spans = traced(make_engine(setup), tmp_path)
    assert spans
    assert _numbers(hist) == _numbers(plain)
    for a, b in zip(jax.tree_util.tree_leaves(hist.final_W),
                    jax.tree_util.tree_leaves(plain.final_W)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_program_spans_price_like_the_clock(setup, tmp_path):
    clock = SimulatedClock("10gbps")
    hist, spans = traced(make_engine(setup, clock=clock), tmp_path)
    progs = [s for s in spans if s[0].startswith("repro.program.")]
    recs = clock.timeline.records
    assert [s[0][len("repro.program."):] for s in progs] \
        == [r.name for r in recs]
    assert [s[3]["bytes"] for s in progs] == [r.bytes for r in recs]
    assert [s[3]["step"] for s in progs] == [r.step for r in recs]


def test_deferred_loss_reads_back_once(setup, tmp_path):
    clock = WallClock(sample_every=4)
    assert clock.defer_loss_readback
    hist, spans = traced(make_engine(setup, clock=clock), tmp_path)
    (rb,) = [s for s in spans if s[0] == "repro.readback.loss"]
    assert rb[3]["step"] == STEPS - 1
    assert rb[1] >= max(s[2] for s in spans if s[0] == "repro.iteration")
    assert len(hist.losses) == STEPS
    assert all(isinstance(v, float) for v in hist.losses)


def test_overlapped_exchange_has_a_fetch_span(setup, tmp_path):
    hist, spans = traced(make_engine(setup, method="dasgd", dasgd_delay=1),
                         tmp_path)
    fetches = [s for s in spans
               if s[0] == "repro.program.mean_delta.fetch"]
    snaps = [s for s in spans if s[0] == "repro.program.mean_delta"]
    # every settled exchange was fetched once; the warm-up syncs are
    # immediate all_means, and the last snapshot may still be in flight
    means = [s for s in spans if s[0] == "repro.program.all_mean"]
    assert fetches and len(fetches) == len(hist.s_k) - len(means)
    assert len(snaps) - len(fetches) in (0, 1)
    # each fetch comes in a later iteration than its snapshot
    assert all(f[3]["step"] > s[3]["step"] for f, s in zip(fetches, snaps))
    assert all(s[3]["bytes"] > 0 for s in snaps)
    assert "bytes" not in fetches[0][3]


_NAMES_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, jax.numpy as jnp
assert len(jax.devices()) == 4, jax.devices()
from repro.backends import make_backend
from repro.core import averaging as avg
from repro.data.pipeline import SyntheticImages
from repro.models.cnn import cnn_loss, init_cnn
from repro.optim import get_optimizer

R = 4
params = init_cnn(jax.random.PRNGKey(0), widths=(4, 8))
batch = SyntheticImages(n_samples=64, seed=0).batches(
    n_replicas=R, per_replica_batch=2)(0)
opt = get_optimizer("adamw")
key = jax.random.PRNGKey(1)
for bk in ("vmap", "mesh"):
    b = make_backend(bk)
    b.bind(R)
    W = b.put_params(avg.stack_replicas(params, R))
    o = b.init_opt_state(opt, W)
    anchor = b.put_replicated(jax.tree_util.tree_map(
        lambda x: x[0].astype(jnp.float32), W))
    delta = jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape), W)
    progs = [
        ("replica_step", b.replica_step(cnn_loss, opt), (W, o, batch, 0.1)),
        ("full_step", b.full_step(cnn_loss, opt), (W, o, batch, 0.1)),
        ("qsgd_step", b.qsgd_step(cnn_loss, opt, 4), (W, o, batch, 0.1, key)),
        ("all_mean", b.all_mean(), (W, o)),
        ("inner_mean", b.inner_mean(2), (W,)),
        ("opt_mean", b.opt_mean(), (o,)),
        ("quantized_all_mean", b.quantized_all_mean(4), (W, anchor, key)),
        ("mean_delta", b.mean_delta(), (W,)),
        ("apply_delta", b.apply_delta(), (W, delta)),
    ]
    for name, prog, args in progs:
        fn = prog.__wrapped__
        if hasattr(fn, "lower"):
            lowered = [(name, fn.lower(*args))]
        else:
            # the mesh builds its jitted shard_map on the first call
            before = set(b._cache)
            prog(*args)
            lowered = []
            for k in set(b._cache) - before:
                a = (({"loss": jnp.zeros(R)},) if k[0] == "metrics_mean"
                     else args)
                lowered.append((k[0], b._cache[k].lower(*a)))
        assert lowered, (bk, name)
        for kind, low in lowered:
            head = low.as_text().splitlines()[0]
            want = "metrics_mean" if kind == "metrics_mean" else name
            assert head.startswith(f"module @jit_{want} "), (bk, name, head)
            print(bk, head.split()[1])
print("NAMES OK")
"""


def test_every_program_compiles_to_jit_op_name():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep \
        + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", _NAMES_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "NAMES OK" in r.stdout
    names = set(r.stdout.split())
    assert "@jit_metrics_mean" in names
    assert not any(n in names for n in ("@jit__lambda", "@jit_chunk"))
