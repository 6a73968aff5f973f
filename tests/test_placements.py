"""Placement x strategy matrix harness (DESIGN.md §5 "Placements").

Since PR 2 a behavior cell is (strategy, backend, placement); hand-written
parity tests stopped scaling at the backend layer.  This module asserts,
for **every registered strategy**, that `mesh`+`replica_ddp` and
`mesh`+`replica_tp` reproduce the `vmap` baseline — losses, the variance
probe S_k, the sync schedule, and the comm-bytes accounting — within float
tolerance, plus the placement-specific invariants (TP sharding actually
lands on the 'model' axis, the local step's HLO carries no replica-axis
collective, checkpoints are placement-neutral, hierarchical groups align
with the pod boundary).

Like tests/test_backends.py it is device-count agnostic: under the default
suite jax sees one CPU device and the meshes degenerate; the `backends-tp`
CI job re-runs it with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
so `replica_tp` runs on a genuine 4 data x 2 model topology.  The
subprocess test forces that topology regardless of the parent's platform
(the acceptance matrix).
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.backends import make_backend
from repro.backends.mesh import PLACEMENTS, MeshBackend
from repro.checkpoint.io import (load_checkpoint, save_checkpoint,
                                 strategy_state)
from repro.configs import AveragingConfig
from repro.core import averaging as avg
from repro.core.comm_model import GBPS_100
from repro.data.pipeline import SyntheticImages
from repro.models.cnn import cnn_loss, init_cnn
from repro.optim import get_optimizer, make_lr_schedule
from repro.runtime.engine import TrainerEngine
from repro.strategies import available_strategies

STEPS = 16
REPLICAS = 8


@pytest.fixture(scope="module")
def setup8():
    data = SyntheticImages(n_samples=256, seed=0)
    params0 = init_cnn(jax.random.PRNGKey(0), widths=(8, 16))
    opt = get_optimizer("momentum")
    lr_fn = make_lr_schedule("step", 0.05, STEPS, decay_steps=(10,))
    return data, params0, opt, lr_fn


def resolve(backend):
    """'vmap' or ('mesh', placement) -> an ExecutionBackend argument."""
    if isinstance(backend, tuple):
        name, placement = backend
        return make_backend(name, placement=placement)
    return backend


def make_engine(setup8, method, backend="vmap", steps=STEPS, **cfg_kw):
    data, params0, opt, lr_fn = setup8
    base = dict(method=method, p_init=2, p_const=4, k_sample_frac=0.25,
                warmup_full_sync_steps=2, inner_period=2, adacomm_interval=8)
    base.update(cfg_kw)
    return TrainerEngine(
        loss_fn=cnn_loss, optimizer=opt, params0=params0,
        n_replicas=REPLICAS,
        data_fn=data.batches(n_replicas=REPLICAS, per_replica_batch=4),
        lr_fn=lr_fn, avg_cfg=AveragingConfig(**base), total_steps=steps,
        backend=resolve(backend))


@pytest.fixture(scope="module")
def vmap_baseline(setup8):
    """One vmap run per strategy, shared by every placement cell."""
    cache = {}

    def get(method):
        if method not in cache:
            e = make_engine(setup8, method)
            cache[method] = (e.run(), e)
        return cache[method]

    return get


# ---------------------------------------------------------------------------
# Placement plumbing
# ---------------------------------------------------------------------------


def test_unknown_placement_rejected():
    with pytest.raises(ValueError, match="placement"):
        MeshBackend(placement="replica_nope")


def test_replica_tp_needs_model_axis():
    mesh = jax.make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="model"):
        MeshBackend(mesh=mesh, placement="replica_tp")


def test_replica_tp_specs_use_model_axis(setup8):
    """The TP placement threads base_spec through put_params: fc/conv
    leaves name the 'model' axis in their sharding (whatever its size)."""
    _, params0, opt, _ = setup8
    b = MeshBackend(placement="replica_tp")
    b.bind(REPLICAS)
    W = b.put_params(avg.stack_replicas(params0, REPLICAS))
    specs = {k: jax.tree_util.tree_map(lambda x: x.sharding.spec, W[k])
             for k in ("fc1", "fc2")}
    assert "model" in specs["fc1"]["w"]          # column-parallel
    assert "model" in specs["fc2"]["w"]          # row-parallel
    entry = specs["fc1"]["w"][0]                 # replica axis leads
    assert entry in ("data", ("pod", "data"))
    # replica_ddp keeps inner dims unsharded
    bd = MeshBackend(placement="replica_ddp")
    bd.bind(REPLICAS)
    Wd = bd.put_params(avg.stack_replicas(params0, REPLICAS))
    assert all(s is None for s in Wd["fc1"]["w"].sharding.spec[1:])


def test_replica_tp_shards_over_8_devices(setup8):
    """Meaningful under the backends-tp CI job (8 forced devices): the
    default replica_tp mesh splits 4 data x 2 model and a TP leaf really
    lands on all 8 devices."""
    if len(jax.devices()) != 8:
        pytest.skip("needs the 8-forced-device CI topology")
    _, params0, opt, _ = setup8
    b = MeshBackend(placement="replica_tp")
    assert dict(b.mesh.shape) == {"data": 4, "model": 2}
    b.bind(REPLICAS)
    W = b.put_params(avg.stack_replicas(params0, REPLICAS))
    assert len(W["fc1"]["w"].sharding.device_set) == 8
    assert not W["fc1"]["w"].sharding.is_fully_replicated


# ---------------------------------------------------------------------------
# The matrix: every registered strategy x every placement vs vmap
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("placement", PLACEMENTS)
@pytest.mark.parametrize("method", available_strategies())
def test_matrix_parity(setup8, vmap_baseline, method, placement):
    hv, ev = vmap_baseline(method)
    em = make_engine(setup8, method, ("mesh", placement))
    hm = em.run()
    assert hm.sync_steps == hv.sync_steps, (method, placement)
    assert hm.period_history == hv.period_history
    assert hm.inner_sync_steps == hv.inner_sync_steps
    assert hm.n_syncs == hv.n_syncs
    np.testing.assert_allclose(hm.losses, hv.losses, rtol=2e-4, atol=1e-5)
    np.testing.assert_allclose(hm.s_k, hv.s_k, rtol=1e-3, atol=1e-5)
    # comm-bytes accounting is placement-independent: same events, same
    # bytes per event through the strategy's own hooks
    _, params0, _, _ = setup8
    n_par = sum(x.size for x in jax.tree_util.tree_leaves(params0))
    cv = ev.strategy.comm_stats(n_par, REPLICAS, STEPS, hv.n_syncs, GBPS_100)
    cm = em.strategy.comm_stats(n_par, REPLICAS, STEPS, hm.n_syncs, GBPS_100)
    assert cm == cv


@pytest.mark.parametrize("placement", PLACEMENTS)
def test_matrix_final_params_match(setup8, vmap_baseline, placement):
    hv, _ = vmap_baseline("adpsgd")
    hm = make_engine(setup8, "adpsgd", ("mesh", placement)).run()
    for a, b in zip(jax.tree_util.tree_leaves(hm.final_W),
                    jax.tree_util.tree_leaves(hv.final_W)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Step metrics off the step path (ROADMAP item)
# ---------------------------------------------------------------------------


def test_replica_step_hlo_has_no_collectives(setup8):
    """The local step's lowered HLO carries zero replica-axis collectives:
    scalar metrics come back per-replica and are reduced by a separate
    program, so skipping a sync skips every cross-replica round."""
    data, params0, opt, _ = setup8
    b = MeshBackend(placement="replica_ddp")
    b.bind(REPLICAS)
    W = b.put_params(avg.stack_replicas(params0, REPLICAS))
    ost = b.init_opt_state(opt, W)
    batch = data.batches(n_replicas=REPLICAS, per_replica_batch=4)(0)
    _, _, metrics = b.replica_step(cnn_loss, opt)(W, ost, batch, 0.05)
    assert np.isfinite(float(metrics["loss"]))   # reduced off the step
    b.all_mean()(W, ost)
    step_fn = next(v for k, v in b._cache.items() if k[0] == "step")
    sync_fn = next(v for k, v in b._cache.items()
                   if k[0].startswith("all_mean"))
    step_hlo = step_fn.lower(W, ost, batch, 0.05).as_text()
    assert "all_reduce" not in step_hlo and "all-reduce" not in step_hlo
    # control: the sync program is where the collective lives
    assert "all_reduce" in sync_fn.lower(W, ost).as_text()


# ---------------------------------------------------------------------------
# Hierarchical groups from the mesh pod boundary (ROADMAP multi-pod item)
# ---------------------------------------------------------------------------


def test_hier_group_size_derived_from_pod_axis():
    """On a 2-pod dry-run mesh, hier_adpsgd's unset group_size resolves to
    replicas-per-pod and the device groups tile the innermost ('data')
    axis — inner syncs never cross the pod boundary."""
    mesh = AbstractMesh((2, 2, 2), ("pod", "data", "model"))
    b = MeshBackend(mesh=mesh, placement="replica_tp")
    b.bind(8)
    assert b.replica_axes == ("pod", "data")
    assert b.n_replica_devices == 4
    assert b.default_group_size() == 4           # 8 replicas / 2 pods
    # a 4-replica group = 2 local replicas x 2 'data' devices of one pod
    assert b._device_groups(2) == [[0, 1]]
    with pytest.raises(NotImplementedError, match="tile"):
        b._device_groups(4)                      # would span the pod axis
    # single-pod meshes have no natural boundary -> strategy heuristic
    b1 = MeshBackend(mesh=AbstractMesh((4, 2), ("data", "model")))
    b1.bind(8)
    assert b1.default_group_size() is None


def test_hier_uses_backend_group_size(setup8, vmap_baseline):
    """group_size=0 resolves through the backend; on pod-less meshes (and
    vmap) both fall back to R//2, so schedules agree with the baseline."""
    hv, _ = vmap_baseline("hier_adpsgd")
    h0 = make_engine(setup8, "hier_adpsgd", ("mesh", "replica_tp"),
                     group_size=0).run()
    hc = make_engine(setup8, "hier_adpsgd", ("mesh", "replica_tp"),
                     group_size=REPLICAS // 2).run()
    assert h0.sync_steps == hc.sync_steps == hv.sync_steps
    assert h0.inner_sync_steps == hc.inner_sync_steps
    np.testing.assert_allclose(h0.losses, hc.losses, rtol=1e-6)


# ---------------------------------------------------------------------------
# Cross-placement checkpoint resume (placement-neutral checkpoints)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("save_bk,resume_bk", [
    ("vmap", ("mesh", "replica_tp")),
    (("mesh", "replica_tp"), "vmap"),
    (("mesh", "replica_ddp"), ("mesh", "replica_tp")),
], ids=["vmap->tp", "tp->vmap", "ddp->tp"])
def test_cross_placement_resume(setup8, vmap_baseline, tmp_path,
                                save_bk, resume_bk):
    """A checkpoint saved under one placement resumes under another and
    continues the sync schedule and loss trajectory of an uninterrupted
    run — checkpoints stay placement-neutral (host arrays, re-put through
    the restoring backend's own specs)."""
    h_full, _ = vmap_baseline("adpsgd")

    half = make_engine(setup8, "adpsgd", save_bk)
    half.run(num_steps=STEPS // 2)
    path = str(tmp_path / "xpl")
    save_checkpoint(path, half.W, opt_state=half.opt_state, step=STEPS // 2,
                    controller_state=strategy_state(half.strategy))

    resumed = make_engine(setup8, "adpsgd", resume_bk)
    W, opt_state, meta = load_checkpoint(path)
    resumed.load_state(W, opt_state, strategy_state=meta["controller"])
    h_res = resumed.run(start_step=STEPS // 2)

    tail = [s for s in h_full.sync_steps if s >= STEPS // 2]
    assert h_res.sync_steps == tail
    if tail:
        assert h_res.period_history == h_full.period_history[-len(tail):]
    np.testing.assert_allclose(h_res.losses, h_full.losses[STEPS // 2:],
                               rtol=2e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Reduced-transformer family parity (ROADMAP item): one config per model
# family under mesh+replica_tp vs the vmap baseline.  The cheapest cells —
# dense and ssm, a few seconds each from nightly timings — run in the
# per-PR tier-1 suite (ROADMAP promotion item); the heavier families stay
# behind the nightly/dispatch `placements-transformer` CI job's
# PLACEMENTS_TRANSFORMER=1 opt-in (with 8 forced host devices).
# ---------------------------------------------------------------------------

TIER1_FAMILIES = ("dense", "ssm")

TRANSFORMER_FAMILIES = [
    ("dense", "olmo-1b"),
    ("moe", "mixtral-8x22b"),
    ("ssm", "xlstm-350m"),
    ("hybrid", "jamba-1.5-large-398b"),
    ("vlm", "qwen2-vl-2b"),
    ("audio", "whisper-medium"),
]
_TF_STEPS, _TF_R, _TF_B, _TF_S = 6, 4, 2, 32


def _family_engine(arch, backend):
    import jax.numpy as jnp
    from repro.configs import get_config, reduced
    from repro.data.pipeline import SyntheticTokens
    from repro.launch.steps import make_loss_fn
    from repro.models import model as M

    run_cfg = get_config(arch)
    cfg = reduced(run_cfg.model, max_seq_len=_TF_S)
    data = SyntheticTokens(cfg.vocab_size, _TF_S, n_samples=64, seed=0)
    base_fn = data.batches(n_replicas=_TF_R, per_replica_batch=_TF_B)
    if cfg.encoder is not None:
        # audio: deterministic per-step frame embeddings (post-frontend
        # stub), identical across backends so parity is meaningful
        def data_fn(k, _base=base_fn):
            b = dict(_base(k))
            rng = np.random.RandomState(1000 + k)
            b["frames"] = jnp.asarray(0.1 * rng.randn(
                _TF_R, _TF_B, cfg.encoder.n_frames,
                cfg.d_model).astype("float32"))
            return b
    else:
        data_fn = base_fn
    if isinstance(backend, tuple):
        # the transformer TP rules need the model config for base_spec
        bk = make_backend(backend[0], placement=backend[1], model_cfg=cfg)
    else:
        bk = backend
    return TrainerEngine(
        loss_fn=make_loss_fn(cfg), optimizer=get_optimizer("momentum"),
        params0=M.init_params(jax.random.PRNGKey(0), cfg),
        n_replicas=_TF_R, data_fn=data_fn, lr_fn=lambda k: 0.01,
        avg_cfg=AveragingConfig(method="adpsgd", p_init=2,
                                warmup_full_sync_steps=2, k_sample_frac=0.5),
        total_steps=_TF_STEPS, backend=bk)


@pytest.mark.parametrize("family,arch", TRANSFORMER_FAMILIES,
                         ids=[f for f, _ in TRANSFORMER_FAMILIES])
def test_transformer_family_parity(family, arch):
    if (family not in TIER1_FAMILIES
            and not os.environ.get("PLACEMENTS_TRANSFORMER")):
        pytest.skip("nightly placements-transformer job "
                    "(set PLACEMENTS_TRANSFORMER=1 to run)")
    hv = _family_engine(arch, "vmap").run()
    hm = _family_engine(arch, ("mesh", "replica_tp")).run()
    assert hm.sync_steps == hv.sync_steps, (family, arch)
    assert hm.period_history == hv.period_history
    np.testing.assert_allclose(hm.losses, hv.losses, rtol=5e-4, atol=1e-5,
                               err_msg=f"{family}/{arch}")
    np.testing.assert_allclose(hm.s_k, hv.s_k, rtol=2e-3, atol=1e-5,
                               err_msg=f"{family}/{arch}")


# ---------------------------------------------------------------------------
# Forced 8-device (4 data x 2 model) acceptance matrix — own interpreter
# because the device count is fixed at first jax init
# ---------------------------------------------------------------------------

_MATRIX8_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, numpy as np
assert len(jax.devices()) == 8, jax.devices()
from repro.backends.mesh import MeshBackend
from repro.configs import AveragingConfig
from repro.data.pipeline import SyntheticImages
from repro.models.cnn import cnn_loss, init_cnn
from repro.optim import get_optimizer, make_lr_schedule
from repro.runtime.engine import TrainerEngine
from repro.strategies import available_strategies

STEPS = 14
data = SyntheticImages(n_samples=256, seed=0)
params0 = init_cnn(jax.random.PRNGKey(0), widths=(8, 16))
opt = get_optimizer("momentum")
lr_fn = make_lr_schedule("step", 0.05, STEPS, decay_steps=(8,))

def run(backend, method):
    cfg = AveragingConfig(method=method, p_init=2, p_const=4,
                          k_sample_frac=0.25, warmup_full_sync_steps=2,
                          inner_period=2, adacomm_interval=8)
    e = TrainerEngine(loss_fn=cnn_loss, optimizer=opt, params0=params0,
                      n_replicas=8,
                      data_fn=data.batches(n_replicas=8, per_replica_batch=4),
                      lr_fn=lr_fn, avg_cfg=cfg, total_steps=STEPS,
                      backend=backend)
    return e.run(), e

for method in available_strategies():
    hv, _ = run("vmap", method)
    hm, em = run(MeshBackend(placement="replica_tp"), method)
    assert dict(em.backend.mesh.shape) == {"data": 4, "model": 2}
    assert em.backend.n_replica_devices == 4
    assert hm.sync_steps == hv.sync_steps, method
    assert hm.period_history == hv.period_history, method
    assert hm.inner_sync_steps == hv.inner_sync_steps, method
    np.testing.assert_allclose(hm.losses, hv.losses, rtol=2e-4, atol=1e-5,
                               err_msg=method)
    np.testing.assert_allclose(hm.s_k, hv.s_k, rtol=1e-3, atol=1e-5,
                               err_msg=method)
    print(method, "OK")

# TP layout is real: a column-parallel leaf spans all 8 devices
_, em = run(MeshBackend(placement="replica_tp"), "adpsgd")
leaf = em.W["fc1"]["w"]
assert "model" in leaf.sharding.spec, leaf.sharding
assert len(leaf.sharding.device_set) == 8

# 2-pod mesh: hier_adpsgd derives its group from the pod boundary and
# matches the vmap schedule (R//2 == replicas-per-pod here by design)
mesh2 = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
hv, _ = run("vmap", "hier_adpsgd")
hp, ep = run(MeshBackend(mesh=mesh2, placement="replica_tp"), "hier_adpsgd")
assert ep.backend.default_group_size() == 4
assert hp.sync_steps == hv.sync_steps
assert hp.inner_sync_steps == hv.inner_sync_steps
np.testing.assert_allclose(hp.losses, hv.losses, rtol=2e-4, atol=1e-5)
print("MATRIX8 OK")
"""


def test_matrix8_subprocess():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep \
        + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", _MATRIX8_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=540)
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "MATRIX8 OK" in r.stdout
