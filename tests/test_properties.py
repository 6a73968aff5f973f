"""Property-based tests (hypothesis) on the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # listed in requirements.txt; optional here
from hypothesis import given, settings, strategies as stf  # noqa: E402
from jax.sharding import AbstractMesh, PartitionSpec as P  # noqa: E402

from repro.configs import AveragingConfig, ModelConfig  # noqa: E402
from repro.configs.base import ParallelismPlan  # noqa: E402
from repro.core import averaging as avg
from repro.core import qsgd
from repro.core.controller import ADPSGDController, ConstantPeriodController
from repro.launch import sharding as sh  # noqa: E402

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")

finite_f = stf.floats(-100, 100, allow_nan=False, width=32)


@given(stf.integers(1, 8), stf.integers(1, 50), stf.randoms())
def test_sync_idempotent(R, dim, rnd):
    W = {"w": jnp.asarray(np.random.RandomState(rnd.randint(0, 2**31))
                          .randn(R, dim).astype(np.float32))}
    W1, _, sk1 = avg.sync_replicas(W)
    W2, _, sk2 = avg.sync_replicas(W1)
    np.testing.assert_allclose(W1["w"], W2["w"], atol=1e-6)
    assert float(sk2) <= 1e-8  # second sync sees zero variance


@given(stf.integers(2, 8), stf.integers(1, 40), stf.randoms())
def test_sync_preserves_global_mean(R, dim, rnd):
    x = np.random.RandomState(rnd.randint(0, 2**31)).randn(R, dim)
    W = {"w": jnp.asarray(x.astype(np.float32))}
    W1, _, _ = avg.sync_replicas(W)
    np.testing.assert_allclose(np.asarray(W1["w"]).mean(0), x.mean(0),
                               atol=1e-5)


@given(stf.integers(2, 8), stf.randoms())
def test_variance_nonnegative_and_scale_quadratic(R, rnd):
    x = np.random.RandomState(rnd.randint(0, 2**31)).randn(R, 16)
    W = {"w": jnp.asarray(x.astype(np.float32))}
    v1 = float(avg.parameter_variance(W))
    v2 = float(avg.parameter_variance({"w": 2.0 * W["w"]}))
    assert v1 >= 0
    np.testing.assert_allclose(v2, 4 * v1, rtol=1e-4, atol=1e-6)


@given(stf.integers(1, 64), stf.integers(2, 8), stf.randoms())
def test_qsgd_error_bound(n, bits, rnd):
    rs = np.random.RandomState(rnd.randint(0, 2**31))
    x = jnp.asarray(rs.randn(n).astype(np.float32) * 10)
    key = jax.random.PRNGKey(rnd.randint(0, 2**31))
    lv, norm = qsgd.quantize(x, key, bits)
    dq = qsgd.dequantize(lv, norm, bits)
    s = (1 << (bits - 1)) - 1
    assert float(jnp.max(jnp.abs(dq - x))) <= float(norm) / s + 1e-5
    # levels stay within int8-representable range for bits<=8
    assert int(jnp.abs(lv.astype(jnp.int32)).max()) <= s


@given(stf.integers(1, 30), stf.integers(1, 200))
def test_constant_controller_sync_count(p, steps):
    cfg = AveragingConfig(method="cpsgd", p_const=p,
                          warmup_full_sync_steps=0)
    c = ConstantPeriodController(cfg, steps)
    syncs = sum(c.sync_now(k) for k in range(steps))
    assert syncs == steps // p


@given(stf.lists(stf.floats(1e-6, 1e3), min_size=1, max_size=60),
       stf.floats(1e-4, 1.0))
def test_adpsgd_period_always_valid(sks, lr):
    cfg = AveragingConfig(method="adpsgd", p_init=4, k_sample_frac=0.2,
                          p_min=1, p_max=64)
    c = ADPSGDController(cfg, total_steps=100)
    k = 0
    for s in sks:
        while not c.sync_now(k):
            k += 1
        c.observe(k, lr, s)
        assert cfg.p_min <= c.period <= cfg.p_max
        k += 1


@given(stf.integers(2, 6), stf.integers(1, 3), stf.randoms())
def test_group_sync_partitions(R_half, group_pow, rnd):
    R = 2 * R_half
    g = min(2 ** group_pow, R)
    while R % g:
        g //= 2
    x = np.random.RandomState(rnd.randint(0, 2**31)).randn(R, 8)
    W = {"w": jnp.asarray(x.astype(np.float32))}
    Wg = avg.group_sync(W, g)
    out = np.asarray(Wg["w"])
    for i in range(0, R, g):
        # within-group equality; group mean preserved
        np.testing.assert_allclose(out[i:i + g],
                                   np.broadcast_to(x[i:i + g].mean(0), (g, 8)),
                                   atol=1e-5)
    # cross-group variance survives (outer sync is separate)
    if R > g:
        assert float(avg.parameter_variance(Wg)) >= 0


@given(stf.integers(1, 4), stf.integers(4, 32), stf.randoms())
def test_optimizers_reduce_quadratic(R, dim, rnd):
    from repro.optim import get_optimizer
    rs = np.random.RandomState(rnd.randint(0, 2**31))
    target = jnp.asarray(rs.randn(dim).astype(np.float32))

    def loss_fn(p, b):
        d = p["w"] - target
        return jnp.sum(d * d), {}

    for name in ("sgd", "momentum", "adamw"):
        opt = get_optimizer(name)
        params = {"w": jnp.zeros((dim,))}
        st = opt.init(params)
        l0 = float(loss_fn(params, None)[0])
        g = jax.grad(lambda p: loss_fn(p, None)[0])
        lr = 0.05 if name != "adamw" else 0.05
        for _ in range(30):
            params, st = opt.update(g(params), st, params, jnp.float32(lr))
        assert float(loss_fn(params, None)[0]) < l0


# ---------------------------------------------------------------------------
# base_spec divisibility guards (launch/sharding.py): a dim is sharded only
# if the mesh axis divides it; odd sizes fall back to replication, and every
# produced PartitionSpec must be valid for the mesh.
# ---------------------------------------------------------------------------


def _check_spec_valid(spec, shape, mesh):
    """GSPMD validity: named axes exist, appear at most once across the
    spec, and divide the dim they shard."""
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    seen = []
    assert len(spec) <= len(shape)
    for dim, entry in zip(shape, tuple(spec) + (None,) * len(shape)):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        for a in axes:
            assert a in sizes, (spec, mesh.axis_names)
            assert a not in seen, f"axis {a} used twice in {spec}"
            seen.append(a)
        total = int(np.prod([sizes[a] for a in axes]))
        assert dim % total == 0, (spec, shape, sizes)


# paths drawn from the real rule table: megatron projections, embeddings,
# MoE experts, CNN fc/conv — plus an unmatched path (catch-all replication)
_PATHS_2D = ["embed", "lm_head", "wq|w", "wo|w", "w_up|w", "w_down|w",
             "fc1|w", "fc2|w", "mystery|w"]


@given(stf.sampled_from(_PATHS_2D),
       stf.integers(1, 4099), stf.integers(1, 515),
       stf.sampled_from([2, 3, 4, 8, 16]))
def test_base_spec_divisibility_guard(path, d0, d1, m):
    mesh = AbstractMesh((4, m), ("data", "model"))
    plan = ParallelismPlan(plan="replica_dp", placement="replica_tp")
    spec = sh.base_spec(ModelConfig(), path, (d0, d1), mesh, plan)
    _check_spec_valid(spec, (d0, d1), mesh)
    # odd sizes on *both* dims -> full fallback to replication
    if d0 % m and d1 % m:
        assert all(s is None for s in spec), (path, spec)


@given(stf.integers(1, 4099), stf.sampled_from([2, 4, 8, 16]))
def test_vocab_parallel_embed_falls_back(vocab, m):
    """Odd vocab sizes fall back from vocab-parallel to d-model sharding
    (and to replication when d_model is odd too)."""
    mesh = AbstractMesh((4, m), ("data", "model"))
    plan = ParallelismPlan(plan="replica_dp")
    d_model = 8 * m
    spec = sh.base_spec(ModelConfig(), "embed", (vocab, d_model), mesh, plan)
    if vocab % m == 0:
        assert spec == ("model", None)
    else:
        assert spec == (None, "model")
    _check_spec_valid(spec, (vocab, d_model), mesh)


@given(stf.integers(2, 9), stf.integers(1, 129), stf.integers(1, 129),
       stf.sampled_from([2, 4, 8]), stf.booleans())
def test_param_specs_always_valid_for_mesh(R_pow, d0, d1, m, two_pod):
    """Stacked param_specs over a pytree with odd/even dims stay valid for
    1- and 2-pod meshes under the replica_tp plan; the leading entry is
    always the replica-axis entry."""
    R = 4 * R_pow      # replica-axis divisibility is bind()'s runtime guard,
    #                    not param_specs' — keep R a multiple of the 4
    #                    replica devices both meshes have
    mesh = (AbstractMesh((2, 2, m), ("pod", "data", "model")) if two_pod
            else AbstractMesh((4, m), ("data", "model")))
    rep = ("pod", "data") if two_pod else ("data",)
    tree = {"fc1": {"w": np.zeros((R, d0, d1)), "b": np.zeros((R, d1))},
            "odd": {"w": np.zeros((R, d0))}}
    specs = sh.param_specs(ModelConfig(), tree, mesh,
                           ParallelismPlan(plan="replica_dp",
                                           placement="replica_tp"),
                           replica_axes=rep, stacked=True)
    flat_s = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda s: isinstance(s, P))
    flat_x = jax.tree_util.tree_leaves(tree)
    for spec, x in zip(flat_s, flat_x):
        assert spec[0] == (rep if len(rep) > 1 else rep[0])
        _check_spec_valid(spec, x.shape, mesh)
