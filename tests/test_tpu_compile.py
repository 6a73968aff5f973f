"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each kernel is lowered and compiled for a described (not
attached) ``v5e:2x2`` topology, which raises what the chip's compiler would
raise — misaligned blocks, scalar stores to VMEM, too much fast memory —
and whose memory analysis shows which buffers a program reuses.
The topology is described inside a fixture, never at import time, so every
pytest-xdist worker collects the same tests and only the worker that runs
this file loads the TPU library."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.backends import base
from repro.backends.mesh import MeshBackend
from repro.backends.vmap import VmapBackend
from repro.configs import get_config
from repro.core import averaging as avg
from repro.kernels import param_variance, qsgd_quant
from repro.launch import steps
from repro.models import model as M
from repro.optim import get_optimizer


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


CASES = {
    # OLMo-1B's MLP leaf with 2 stacked replicas: the sync kernel
    "mean_and_sqdev": (param_variance.mean_and_sqdev,
                       [((2, 2048, 8192), jnp.float32)]),
    # a leaf with no lane-dense view: flattened and padded
    "mean_and_sqdev_padded": (param_variance.mean_and_sqdev,
                              [((2, 1000, 7), jnp.float32)]),
    "sqnorm": (qsgd_quant.sqnorm, [((2048, 8192), jnp.float32)]),
    "quantize": (qsgd_quant.quantize, [((2048, 8192), jnp.float32),
                                       ((2048, 8192), jnp.float32)]),
    "dequantize": (qsgd_quant.dequantize, [((2048, 8192), jnp.int8),
                                           ((), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, specs = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("sync_momentum", [False, True])
def test_vmap_sync_aliases_params_and_adamw_state(
        sync_momentum, one_chip, no_persistent_cache, monkeypatch):
    """The vmap ``all_mean`` program, built as on a TPU, writes its results
    into the buffers of W and of AdamW's m and v: an undonated state that
    comes back unchanged is a full copy of m and v on every sync."""
    # donated() and the kernel's interpret switch ask the platform
    monkeypatch.setattr(base.jax, "default_backend", lambda: "tpu")
    mc = dataclasses.replace(get_config("olmo-1b").model, n_layers=1)
    p1 = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), mc))
    W = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((2,) + x.shape, x.dtype), p1)
    O = jax.eval_shape(jax.vmap(get_optimizer("adamw").init), W)
    W, O = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        (W, O))
    b = VmapBackend(use_kernel=True)
    b.bind(2)
    prog = b.all_mean(sync_momentum=sync_momentum).__wrapped__
    mem = prog.lower(W, O).compile().memory_analysis()
    assert mem.alias_size_in_bytes == mem.argument_size_in_bytes
    # what is left over is S_k alone, less than any leaf of W
    smallest = min(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(W))
    assert mem.output_size_in_bytes - mem.alias_size_in_bytes < smallest


def _olmo_step(one_chip, **changes):
    """The vmap local step of OLMo-1B at the benchmark's shapes (2 replicas
    x 1 x 2048 tokens), AdamW, W and the state donated, and its arguments'
    shapes on the described chip."""
    mc = dataclasses.replace(get_config("olmo-1b").model, **changes)
    opt = get_optimizer("adamw", weight_decay=0.1)
    p1 = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), mc))
    W = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((2,) + x.shape, x.dtype), p1)
    O = jax.eval_shape(jax.vmap(opt.init), W)
    args = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        (W, O, {"tokens": jax.ShapeDtypeStruct((2, 1, 2048), jnp.int32)},
         jax.ShapeDtypeStruct((), jnp.float32)))
    step = jax.jit(avg.make_local_step(steps.make_loss_fn(mc), opt),
                   donate_argnums=(0, 1))
    return step, args


# an f32 buffer with a 16-head axis before two 2048 axes: the (H, S, S)
# score, softmax or dS tensor of plain attention at S = 2048
_F32_SCORES = re.compile(r"f32\[(?:\d+,)*16,(?:\d+,)*2048,2048\]")
_FLASH_CALL = re.compile(
    r"%(flash_attention|flash_mha_bwd_dkv|flash_mha_bwd_dq)\S* = "
    r"[^\n]*custom_call_target=\"tpu_custom_call\"")


def test_olmo_step_attends_with_flash_kernels(
        one_chip, no_persistent_cache, monkeypatch):
    """Built as on a TPU, the training step attends through the flash
    kernels (forward, its remat recompute, dK/dV and dQ in the layers'
    scanned body), holds no f32 (16, 2048, 2048) score buffer, and needs
    less scratch memory than plain attention's 6,723,112,960 B at depth 4."""
    monkeypatch.setattr(base.jax, "default_backend", lambda: "tpu")
    step, args = _olmo_step(one_chip, n_layers=4)
    compiled = step.lower(*args).compile()
    hlo = compiled.as_text()
    calls = [m.group(1) for m in _FLASH_CALL.finditer(hlo)]
    assert sorted(calls) == ["flash_attention", "flash_attention",
                             "flash_mha_bwd_dkv", "flash_mha_bwd_dq"]
    assert hlo.count('custom_call_target="tpu_custom_call"') == len(calls)
    assert not _F32_SCORES.search(hlo)
    assert compiled.memory_analysis().temp_size_in_bytes < 6_723_112_960


@pytest.mark.parametrize("platform,changes", [
    ("cpu", {}),                           # not a TPU
    ("tpu", {"sliding_window": 1024}),     # a mask the kernels do not take
    ("tpu", {"attn_logit_softcap": 30.0}),
])
def test_olmo_step_keeps_plain_attention(
        platform, changes, one_chip, monkeypatch):
    """Off the TPU, and for masks or logits the flash kernels do not
    compute, the step keeps the plain attention core with its f32 scores."""
    monkeypatch.setattr(base.jax, "default_backend", lambda: platform)
    step, args = _olmo_step(one_chip, n_layers=1, **changes)
    text = step.lower(*args).as_text()
    assert "tpu_custom_call" not in text
    assert "2048x2048xf32" in text


@pytest.mark.parametrize("placement,shape,flash", [
    ("replica_tp", (1, 1), False),    # 'model' left to GSPMD, even at size 1
    ("replica_tp", (2, 2), False),
    ("replica_ddp", (2, 2), True),    # manual over every axis
])
def test_mesh_step_lowers_as_on_a_tpu(
        placement, shape, flash, topo, monkeypatch):
    """The mesh backend's local step, built as on a TPU, lowers under both
    placements.  GSPMD cannot partition a Mosaic kernel, so the flash
    kernels appear only where the program is manual over every mesh axis;
    ``replica_tp`` keeps the plain attention core."""
    monkeypatch.setattr(base.jax, "default_backend", lambda: "tpu")
    mc = dataclasses.replace(get_config("olmo-1b").model, n_layers=1)
    opt = get_optimizer("adamw", weight_decay=0.1)
    devices = np.array(topo.devices[:shape[0] * shape[1]]).reshape(shape)
    b = MeshBackend(Mesh(devices, ("data", "model")), model_cfg=mc,
                    placement=placement)
    b.bind(2)
    p1 = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), mc))
    W = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((2,) + x.shape, x.dtype), p1)
    O = jax.eval_shape(jax.vmap(opt.init), W)

    def placed(tree, shardings):
        return jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, shardings)

    args = (placed(W, b._param_shardings(W)), placed(O, b._opt_shardings(O, W)),
            {"tokens": jax.ShapeDtypeStruct(
                (2, 1, 2048), jnp.int32,
                sharding=NamedSharding(b.mesh, P("data")))},
            jax.ShapeDtypeStruct((), jnp.float32,
                                 sharding=NamedSharding(b.mesh, P())))
    prog = b.replica_step(steps.make_loss_fn(mc), opt).__wrapped__
    text = jax.jit(prog).lower(*args).as_text()
    assert ("tpu_custom_call" in text) == flash
