"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each kernel is lowered and compiled for a described (not
attached) ``v5e:2x2`` topology, which raises what the chip's compiler would
raise — misaligned blocks, scalar stores to VMEM, too much fast memory —
and whose memory analysis shows which buffers a program reuses.
The topology is described inside a fixture, never at import time, so every
pytest-xdist worker collects the same tests and only the worker that runs
this file loads the TPU library."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.backends import base
from repro.backends.vmap import VmapBackend
from repro.configs import get_config
from repro.kernels import param_variance, qsgd_quant
from repro.models import model as M
from repro.optim import get_optimizer


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
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
