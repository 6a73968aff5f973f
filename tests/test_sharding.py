"""Sharding-rule validity for all architectures on an abstract production
mesh: every spec must divide the dims it shards (GSPMD's hard requirement)."""
import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config
from repro.launch import sharding as sh
from repro.launch import specs as sp
from repro.launch.mesh import replica_axes_for

ARCHS = ["qwen2-vl-2b", "xlstm-350m", "whisper-medium", "qwen2.5-14b",
         "olmo-1b", "glm4-9b", "mixtral-8x22b", "jamba-1.5-large-398b",
         "deepseek-v2-lite-16b", "minicpm-2b"]

MESH_1POD = AbstractMesh((16, 16), ("data", "model"))
MESH_2POD = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _axis_sizes(mesh):
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def check_divisibility(spec_tree, abs_tree, mesh, stacked):
    sizes = _axis_sizes(mesh)
    leaves_s = jax.tree_util.tree_leaves(
        spec_tree, is_leaf=lambda s: isinstance(s, P))
    leaves_x = jax.tree_util.tree_leaves(abs_tree)
    assert len(leaves_s) == len(leaves_x)
    for spec, x in zip(leaves_s, leaves_x):
        assert len(spec) <= x.ndim, (spec, x.shape)
        for dim, entry in zip(x.shape, spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            total = int(np.prod([sizes[a] for a in axes]))
            assert dim % total == 0, (spec, x.shape)


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("mesh", [MESH_1POD, MESH_2POD],
                         ids=["1pod", "2pod"])
def test_param_specs_divisible(arch, mesh):
    run = get_config(arch)
    cfg = run.model
    multi = "pod" in mesh.axis_names
    rep = replica_axes_for(run.parallelism.plan, multi)
    R = int(np.prod([_axis_sizes(mesh)[a] for a in rep])) if rep else 1
    W = sp.abstract_params(cfg, n_replicas=R)
    spec = sh.param_specs(cfg, W, mesh, run.parallelism,
                          replica_axes=rep, stacked=True)
    check_divisibility(spec, W, mesh, stacked=True)


@pytest.mark.parametrize("arch", ["glm4-9b", "mixtral-8x22b",
                                  "deepseek-v2-lite-16b", "xlstm-350m",
                                  "jamba-1.5-large-398b"])
def test_cache_specs_divisible(arch):
    run = get_config(arch)
    cfg = run.model
    for B, S in ((128, 1024), (1, 2048)):
        caches = sp.abstract_caches(cfg, B, S)
        spec = sh.cache_specs(cfg, caches, MESH_1POD, batch=B)
        check_divisibility(spec, caches, MESH_1POD, stacked=False)


def test_big_tensors_are_sharded_qwen():
    """The heavy matrices must actually shard over 'model' (not silently
    fall back to replication)."""
    run = get_config("qwen2.5-14b")
    W = sp.abstract_params(run.model, n_replicas=16)
    spec = sh.param_specs(run.model, W, MESH_1POD, run.parallelism,
                          replica_axes=("data",), stacked=True)
    blk = spec["blocks"][0]
    assert blk["attn"]["wq"]["w"] == P("data", None, "model")
    assert blk["attn"]["wo"]["w"] == P("data", "model", None)
    assert blk["mlp"]["w_gate"]["w"] == P("data", None, "model")
    assert blk["mlp"]["w_down"]["w"] == P("data", "model", None)
    # vocab-parallel embedding (hillclimb A1): vocab dim takes 'model'
    assert spec["embed"] == P("data", "model", None)


def test_fsdp_plan_adds_data_axis():
    run = get_config("mixtral-8x22b")
    W = sp.abstract_params(run.model, n_replicas=1)
    spec = sh.param_specs(run.model, W, MESH_1POD, run.parallelism,
                          replica_axes=(), stacked=True)
    blk = spec["blocks"][0]
    # experts: E=8 not divisible by 16 -> F dim takes 'model'; fsdp adds
    # 'data' on the largest remaining dim
    s = blk["moe"]["w_gate"]
    assert "model" in s and "data" in s
    flat = [x for x in jax.tree_util.tree_leaves(
        spec, is_leaf=lambda s_: isinstance(s_, P))]
    n_data = sum(1 for s_ in flat for e in s_ if e == "data")
    assert n_data > len(flat) // 3  # most big params are fsdp-sharded


def test_replica_axes_mapping():
    assert replica_axes_for("replica_dp", False) == ("data",)
    assert replica_axes_for("replica_dp", True) == ("pod", "data")
    assert replica_axes_for("fsdp", False) == ()
    assert replica_axes_for("fsdp", True) == ("pod",)


@pytest.mark.parametrize("arch", ARCHS)
def test_train_batch_specs_shapes(arch):
    from repro.configs import INPUT_SHAPES
    cfg = get_config(arch).model
    batch, spec = sp.train_batch_specs(cfg, INPUT_SHAPES["train_4k"], 16)
    tok = batch["tokens"]
    assert tok.shape[0] == 16 and tok.shape[1] == 16
    total_seq = tok.shape[2] + (cfg.vision.n_patches if cfg.vision else 0)
    assert total_seq == 4096
