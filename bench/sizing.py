#!/usr/bin/env python3
"""Memory of a cell's local-step program, compiled for a described v5e
without the chip: how the batch of a configuration was chosen.

    JAX_PLATFORMS=cpu python3 bench/sizing.py --config olmo-1b.d4.r2 --batch 1 2

The vmap step is compiled as the trainer's vmap backend builds it on a
TPU (W and the optimizer state donated) for one chip of a described
``v5e:2x2``; a mesh configuration's step is compiled through the trainer's
mesh backend over all four.  Prints ``memory_analysis()`` per batch.  This
is not part of a benchmark run."""
import argparse
import dataclasses
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--batch", type=int, nargs="+", default=[1])
    ap.add_argument("--seq", type=int, default=2048)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)
    from repro.backends.mesh import MeshBackend
    from repro.configs import get_config
    from repro.core import averaging as avg
    from repro.launch import steps
    from repro.models import model as M
    from repro.optim import get_optimizer

    import harness
    c = json.loads((HERE / "configs" / f"{args.config}.json").read_text())
    fam = harness._module(HERE / "reference" / f"{c['family']}.py",
                          f"reference.{c['family']}")
    arch, fields = fam.program_config(c)
    run = get_config(arch)
    mc = dataclasses.replace(run.model, **fields)
    dep = c["deployment"]
    R = dep["replicas"]
    opt = get_optimizer(run.optimizer, weight_decay=c["optimizer"]
                        ["weight_decay"])
    loss_fn = steps.make_loss_fn(mc)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    p1 = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), mc))
    W = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((R,) + x.shape, x.dtype), p1)
    O = jax.eval_shape(jax.vmap(opt.init), W)
    for b in args.batch:
        batch = {"tokens": jax.ShapeDtypeStruct((R, b, args.seq), jnp.int32)}
        lr = jax.ShapeDtypeStruct((), jnp.float32)
        if dep["backend"] == "mesh":
            mesh = Mesh(np.array(topo.devices).reshape(-1, 1),
                        ("data", "model"))
            be = MeshBackend(mesh, placement=dep.get("placement",
                                                     "replica_ddp"))
            be.bind(R)
            step = jax.jit(be.replica_step(loss_fn, opt))

            def put(t):
                return jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(
                        x.shape, x.dtype, sharding=NamedSharding(
                            mesh, P("data") if x.ndim else P())), t)
        else:
            step = jax.jit(avg.make_local_step(loss_fn, opt),
                           donate_argnums=(0, 1))
            one = SingleDeviceSharding(topo.devices[0])

            def put(t):
                return jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                   sharding=one), t)
        try:
            m = step.lower(put(W), put(O), put(batch), lr).compile() \
                .memory_analysis()
            total = (m.argument_size_in_bytes + m.output_size_in_bytes
                     + m.temp_size_in_bytes - m.alias_size_in_bytes)
            print(json.dumps({
                "config": args.config, "per_replica_batch": b,
                "argument_bytes": m.argument_size_in_bytes,
                "output_bytes": m.output_size_in_bytes,
                "alias_bytes": m.alias_size_in_bytes,
                "temp_bytes": m.temp_size_in_bytes,
                "total_bytes": total}), flush=True)
        except Exception as e:  # the compiler refuses what does not fit
            print(json.dumps({"config": args.config, "per_replica_batch": b,
                              "refused": str(e).splitlines()[0][:300]}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
