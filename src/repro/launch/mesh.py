"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module touches no jax device state — required because the dry-run forces
512 host devices via XLA_FLAGS before first jax init, while tests and
benches must keep seeing 1 device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: 256 chips as (data=16, model=16).
    Multi-pod: 2 pods = 512 chips as (pod=2, data=16, model=16)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1) -> Mesh:
    """Tiny mesh over whatever devices exist (tests / local runs)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return jax.make_mesh((n // model_parallel, model_parallel),
                         ("data", "model"))


def auto_axes(mesh: Mesh) -> Mesh:
    """The same devices and axis names with every axis in GSPMD's
    ``Auto`` mode (``jax.make_mesh`` defaults to ``Explicit``): the
    shard_map programs name their manual axes, and the partitioner owns
    the rest."""
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def replica_axes_for(plan: str, multi_pod: bool):
    """Mesh axes consumed by the leading replica dim (DESIGN.md §4)."""
    if plan in ("replica_dp", "replica_ddp"):
        return ("pod", "data") if multi_pod else ("data",)
    # fsdp: local-SGD replicas only across pods (DiLoCo-style)
    return ("pod",) if multi_pod else ()


def n_replicas_for(mesh: Mesh, plan: str, multi_pod: bool) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    r = 1
    for ax in replica_axes_for(plan, multi_pod):
        r *= sizes.get(ax, 1)
    return max(r, 1)
