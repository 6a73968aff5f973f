#!/usr/bin/env python3
"""Smoke test of the local-SGD trainer on a TPU, in one process.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-chip mesh phase only

One chip: ADPSGD trains OLMo-1B at its published widths (depth cut to 4 of
its 16 layers) with 2 replicas through ``repro.launch.train.main``, then
the sync and QSGD Pallas kernels are checked on the chip against their jnp
references.  Four chips: the same trainer on ``--backend mesh`` with one
replica per chip, against ``--backend vmap`` with the same seeds, data and
schedule on one chip.  Any failed check exits non-zero.  The last line of
stdout is the JSON result.  There is no CPU fallback: without a TPU the
script exits non-zero before it prints a result.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "olmo-1b"
SEQ = 2048
# ADPSGD: two warm-up full syncs, then adaptive periods from p=2
SCHEDULE = ["--method", "adpsgd", "--warmup-sync", "2", "--p-init", "2",
            "--seed", "0", "--net", "real"]
# compile events JAX reports (jax._src.dispatch): trace, lower, XLA compile
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class Failed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    print(f"  {'ok  ' if ok else 'FAIL'} {what}", flush=True)
    if not ok:
        raise Failed(what)


def train(argv):
    """One in-process run of the trainer; returns (engine, compile seconds
    spent inside it)."""
    from repro.launch import train as trainer
    spent = [0.0]

    def on_event(name, secs, **_):
        if name in COMPILE_EVENTS:
            spent[0] += secs

    import jax
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        engine = trainer.main(argv)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    return engine, spent[0]


def report_run(engine, compile_s: float, label: str):
    """Print losses, syncs and timings of one run and check them."""
    import numpy as np
    hist = engine.history
    step_s = [r.compute_s for r in engine.timeline.records
              if r.name == "replica_step"]
    print(f"[{label}] compile (trace+lower+XLA) {compile_s:.3f} s; "
          f"first step incl. compile {step_s[0]:.3f} s; later steps "
          f"(block_until_ready) {[round(s, 4) for s in step_s[1:]]} s, "
          f"median {float(np.median(step_s[1:])):.4f} s")
    print(f"[{label}] losses {[round(v, 5) for v in hist.losses]}")
    print(f"[{label}] syncs at {hist.sync_steps}, S_k "
          f"{[float(f'{s:.6g}') for s in hist.s_k]}, periods "
          f"{hist.period_history}")
    check(all(math.isfinite(v) for v in hist.losses), "losses finite")
    check(len(hist.s_k) >= 2, f"{len(hist.s_k)} syncs >= 2")
    check(all(math.isfinite(s) and s > 0 for s in hist.s_k),
          "S_k finite and > 0 after local steps")
    return step_s


def one_chip(dev) -> None:
    import jax
    import jax.numpy as jnp
    from repro.backends.ops import all_mean_op
    from repro.core import averaging as avg
    from repro.kernels import ops as kops
    from repro.kernels import ref

    R, B, L = 2, 1, 4
    engine, compile_s = train(
        ["--arch", ARCH, "--no-reduced", "--layers", str(L),
         "--backend", "vmap", "--replicas", str(R), "--batch", str(B),
         "--seq", str(SEQ), "--steps", "12", *SCHEDULE])
    report_run(engine, compile_s, "vmap x1 chip")
    W = engine.W
    check(W["embed"].shape == (R, 50304, 2048), f"embed {W['embed'].shape}")
    up = W["blocks"][0]["mlp"]["w_up"]["w"]
    check(len(W["blocks"]) == L and up.shape == (R, 2048, 8192),
          f"{len(W['blocks'])} blocks, w_up {up.shape}")
    print(f"peak_bytes_in_use {dev.memory_stats()['peak_bytes_in_use']}")

    print("kernels against their references, on the chip:")
    # the sync program the backend builds must hold the Pallas kernel
    sync = engine.backend.lower(all_mean_op())
    hlo = sync.__wrapped__.lower(W, engine.opt_state).compile().as_text()
    check("tpu_custom_call" in hlo, "sync program holds tpu_custom_call")

    # mean + S_k on a real (2, 2048, 8192) leaf whose replicas differ
    noise = 1e-3 * jax.random.normal(jax.random.PRNGKey(1), up.shape[1:])
    leaf = jnp.stack([up[0], up[0] + noise])
    mean_k, sq_k = jax.jit(kops.param_mean_and_sqdev)(leaf)
    W_ref, _, s_ref = jax.jit(lambda w: avg.sync_replicas(
        {"w": w}, None, use_kernel=False))(leaf)
    d_mean = float(jnp.max(jnp.abs(mean_k - W_ref["w"][0])))
    s_k = float(sq_k) / R
    print(f"  mean+sqdev: max |mean - ref| {d_mean:.3e}; "
          f"S_k kernel {s_k!r} ref {float(s_ref)!r}")
    # the mean is elementwise over 2 replicas (exact); S_k sums 3.4e7 f32
    # terms in a different order, hence a relative 1e-5
    check(d_mean <= 1e-6, "kernel mean == jnp mean (atol 1e-6)")
    check(abs(s_k - float(s_ref)) <= 1e-5 * abs(float(s_ref)),
          "kernel S_k == sync_replicas S_k (rtol 1e-5)")

    # QSGD levels at the kernel's norm, on the same uniforms
    x = noise
    u = jax.random.uniform(jax.random.PRNGKey(2), x.shape)
    qfn = jax.jit(kops.qsgd_quantize)
    lv, nm = qfn(x, u)
    lv_ref, nm_ref = ref.quantize_ref(x, u, norm=nm)
    _, nm_own = ref.quantize_ref(x, u)
    n_diff = int(jnp.sum(lv != lv_ref))
    print(f"  qsgd: norm kernel {float(nm)!r} ref {float(nm_own)!r}; "
          f"levels differing at the kernel's norm {n_diff} of {lv.size}")
    check(abs(float(nm) - float(nm_own)) <= 1e-5 * float(nm_own),
          "kernel norm == ref norm (rtol 1e-5: blocked f32 sum)")
    check(n_diff == 0, "quantize levels == ref levels")
    dq = jax.jit(kops.qsgd_dequantize)(lv, nm)
    check(bool(jnp.array_equal(dq, ref.dequantize_ref(lv_ref, nm))),
          "dequantize == ref")
    for name, fn, args in (("quantize", qfn, (x, u)),
                           ("dequantize", kops.qsgd_dequantize, (lv, nm))):
        txt = jax.jit(fn).lower(*args).compile().as_text()
        check("tpu_custom_call" in txt, f"{name} compiled as tpu_custom_call")


def four_chips(devs) -> None:
    """ADPSGD with one replica per chip (mesh, replica_ddp) against the
    same run on one chip (vmap).  Four replicas fit one chip at depth 1."""
    import gc

    import jax
    import numpy as np
    R, B, L = 4, 1, 1
    common = ["--arch", ARCH, "--no-reduced", "--layers", str(L),
              "--replicas", str(R), "--batch", str(B), "--seq", str(SEQ),
              "--steps", "10", *SCHEDULE]
    mesh, c_mesh = train(common + ["--backend", "mesh",
                                   "--placement", "replica_ddp"])
    step_mesh = report_run(mesh, c_mesh, "mesh x4 chips")
    placed = {d.id for x in jax.tree_util.tree_leaves(mesh.W)
              for d in x.sharding.device_set}
    print(f"W leaves lie on devices {sorted(placed)}")
    check(placed == {d.id for d in devs}, "replicas on all four chips")
    h_mesh = mesh.history
    h_mesh.final_W = h_mesh.final_opt = None     # free the chips for vmap
    del mesh
    gc.collect()
    vm, c_vm = train(common + ["--backend", "vmap"])
    step_vm = report_run(vm, c_vm, "vmap x1 chip")
    h_vm = vm.history
    check(h_mesh.sync_steps == h_vm.sync_steps
          and h_mesh.period_history == h_vm.period_history,
          "identical sync schedule")
    dl = np.max(np.abs(np.subtract(h_mesh.losses, h_vm.losses))
                / np.abs(h_vm.losses))
    ds = np.max(np.abs(np.subtract(h_mesh.s_k, h_vm.s_k))
                / np.abs(h_vm.s_k))
    print(f"mesh vs vmap: max rel diff losses {dl:.3e}, S_k {ds:.3e}; "
          f"median step mesh {np.median(step_mesh[1:]):.4f} s, "
          f"vmap {np.median(step_vm[1:]):.4f} s")
    # bf16 compute: one device program per replica (mesh) against one
    # program over all four (vmap) may fuse and round differently
    check(dl <= 1e-3, "losses agree (rtol 1e-3)")
    check(ds <= 1e-2, "S_k agree (rtol 1e-2)")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh phase and its vmap "
                         "comparison")
    args = ap.parse_args()
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the repo's src/ is not beside this script: {e}",
              file=sys.stderr)
        return 2
    import jax
    from repro.launch.cache import use_compile_cache
    cache_dir = Path(use_compile_cache())
    n_cached = len(list(cache_dir.iterdir())) if cache_dir.is_dir() else 0
    print(f"compile cache: {cache_dir} ({n_cached} entries before the run)")
    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: no TPU; it does not run elsewhere",
              file=sys.stderr)
        return 1
    if len(devs) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)}",
              file=sys.stderr)
        return 1
    t0 = time.time()
    try:
        (four_chips(devs) if args.chips == 4 else one_chip(dev))
    except Failed as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    n_after = len(list(cache_dir.iterdir())) if cache_dir.is_dir() else 0
    print(f"total {time.time() - t0:.1f} s; compile cache now holds "
          f"{n_after} entries", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
