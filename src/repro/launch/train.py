"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --method adpsgd \
        --steps 200 --replicas 4 --backend vmap

runs the smoke-size model (``--reduced``, the default: d_model <= 128, two
layers).  ``--no-reduced`` runs the config's published widths;
``--layers N`` then keeps the first N of its published layers, the one cut
that fits a model onto fewer chips (the run prints it beside the published
depth).

``--method`` accepts any name registered in ``repro/strategies`` (the five
paper methods plus hier_adpsgd, qsgd_periodic, adacomm, dasgd, and anything
a plugin registers); ``--backend`` any name in ``repro/backends`` (vmap =
host device; mesh = replica axis sharded over the devices jax sees —
on this container set ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
to give the mesh N host devices, on a real cluster the same driver takes
the production mesh from launch/mesh.py).  ``--placement replica_tp`` lets
one mesh replica span the 'model' mesh axis (megatron-style tensor
parallelism inside each replica — DESIGN.md §5 "Placements");
``--model-parallel`` sizes that axis on the host mesh.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import numpy as np

from repro.backends import available_backends, make_backend
from repro.checkpoint.io import save_checkpoint, strategy_state
from repro.configs import AveragingConfig, get_config, reduced
from repro.data.pipeline import SyntheticTokens
from repro.launch.cache import use_compile_cache
from repro.launch.steps import make_loss_fn
from repro.models import model as M
from repro.optim import get_optimizer, make_lr_schedule
from repro.runtime.clock import make_clock
from repro.runtime.engine import Checkpointer, PeriodicEval, TrainerEngine
from repro.strategies import available_strategies, make_strategy


def main(argv=None) -> TrainerEngine:
    """Parse ``argv`` (default: the command line), train, print the
    summary, and return the engine (history, final state, backend)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--method", default="adpsgd",
                    choices=available_strategies())
    ap.add_argument("--backend", default="vmap",
                    choices=available_backends(),
                    help="execution backend: where replicas live and how "
                         "syncs lower (repro/backends)")
    ap.add_argument("--sync-kernel", default="auto",
                    choices=["auto", "on", "off"],
                    help="fused Pallas mean+sqdev kernel in the sync "
                         "(auto = on TPU only, where it is profitable)")
    ap.add_argument("--placement", default="replica_ddp",
                    choices=["replica_ddp", "replica_tp"],
                    help="mesh-backend replica layout: replica_ddp = each "
                         "replica is a whole-model copy; replica_tp = one "
                         "replica spans the 'model' mesh axis "
                         "(megatron-style TP inside each replica)")
    ap.add_argument("--model-parallel", type=int, default=0,
                    help="model-axis size of the host mesh (0 = auto: 2 "
                         "for replica_tp when the device count is even, "
                         "else 1)")
    ap.add_argument("--net", default="none",
                    help="telemetry clock (runtime/clock.py): 'none' = no "
                         "instrumentation, 'real' = WallClock around "
                         "block-until-ready dispatches, '10gbps'/'100gbps'/"
                         "'<x>gbps' = SimulatedClock charging compute per "
                         "step and communication from the analytic model "
                         "at that bandwidth (bit-reproducible)")
    ap.add_argument("--wallclock-sample-every", type=int, default=1,
                    help="with --net real: block-until-ready only every N "
                         "steps and interpolate the Timeline in between, "
                         "keeping the async dispatch pipeline N steps deep "
                         "(1 = measure every dispatch)")
    ap.add_argument("--adacomm-mode", default="iterations",
                    choices=["iterations", "time"],
                    help="adacomm block definition: 'iterations' (interval "
                         "of steps) or 'time' (t0-second wall-clock blocks "
                         "on the --net clock, the paper's form)")
    ap.add_argument("--adacomm-t0", type=float, default=1.0,
                    help="seconds per adacomm_mode=time adaptation block")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--replicas", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4, help="per-replica batch")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="smoke-size widths (configs.base.reduced); "
                         "--no-reduced runs the published widths")
    ap.add_argument("--layers", type=int, default=0,
                    help="keep the first N layers of the model (0 = all); "
                         "a cut of depth only, to fit fewer chips")
    ap.add_argument("--p-init", type=int, default=2)
    ap.add_argument("--p-const", type=int, default=8)
    ap.add_argument("--warmup-sync", type=int, default=8)
    ap.add_argument("--inner-period", type=int, default=1)
    ap.add_argument("--lr", type=float, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None,
                    help="write a final checkpoint (replica-averaged) here")
    ap.add_argument("--out", default=None)
    # callback-bus flags: periodic eval + periodic (resumable) checkpoints
    ap.add_argument("--eval-every", type=int, default=0,
                    help="evaluate the replica-averaged model every N steps")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="checkpoint every N steps (needs --ckpt-path)")
    ap.add_argument("--ckpt-path", default=None,
                    help="directory for --ckpt-every checkpoints")
    ap.add_argument("--keep-replicas", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="periodic checkpoints keep the stacked replica "
                         "axis (resumable); --no-keep-replicas writes "
                         "replica-averaged export checkpoints")
    args = ap.parse_args(argv)
    print(f"compile cache: {use_compile_cache()}")

    run = get_config(args.arch)
    cfg = reduced(run.model, max_seq_len=args.seq) if args.reduced else run.model
    if args.layers:
        if not 0 < args.layers <= cfg.n_layers:
            ap.error(f"--layers must be in 1..{cfg.n_layers}")
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    print(f"[{cfg.name}] {'reduced' if args.reduced else 'published'} widths:"
          f" d_model={cfg.d_model} heads={cfg.n_heads}x{cfg.head_dim()}"
          f" kv_heads={cfg.n_kv_heads} d_ff={cfg.d_ff} ({cfg.mlp_type})"
          f" vocab={cfg.vocab_size} tied={cfg.tie_embeddings};"
          f" depth {cfg.n_layers} of {run.model.n_layers} published layers;"
          f" params {cfg.param_dtype}, compute {cfg.compute_dtype};"
          f" {args.replicas} replicas x {args.batch} seq x {args.seq} tokens")
    avg_cfg = AveragingConfig(
        method=args.method, p_init=args.p_init, p_const=args.p_const,
        warmup_full_sync_steps=args.warmup_sync, k_sample_frac=0.25,
        inner_period=args.inner_period, adacomm_mode=args.adacomm_mode,
        adacomm_t0=args.adacomm_t0)
    clock = make_clock(args.net,
                       wallclock_sample_every=args.wallclock_sample_every)
    if args.adacomm_mode == "time" and clock is None:
        ap.error("--adacomm-mode time needs a clock: pass --net "
                 "real|10gbps|100gbps|<x>gbps")
    lr = args.lr if args.lr is not None else min(run.learning_rate, 0.05)
    lr_fn = make_lr_schedule(
        "step", lr, args.steps,
        decay_steps=(args.steps // 2, 3 * args.steps // 4))
    opt = get_optimizer(run.optimizer, momentum_coef=run.momentum)

    data = SyntheticTokens(cfg.vocab_size, args.seq,
                           n_samples=args.replicas * args.batch * 64,
                           seed=args.seed)
    data_fn = data.batches(n_replicas=args.replicas,
                           per_replica_batch=args.batch)
    params0 = M.init_params(jax.random.PRNGKey(args.seed), cfg)
    loss_fn = make_loss_fn(cfg)
    strategy = make_strategy(avg_cfg, args.steps)
    use_kernel = {"auto": None, "on": True, "off": False}[args.sync_kernel]
    backend_kw = dict(use_kernel=use_kernel)
    if args.backend == "mesh":
        backend_kw.update(placement=args.placement,
                          model_parallel=args.model_parallel or None)
    elif args.placement != "replica_ddp" or args.model_parallel:
        ap.error("--placement/--model-parallel are mesh-backend options "
                 "(use --backend mesh)")
    backend = make_backend(args.backend, **backend_kw)

    callbacks = []
    if args.eval_every:
        callbacks.append(PeriodicEval(
            loss_fn, lambda: data.eval_batches(batch=args.batch * 4),
            every=args.eval_every))
    if args.ckpt_every:
        if not args.ckpt_path:
            ap.error("--ckpt-every needs --ckpt-path")
        callbacks.append(Checkpointer(args.ckpt_path, every=args.ckpt_every,
                                      keep_replicas=args.keep_replicas))

    engine = TrainerEngine(
        loss_fn=loss_fn, optimizer=opt, params0=params0,
        n_replicas=args.replicas, data_fn=data_fn, lr_fn=lr_fn,
        avg_cfg=avg_cfg, total_steps=args.steps, strategy=strategy,
        backend=backend, clock=clock, callbacks=callbacks,
        track_variance_every=max(1, args.steps // 50), seed=args.seed)
    del params0                 # the engine holds the stacked replicas
    t0 = time.time()
    hist = engine.run()
    dt = time.time() - t0

    print(f"[{args.arch} / {args.method} / {args.backend}] "
          f"{args.steps} steps in {dt:.1f}s  ({backend.describe()})")
    print(f"  loss {hist.losses[0]:.4f} -> "
          f"{np.mean(hist.losses[-10:]):.4f}")
    print(f"  syncs={hist.n_syncs} mean_period="
          f"{args.steps / max(1, hist.n_syncs):.2f} "
          f"final_p={hist.period_history[-1] if hist.period_history else 1}")
    if hist.inner_sync_steps:
        print(f"  inner_syncs={len(hist.inner_sync_steps)}")
    if hist.evals:
        print(f"  evals={len(hist.evals)} last@step{hist.eval_steps[-1]}: "
              + " ".join(f"{k}={v:.4f}" for k, v in hist.evals[-1].items()))
    print(f"  weighted-avg Var[W_k] (paper Eq.9) = "
          f"{hist.weighted_avg_variance():.3e}")
    if hist.timing:
        t = hist.timing
        print(f"  [{t['clock']} clock / {args.net}] "
              f"compute={t['compute_s']:.3f}s comm={t['comm_s']:.3f}s "
              f"total={t['sim_wall_s']:.3f}s "
              f"bytes/node={t['bytes']:.3e}")
    if args.ckpt:
        from repro.core.averaging import replica_mean
        save_checkpoint(args.ckpt, replica_mean(hist.final_W),
                        step=args.steps,
                        controller_state=strategy_state(strategy))
        print(f"  checkpoint -> {args.ckpt}")
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"arch": args.arch, "method": args.method,
                       "backend": args.backend,
                       "evals": hist.evals, "eval_steps": hist.eval_steps,
                       "losses": hist.losses, "s_k": hist.s_k,
                       "sync_steps": hist.sync_steps,
                       "periods": hist.period_history,
                       "inner_sync_steps": hist.inner_sync_steps,
                       "variances": hist.variances,
                       "variance_steps": hist.variance_steps,
                       "timing": hist.timing}, f)
        print(f"  history -> {args.out}")
    return engine


if __name__ == "__main__":
    main()
