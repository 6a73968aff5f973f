"""One benchmark run of a training cell: build the trainer's engine from the
cell's files, drive it through its first iterations and compare them with
the plain reference, warm it up, measure a window, and print the result.

Everything that belongs to one cell is data found by name:
``BENCHMARK.json`` names the cell's configuration file and traffic mix
(``bench/traffic/<traffic>.json``), its limits are in
``bench/limits/<cell>.json``, a configuration's model family has its
reference in ``bench/reference/<family>.py``, and each per-layer metric is
read by ``bench/metrics/<metric>.py``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Optional

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
N_CHECKED = 3           # iterations the reference follows
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


class Failed(RuntimeError):
    pass


# ---------------------------------------------------------------- the cell
@dataclasses.dataclass
class Cell:
    root: Path
    name: str
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    w = next((w for w in bench["workloads"] if w["name"] == name), None)
    if w is None:
        raise Failed(f"no workload {name!r} in BENCHMARK.json; have "
                     f"{[x['name'] for x in bench['workloads']]}")
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    d = root / "bench"

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(
        root=root, name=name, workload=w,
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads((d / "traffic" / f"{w['traffic']}.json")
                           .read_text()),
        limits=json.loads((d / "limits" / f"{name}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if mine(m)],
        per_layer=[m for m in bench["per_layer"] if mine(m)])


def _module(path: Path, name: str):
    if not path.is_file():
        raise Failed(f"missing {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(cell: Cell):
    return _module(HERE / "reference" / f"{cell.config['family']}.py",
                   f"reference.{cell.config['family']}")


def metric_reader(cell: Cell, name: str):
    return _module(cell.root / "bench" / "metrics" / f"{name}.py",
                   f"bench_metric_{name}")


def seed31(seed: int) -> int:
    """The seed as the trainer's numpy and JAX seeding takes it."""
    return seed % (2 ** 31)


def k_sample(cell: Cell) -> int:
    return int(cell.traffic["sampling_steps"])


# ------------------------------------------------------------ the devices
def devices(chips: int, require_chip: bool = True):
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    if require_chip and d.platform != "tpu":
        raise Failed(f"no TPU (JAX runs on {d.platform}); the benchmark "
                     "measures the chip only")
    if require_chip and len(devs) != chips:
        raise Failed(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


# ------------------------------------------------------------- the engine
class Feed:
    """The trainer's input pipeline over the cell's corpus, with a host
    span around each call; keeps the batches of the checked iterations."""

    def __init__(self, sharder):
        import jax
        self.sharder = sharder
        self.annotate = jax.profiler.TraceAnnotation
        self.fed = {}

    def __call__(self, k):
        with self.annotate("bench.input"):
            b = self.sharder(k)
        if k < N_CHECKED:
            self.fed[k] = np.asarray(b["tokens"])
        return b


def build(cell: Cell, seed: int):
    """The engine of one run, as ``repro.launch.train`` builds it, with the
    weights and the corpus made from ``seed`` by the benchmark."""
    import jax
    from repro.backends import make_backend
    from repro.configs import AveragingConfig, get_config
    from repro.data.pipeline import EpochSharder
    from repro.launch import steps
    from repro.models import model as M
    from repro.optim import get_optimizer, make_lr_schedule
    from repro.runtime.engine import TrainerEngine
    from repro.strategies import make_strategy
    import tokens as corpus

    c, t = cell.config, cell.traffic
    fam = family(cell)
    arch, fields = fam.program_config(c)
    run = get_config(arch)
    mc = dataclasses.replace(run.model, **fields)
    dep = c["deployment"]
    R, B, S = dep["replicas"], t["per_replica_batch"], t["seq_len"]
    horizon = int(t["horizon_steps"])
    a = dict(t["averaging"])
    avg_cfg = AveragingConfig(
        **a, k_sample_frac=(k_sample(cell) + 0.5) / horizon)
    opt_c = c["optimizer"]
    if run.optimizer != opt_c["name"]:
        raise Failed(f"{arch} trains with {run.optimizer}, the configuration "
                     f"states {opt_c['name']}")
    lr = float(opt_c["lr"])
    lr_fn = make_lr_schedule("step", lr, horizon,
                             decay_steps=(horizon // 2, 3 * horizon // 4))
    opt = get_optimizer(run.optimizer, momentum_coef=run.momentum,
                        weight_decay=opt_c["weight_decay"])
    s = seed31(seed)
    key = jax.random.PRNGKey(s)
    init = jax.jit(lambda k: fam.init_params(k, c))
    want = jax.eval_shape(lambda: M.init_params(key, mc))
    got = jax.eval_shape(init, key)
    if (jax.tree_util.tree_structure(want) != jax.tree_util.tree_structure(got)
            or [(x.shape, x.dtype) for x in jax.tree_util.tree_leaves(want)]
            != [(x.shape, x.dtype) for x in jax.tree_util.tree_leaves(got)]):
        raise Failed("the reference's weights do not have the trainer's "
                     "layout for this configuration")
    toks = corpus.corpus(s, mc.vocab_size, S, t["corpus"])
    feed = Feed(EpochSharder({"tokens": toks}, toks.shape[0], R, B, s))
    backend_kw = {"placement": dep["placement"]} if "placement" in dep \
        else {}
    strategy = make_strategy(avg_cfg, horizon)
    if getattr(getattr(strategy, "controller", None), "k_sample",
               k_sample(cell)) != k_sample(cell):
        raise Failed("the strategy's sampling window is not the traffic's")
    engine = TrainerEngine(
        loss_fn=steps.make_loss_fn(mc), optimizer=opt, params0=init(key),
        n_replicas=R, data_fn=feed, lr_fn=lr_fn, avg_cfg=avg_cfg,
        total_steps=horizon, strategy=strategy,
        backend=make_backend(dep["backend"], **backend_kw), seed=s)
    orig = strategy.dispatch
    annotate = jax.profiler.TraceAnnotation

    def dispatch(action, *args):
        with annotate(f"bench.dispatch.{action}"):
            return orig(action, *args)

    strategy.dispatch = dispatch
    return engine, feed, init, key, fam, mc


def first_iterations(engine, feed, init, key, b1: float) -> dict:
    """Drive the engine through its first iterations, through the window's
    own call and feed, and read what the reference checks: the mean loss of
    each, the first gradient's leaf norms from AdamW's first moment
    (m = (1 − b1)·g after one step), S_k at each sync, and the parameter
    change's leaf norms after the last one, before the next step."""
    import jax
    import jax.numpy as jnp

    def norms(tree):
        return jnp.stack([
            jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                             axis=tuple(range(1, x.ndim))))
            for x in jax.tree_util.tree_leaves(tree)])

    engine.run(0, 1)
    grad = np.asarray(jax.jit(norms)(engine.opt_state["m"])) / (1.0 - b1)
    engine.run(1, N_CHECKED - 1)
    delta = jax.jit(lambda W, k: norms(jax.tree_util.tree_map(
        lambda w, w0: w - w0[None], W, init(k))))
    upd = np.asarray(delta(engine.W, key))
    h = engine.history
    fed = [feed.fed[k] for k in range(N_CHECKED)]
    if len({r.tobytes() for b in fed for r in b.reshape(-1, b.shape[-1])}) \
            != sum(b.shape[0] * b.shape[1] for b in fed):
        raise Failed("the checked iterations were not fed distinct rows")
    return {"losses": list(h.losses[:N_CHECKED]), "grad_norms": grad,
            "s_k": list(h.s_k), "update_norms": upd,
            "sync_steps": list(h.sync_steps), "batches": fed}


# ------------------------------------------------------------- one run
class CompileCounter:
    def __init__(self):
        import jax
        self.n = 0
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, name, secs, **_):
        if name in COMPILE_EVENTS:
            self.n += 1

    def close(self):
        self._jax.monitoring.unregister_event_duration_listener(self)


def main(args, require_chip: bool = True, root: Path = ROOT,
         t_start: Optional[float] = None) -> int:
    t_start = time.monotonic() if t_start is None else t_start
    try:
        return _run(args, require_chip, root, t_start)
    except Failed as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1


def _run(args, require_chip, root, t_start) -> int:
    cell = load_cell(args.workload, root)
    if args.seconds <= 0:
        raise Failed("--seconds must be positive")
    try:
        import repro  # noqa: F401
    except ImportError as e:
        raise Failed(f"the trainer (src/repro) is not in this checkout: {e}")
    import jax
    devs = devices(cell.chips, require_chip)
    import peaks
    try:
        peak = peaks.lookup(devs[0].device_kind) if require_chip else None
    except KeyError as e:
        raise Failed(str(e))
    from repro.launch.cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}", flush=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = CompileCounter()

    stage = time.monotonic()

    def took(what):
        nonlocal stage
        now = time.monotonic()
        print(f"{what}: {now - stage:.3f} s", flush=True)
        stage = now

    engine, feed, init, key, fam, mc = build(cell, args.seed)
    took("build (corpus, weights, engine)")
    t = cell.traffic
    dep = cell.config["deployment"]
    R, B, S = dep["replicas"], t["per_replica_batch"], t["seq_len"]
    got = first_iterations(engine, feed, init, key,
                           cell.config["optimizer"]["b1"])
    took("checked iterations")
    k = N_CHECKED
    warm = int(t["warmup_steps"])
    if warm < max(k_sample(cell), N_CHECKED):
        raise Failed("warm-up must cover the sampling window")
    engine.run(k, warm - k)
    k = warm
    jax.block_until_ready(engine.W)
    took("warm-up")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    n_compiles = compiles.n
    t0 = time.monotonic()
    setup_s = t0 - t_start
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            engine.run(k, 1)
            k += 1
            if time.monotonic() - t0 >= args.seconds:
                break
        jax.block_until_ready(engine.W)
    t1 = time.monotonic()
    if trace_dir:
        jax.profiler.stop_trace()
    in_window = compiles.n - n_compiles
    compiles.close()
    steps = k - warm
    h = engine.history
    losses = h.losses[warm:k]
    failed = sum(not math.isfinite(v) for v in losses)
    print(f"window: {steps} steps in {t1 - t0:.4f} s; compiles inside the "
          f"window: {in_window}", flush=True)
    print(f"syncs at {h.sync_steps}; periods {h.period_history}", flush=True)
    peak_bytes = max(d.memory_stats()["peak_bytes_in_use"] for d in devs) \
        if require_chip else 0
    hist = {"sync_steps": list(h.sync_steps), "s_k": list(h.s_k),
            "periods": list(h.period_history), "n_steps": k}
    del engine, h
    gc.collect()
    print(f"device bytes held when the reference starts: "
          f"{sum(x.nbytes for x in jax.live_arrays())}", flush=True)

    stage = time.monotonic()
    values = _check(cell, fam, args.seed, got, hist)
    took("reference")
    import compare
    ok, rows = compare.judge(values, cell.limits)

    out = {"correct": ok, "attempted": steps, "failed": failed}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    if trace_dir:
        metrics, extra = _per_layer(cell, trace_dir, peak, fam, mc, R, B, S,
                                    steps)
        device.update(busy_s=extra["busy_s"], window_s=extra["window_s"])
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = extra["breakdown"]
    else:
        tokens = steps * R * B * S
        e2e = {"tokens_per_s": (tokens / (t1 - t0), "tokens/s"),
               "peak_hbm_gb": (peak_bytes / 1e9, "GB"),
               "setup_s": (setup_s, "s")}
        out["metrics"] = {m["name"]: {"value": e2e[m["name"]][0],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
        out["device"] = device
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in rows}
    for n, v, lim in rows:
        print(f"check {n} {v!r} limit {lim!r}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


def _check(cell, fam, seed, got, hist) -> dict:
    """The reference's numbers against the program's (``compare.py``)."""
    import compare
    from reference import train as ref_train
    from reference.numerics import Numerics
    a = cell.traffic["averaging"]
    ref = ref_train.run(fam, cell.config, a, k_sample(cell), seed31(seed),
                        got["batches"], Numerics())
    values = compare.numbers(got, ref)
    from reference.schedule import replay
    values["schedule"] = replay(
        a, k_sample(cell), float(cell.config["optimizer"]["lr"]),
        hist["n_steps"], hist["sync_steps"], hist["s_k"], hist["periods"])
    return values


def _per_layer(cell, trace_dir, peak, fam, mc, R, B, S, steps):
    import glob

    import jax
    import reduce_trace
    try:
        path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
        red = reduce_trace.reduce(jax.profiler.ProfileData.from_file(path))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = {"peak": peak, "chips": cell.chips,
           "flops_per_token": fam.flops_per_token(cell.config, S),
           "traced_tokens": steps * R * B * S}
    metrics = {}
    for m in cell.per_layer:
        v = metric_reader(cell, m["name"]).read(run, red)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    w0, w1 = red["window_ns"]
    busy = sum(d["busy_ns"] for d in red["devices"]) / len(red["devices"])
    return metrics, {"busy_s": busy / 1e9, "window_s": (w1 - w0) / 1e9,
                     "breakdown": reduce_trace.breakdown(red)}
