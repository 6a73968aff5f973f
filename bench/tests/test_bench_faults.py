"""Whole benchmark runs on the CPU, past the harness's look for a chip, of
a throwaway OLMo-shaped cell whose files (configuration, traffic, limits,
a per-layer metric) live in a temporary directory: a sound run comes out
correct, and one with the timed path broken underneath comes out not
correct, under the limits of the benchmark's own OLMo cell."""
import argparse
import json
import shutil

import jax
import numpy as np
import pytest

from bench_paths import BENCH

import compare  # noqa: E402
import harness  # noqa: E402
from reference import olmo  # noqa: E402
from reference import train as ref_train  # noqa: E402
from reference.numerics import Numerics  # noqa: E402

LIMITS = BENCH / "limits" / "olmo1b-d4r2-adpsgd.json"
METRIC = '''
def read(run, red):
    return float(red["dispatches"].get("step", 0))
'''


@pytest.fixture
def cell_root(tmp_path, monkeypatch):
    """A checkout-shaped directory holding one throwaway cell."""
    d = tmp_path / "bench"
    for sub in ("configs", "traffic", "limits", "metrics"):
        (d / sub).mkdir(parents=True)
    c = json.loads((BENCH / "configs" / "olmo-1b.d4.r2.json").read_text())
    c.update(name="tiny", hidden_size=64, intermediate_size=128,
             num_attention_heads=4, num_key_value_heads=4,
             num_hidden_layers=2, vocab_size=256,
             dtypes={"params": "float32", "compute": "float32"})
    (d / "configs" / "tiny.json").write_text(json.dumps(c))
    t = json.loads((BENCH / "traffic" / "adpsgd.b1.s2048.json").read_text())
    t.update(seq_len=32, corpus=dict(t["corpus"], rows=64))
    (d / "traffic" / "tiny-mix.json").write_text(json.dumps(t))
    shutil.copy(LIMITS, d / "limits" / "tiny-cell.json")
    (d / "metrics" / "steps_traced.py").write_text(METRIC)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tiny", "file": "bench/configs/tiny.json"}],
        "workloads": [{"name": "tiny-cell", "config": "tiny",
                       "traffic": "tiny-mix", "chips": 1}],
        "end_to_end": [{"name": n, "unit": u} for n, u in
                       (("tokens_per_s", "tokens/s"), ("peak_hbm_gb", "GB"),
                        ("setup_s", "s"))],
        "per_layer": [{"name": "steps_traced", "unit": "1",
                       "workloads": ["tiny-cell"]},
                      {"name": "sync_ms", "unit": "ms",
                       "workloads": ["another-cell"]}]}))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    yield tmp_path
    for k, v in saved.items():
        jax.config.update(k, v)


def run_cell(root, capsys):
    args = argparse.Namespace(workload="tiny-cell", seed=2_500_000_001,
                              seconds=0.3, trace=0)
    assert harness.main(args, require_chip=False, root=root) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(out)[-1] == "checks"
    return out


def test_files_are_found_by_name(cell_root):
    cell = harness.load_cell("tiny-cell", cell_root)
    assert cell.config["hidden_size"] == 64
    assert cell.traffic["seq_len"] == 32
    assert [m["name"] for m in cell.per_layer] == ["steps_traced"]
    reader = harness.metric_reader(cell, "steps_traced")
    assert reader.read({}, {"dispatches": {"step": 7}}) == 7.0


def test_sound_run_is_correct(cell_root, capsys):
    out = run_cell(cell_root, capsys)
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"tokens_per_s", "peak_hbm_gb", "setup_s"}
    assert set(out["checks"]) == set(compare.NUMBERS)


def _unchanged(step):
    def broken(W, opt_state, batch, lr):
        _, _, metrics = step(W, opt_state, batch, lr)
        return W, opt_state, metrics
    return broken


def _answer_altered(step):
    def broken(W, opt_state, batch, lr):
        W, opt_state, metrics = step(W, opt_state, batch, lr)
        return W, opt_state, dict(metrics, loss=metrics["loss"] * 1.01)
    return broken


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "no_exchange", "answer_altered"])
def test_broken_timed_path_is_not_correct(fault, cell_root, capsys,
                                          monkeypatch):
    from repro.core import averaging as avg
    from repro.launch import steps
    if fault in ("state_unchanged", "answer_altered"):
        make = avg.make_local_step
        wrap = _unchanged if fault == "state_unchanged" else _answer_altered
        monkeypatch.setattr(avg, "make_local_step",
                            lambda *a: wrap(make(*a)))
    elif fault == "half_batch":
        make_loss = steps.make_loss_fn

        def half(mc):
            loss = make_loss(mc)

            def f(params, batch):
                B, S = batch["tokens"].shape
                mask = np.ones((B, S - 1), np.float32)
                mask[:, (S - 1) // 2:] = 0.0
                return loss(params, dict(batch, loss_mask=mask))
            return f
        monkeypatch.setattr(steps, "make_loss_fn", half)
    else:
        sync = avg.sync_replicas

        def no_exchange(W, opt_state=None, **kw):
            _, opt_state, s_k = sync(W, opt_state, **kw)
            return W, opt_state, s_k
        monkeypatch.setattr(avg, "sync_replicas", no_exchange)
    out = run_cell(cell_root, capsys)
    assert out["correct"] is False, out["checks"]


def test_control_is_not_correct(cell_root):
    """The reference in the program's place, with its weights and AdamW
    state kept in bfloat16, fails the cell's limits."""
    cell = harness.load_cell("tiny-cell", cell_root)
    rng = np.random.default_rng(0)
    batches = [rng.integers(0, 256, (2, 1, 32)).astype(np.int32)
               for _ in range(harness.N_CHECKED)]
    a, ks = cell.traffic["averaging"], harness.k_sample(cell)
    ref = ref_train.run(olmo, cell.config, a, ks, 7, batches, Numerics())
    ctl = ref_train.run(olmo, cell.config, a, ks, 7, batches,
                        Numerics(store="bfloat16", operands="bfloat16"))
    ok, rows = compare.judge(dict(compare.numbers(ctl, ref), schedule=0),
                             cell.limits)
    assert not ok, rows
    ok, _ = compare.judge(dict(compare.numbers(ref, ref), schedule=0),
                          cell.limits)
    assert ok
