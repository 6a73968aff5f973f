"""The benchmark's arithmetic on the CPU: the reduction of a trace recorded
on a v5e (OLMo-1B at 4 layers, 2 replicas, 7 steps and 3 syncs of a
benchmark window), model FLOPs per token, kernel bytes, the peak table,
the schedule replay and the comparison."""
import gzip
import json
import math
import subprocess
import sys

import numpy as np
import pytest

from bench_paths import BENCH, ROOT

import compare  # noqa: E402
import harness  # noqa: E402
import peaks  # noqa: E402
import reduce_trace  # noqa: E402
from reference import olmo, xlstm  # noqa: E402
from reference.schedule import Algorithm2, replay  # noqa: E402

TRACE = BENCH / "tests" / "data" / "olmo_d4r2_window.xplane.pb.gz"


@pytest.fixture(scope="module")
def red():
    import jax
    pd = jax.profiler.ProfileData.from_serialized_xspace(
        gzip.decompress(TRACE.read_bytes()))
    return reduce_trace.reduce(pd)


def _config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def _read(name, run, red):
    cell = harness.Cell(ROOT, "x", {}, {}, {}, {}, [], [])
    return harness.metric_reader(cell, name).read(run, red)


RUN = {"peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
       "chips": 1, "flops_per_token": 2430074880.0,
       "traced_tokens": 7 * 2 * 2048}


def test_trace_window_dispatches_and_busy_union(red):
    w0, w1 = red["window_ns"]
    assert red["dispatches"] == {"step": 7, "sync": 3}
    assert red["input"]["n"] == 7
    (dev,) = red["devices"]
    assert dev["plane"] == "/device:TPU:0"
    # op self times tile the busy union exactly: nested ops (a while loop
    # and its body) are not counted twice
    assert sum(dev["ops_ns"].values()) == pytest.approx(dev["busy_ns"])
    assert dev["busy_ns"] == pytest.approx(1197544228.0)
    assert w1 - w0 == pytest.approx(1375724352.0)
    idle = sum(dev["gaps_ns"].values())
    assert idle + dev["busy_ns"] == pytest.approx(w1 - w0)


def test_trace_programs_and_idle_share(red):
    assert _read("local_step_ms", RUN, red) == pytest.approx(158.9854522857)
    assert _read("sync_ms", RUN, red) == pytest.approx(28.2619756667)
    assert _read("device_idle_share", RUN, red) == pytest.approx(12.951731, 1e-6)
    assert _read("input_ms", RUN, red) == pytest.approx(0.5554814, 1e-6)
    mfu = _read("step_mfu", RUN, red)
    assert 25.7 < mfu < 25.71


def test_trace_gap_attribution(red):
    gaps = red["devices"][0]["gaps_ns"]
    order = sorted(gaps, key=gaps.get, reverse=True)
    # the allocator's defragmentation in the dispatch after each sync
    # leaves the device idle longest; then the per-step loss read-back
    assert order[0] == "memory defragmentation"
    assert gaps["memory defragmentation"] == pytest.approx(124865514.0)
    assert set(gaps) <= {lab for lab, _ in reduce_trace.HOST_LABELS} | {
        reduce_trace.OTHER_HOST, reduce_trace.IN_PROGRAM}
    bd = reduce_trace.breakdown(red)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][0][0] == "step:fusion(kOutput)"


def test_trace_kernel_roofline(red):
    calls = red["devices"][0]["kernels"]
    assert len(calls) == 3 * 29              # 29 leaves, 3 syncs
    assert all(k[0] == "mean_and_sqdev" for k in calls)
    share = _read("param_variance_roofline", RUN, red)
    assert share == pytest.approx(81.5731161, 1e-6)
    assert 0 < share <= 100


def test_kernel_hbm_bytes_from_shapes():
    hbm = ("%mean_and_sqdev.57 = (f32[50304,2048]{1,0:T(8,128)}, "
           "f32[128,2048]{1,0:T(8,128)S(1)}) custom-call(f32[2,50304,2048]"
           "{2,1,0:T(8,128)} %W__embed__.1), custom_call_target="
           "\"tpu_custom_call\", operand_layout_constraints="
           "{f32[2,50304,2048]{2,1,0}}")
    n = 50304 * 2048
    # read both replicas, write the mean; the accumulator lives in memory
    # space 1 and the layout constraint is not a buffer
    assert reduce_trace.hbm_bytes(hbm) == 2 * n * 4 + n * 4
    vmem = ("%mean_and_sqdev.30 = (f32[2048,2048]{1,0:T(8,128)S(1)}, "
            "f32[128,2048]{1,0:T(8,128)S(1)}) custom-call(f32[2,2048,2048]"
            "{2,1,0:T(8,128)S(1)} %copy-done.5), custom_call_target=")
    assert reduce_trace.hbm_bytes(vmem) == 0
    assert reduce_trace.op_base(vmem) == "mean_and_sqdev"
    assert reduce_trace.op_base("%fusion.12 = f32[2] fusion(), kind=kLoop") \
        == "fusion(kLoop)"


def test_flops_per_token():
    c = _config("olmo-1b.d4.r2")
    # 6 x 371.4M weights (tied head, no lookup) + 12·L·D·S attention
    assert olmo.flops_per_token(c, 2048) == 6 * 371458048 + 12 * 4 * 2048 * 2048
    assert olmo.flops_per_token(c, 2048) == pytest.approx(2.43e9, rel=1e-3)
    x = _config("xlstm-350m.d8.r2")
    D, Di, H, V = 1024, 2048, 4, 50304
    m_w = D * 2 * Di + 4 * Di + 3 * Di * Di + Di * 2 * H + Di * D
    s_w = D * 4 * D + H * 256 * 1024 + 3 * D * 1344
    want = 6 * (7 * m_w + s_w + V * D) + 7 * 3 * (4 * 256 * Di + 4 * 512 * Di)
    assert xlstm.flops_per_token(x, 2048) == want


def test_peak_table_refuses_unknown_kind():
    assert peaks.lookup("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.lookup("TPU v9 imaginary")


def test_schedule_replay():
    a = {"p_init": 2, "warmup_full_sync_steps": 2, "lower": 0.7,
         "upper": 1.3, "p_min": 1, "p_max": 256}
    ctl = Algorithm2(a, k_sample=8)
    steps, periods, s_k = [], [], []
    rng = np.random.default_rng(0)
    for k in range(60):
        if ctl.sync_now(k):
            s = float(rng.uniform(0.5, 1.5))
            ctl.observe(k, 4e-4, s)
            steps.append(k), periods.append(ctl.p), s_k.append(s)
    assert replay(a, 8, 4e-4, 60, steps, s_k, periods) == 0
    assert replay(a, 8, 4e-4, 60, steps, s_k, periods[:-1] + [99]) == 1
    assert replay(a, 8, 4e-4, 60, steps[:-1], s_k[:-1], periods[:-1]) == 1
    # a trainer stuck at p_init through the adaptive phase
    fixed = list(range(0, 2)) + list(range(3, 60, 2))
    assert replay(a, 8, 4e-4, 60, fixed, s_k, [2] * len(fixed)) > 0


def test_compare_worst_leaf_and_judge():
    ref = {"losses": [10.0, 9.0, 8.0], "s_k": [1.0, 2.0],
           "grad_norms": np.array([[1.0, 1.0], [2.0, 2.0], [1e-9, 1e-9]]),
           "update_norms": np.array([[1.0, 1.0], [1.0, 1.0], [5.0, 5.0]])}
    got = dict(ref, update_norms=ref["update_norms"] * [[1.0], [1.01], [3]])
    v = compare.numbers(got, ref)
    # the third leaf has no gradient in the reference: its change is left
    # out; the second leaf's 1 % gap is read against its own norm
    assert v["update"] == pytest.approx(0.01)
    assert v["loss"] == 0.0 and v["grad"] == 0.0
    lim = {"loss": 1e-3, "grad": 1e-2, "s_k": 1e-2, "update": 2e-2,
           "schedule": 0}
    ok, rows = compare.judge(dict(v, schedule=0), lim)
    assert ok and [r[0] for r in rows] == list(compare.NUMBERS)
    ok, _ = compare.judge(dict(v, schedule=1), lim)
    assert not ok
    ok, _ = compare.judge(dict(v, schedule=0, loss=math.nan), lim)
    assert not ok


def test_corpus_from_large_seed_is_deterministic():
    import tokens
    t = {"rows": 8, "a": 31, "c": 17, "eps": 0.1}
    a = tokens.corpus(3_000_000_017 % 2 ** 31, 50304, 64, t)
    b = tokens.corpus(3_000_000_017 % 2 ** 31, 50304, 64, t)
    assert a.shape == (8, 64) and a.dtype == np.int32
    assert np.array_equal(a, b) and a.max() < 50304
    assert not np.array_equal(a, tokens.corpus(5, 50304, 64, t))


def test_run_exits_nonzero_without_tpu():
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "HOME": str(ROOT)}
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "olmo1b-d4r2-adpsgd", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=ROOT, timeout=120)
    assert p.returncode != 0
    assert "device: platform=cpu" in p.stdout
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
