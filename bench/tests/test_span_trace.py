"""The reduction of a trace by the trainer's own ``repro.*`` spans
(``bench/span_trace.py``) on the CPU, from two traces recorded on a v5e
(OLMo-1B at 4 layers, 2 replicas): a benchmark window of a trainer without
those spans, and one of a trainer with them."""
import json

import pytest

from bench_paths import BENCH, ROOT

import harness  # noqa: E402
import reduce_trace  # noqa: E402
import span_trace  # noqa: E402

DATA = BENCH / "tests" / "data"
TRACE = DATA / "olmo_d4r2_window.xplane.pb.gz"
# what reduce_trace gives on TRACE
OLD_REDUCED = DATA / "olmo_d4r2_window.reduced.json"
# the same cell's window with the trainer's repro.* spans: 6 iterations,
# syncs at the first and the fourth
SPANS = DATA / "olmo_d4r2_window_spans.xplane.pb.gz"
ALL_MEAN_BYTES = 1485832192      # 2·(2−1)/2 × 4 B × 371,458,048 weights

RUN = {"peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
       "chips": 1, "flops_per_token": 2430074880.0,
       "traced_tokens": 6 * 2 * 2048}


def _read(name, run, red):
    cell = harness.Cell(ROOT, "x", {}, {}, {}, {}, [], [])
    return harness.metric_reader(cell, name).read(run, red)


@pytest.fixture(scope="module")
def old_pd():
    return span_trace.load(TRACE)


@pytest.fixture(scope="module")
def spans_pd():
    return span_trace.load(SPANS)


@pytest.fixture(scope="module")
def old(old_pd):
    return span_trace.reduce(old_pd)


@pytest.fixture(scope="module")
def spans(spans_pd):
    return span_trace.reduce(spans_pd)


def test_old_trace_keeps_every_key_and_value(old_pd, old):
    """The benchmark's reduction of a trace without the trainer's spans is
    the recorded one, and the span reduction finds nothing to read there
    but the idle inside and outside program runs."""
    got = json.loads(json.dumps(reduce_trace.reduce(old_pd)))
    assert got == json.loads(OLD_REDUCED.read_text())
    assert old["iterations"] == 0 and old["sync_bytes"] == 0
    assert set(old["program_idle_ns"]) == {reduce_trace.IN_PROGRAM,
                                           span_trace.OUTSIDE}
    assert span_trace.per_iteration(old, tokens=7 * 2 * 2048) == {}


def test_old_trace_program_ns_by_module_name(old_pd, old):
    red = reduce_trace.reduce(old_pd)
    (dev,) = red["devices"]
    (mine,) = old["devices"]
    assert old["window_ns"] == red["window_ns"]
    assert mine["busy_ns"] == pytest.approx(dev["busy_ns"])
    assert set(mine["program_ns"]) == {
        "jit_step", "jit__lambda", "jit__threefry_fold_in",
        "jit_convert_element_type"}
    # the same runs that the dispatch spans split into step and sync; the
    # step's share there also holds key programs of the next iteration
    assert sum(mine["program_ns"].values()) \
        == pytest.approx(sum(dev["module_ns"].values()))
    assert 0 < dev["module_ns"]["step"] - mine["program_ns"]["jit_step"] \
        < 1e-4 * dev["module_ns"]["step"]
    w0, w1 = old["window_ns"]
    idle = sum(old["program_idle_ns"].values())
    assert idle == pytest.approx(w1 - w0 - dev["busy_ns"], rel=1e-9)


def test_spans_trace_fits_in_a_megabyte():
    assert SPANS.stat().st_size <= 1 << 20


def test_spans_trace_idle_split_tiles_the_idle(spans):
    w0, w1 = spans["window_ns"]
    (dev,) = spans["devices"]
    idle = spans["program_idle_ns"]
    assert sum(idle.values()) == pytest.approx(w1 - w0 - dev["busy_ns"],
                                               rel=1e-3)
    assert all(v >= 0 for v in idle.values())
    assert set(idle) <= {reduce_trace.IN_PROGRAM, span_trace.OUTSIDE} | {
        k for k in idle if k.startswith("repro.")}
    # stable module names: nothing anonymous is left in the window
    names = set(dev["program_ns"])
    assert {"jit_replica_step", "jit_all_mean"} <= names
    assert not names & {"jit__lambda", "jit_chunk", "jit_step"}


def _host_spans(pd, prefix):
    return sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for line in pd.find_plane_with_name("/host:CPU").lines
                  for e in line.events if e.name.startswith(prefix))


def _module_runs(pd, name, w0, w1):
    plane = pd.find_plane_with_name("/device:TPU:0")
    (line,) = [ln for ln in plane.lines if ln.name == "XLA Modules"]
    return sorted((e.start_ns, e.start_ns + e.duration_ns)
                  for e in line.events
                  if e.name.split("(")[0] == name
                  and w0 < e.start_ns < w1)


# the device's timestamps in a v5e trace lead the host's: every key
# program's run starts 0.6-1.3 ms before the host call that launched it
# (PERF.md, Open questions)
LEAD_NS = 1.5e6


@pytest.mark.parametrize("op", ["replica_step", "all_mean"])
def test_spans_trace_runs_pair_with_their_program_spans(spans_pd, spans, op):
    """Each run of a program starts within the span of the call that
    launched it, give or take the clocks' offset, and the offset stays
    under LEAD_NS."""
    w0, w1 = spans["window_ns"]
    calls = [s for s in _host_spans(spans_pd, f"repro.program.{op}")
             if s[2] == f"repro.program.{op}" and w0 <= s[0] and s[1] <= w1]
    runs = _module_runs(spans_pd, f"jit_{op}", w0, w1)
    assert calls and len(runs) == len(calls)
    for (s0, s1, _), (r0, _) in zip(calls, runs):
        assert s0 - LEAD_NS <= r0 <= s1


def test_spans_trace_defragmentation_sits_under_program_spans(spans_pd,
                                                            spans):
    """The allocator's stalls happen inside program calls, so
    dispatch_idle_ms is where they show."""
    w0, w1 = spans["window_ns"]
    progs = [s for s in _host_spans(spans_pd, "repro.program.")
             if w0 <= s[0] and s[1] <= w1]
    stalls = [s for s in _host_spans(spans_pd, "TpuClient::Defragment")
              if w0 <= s[0] and s[1] <= w1]
    assert len(stalls) == 4              # 2 syncs, each splits in two
    for s0, s1, _ in stalls:
        assert any(p0 <= s0 and s1 <= p1 for p0, p1, _ in progs)


def test_spans_trace_per_iteration(spans_pd, spans):
    tokens = RUN["traced_tokens"]
    assert spans["iterations"] == 6
    assert spans["sync_bytes"] == 2 * ALL_MEAN_BYTES
    got = span_trace.per_iteration(spans, tokens)
    assert got["sync_bytes_per_token"] == 2 * ALL_MEAN_BYTES / tokens
    assert set(span_trace.per_iteration(spans)) == {
        "dispatch_idle_ms", "readback_idle_ms", "loop_idle_ms"}
    idle = spans["program_idle_ns"]
    keys = {
        "dispatch_idle_ms": ("repro.program.replica_step",
                             "repro.program.all_mean"),
        "readback_idle_ms": ("repro.readback.loss", "repro.readback.s_k"),
        "loop_idle_ms": ("repro.iteration", "repro.input", "repro.keys"),
    }
    assert set(idle) == {k for ks in keys.values() for k in ks} | {
        reduce_trace.IN_PROGRAM, span_trace.OUTSIDE}
    for name, ks in keys.items():
        assert got[name] == pytest.approx(sum(idle[k] for k in ks) / 6 / 1e6)
    # the recorded window: the defragmentation after each sync (about
    # 20 ms in the sync's call and 20 ms in the next step's), one loss
    # read-back that waited 107 ms on the runtime, four key programs of
    # about 0.7 ms of host time each per sync iteration
    assert got["dispatch_idle_ms"] == pytest.approx(15.304085, abs=1e-6)
    assert got["readback_idle_ms"] == pytest.approx(22.005625, abs=1e-6)
    assert got["loop_idle_ms"] == pytest.approx(4.101327, abs=1e-6)
    # the benchmark's own reduction and readers still read this trace
    red = reduce_trace.reduce(spans_pd)
    assert red["window_ns"] == spans["window_ns"]
    assert red["dispatches"] == {"step": 6, "sync": 2}
    assert _read("local_step_ms", RUN, red) \
        == pytest.approx(158.989362, abs=1e-6)
    assert _read("sync_ms", RUN, red) == pytest.approx(28.263589, abs=1e-6)


def test_window_without_bench_span_spans_the_iterations(spans_pd):
    """A trace of the trainer alone, outside the benchmark, has no
    ``bench.window``: the window runs from the first iteration's start to
    the last one's end."""
    host = spans_pd.find_plane_with_name("/host:CPU")
    its = [m for m in span_trace._marks(host)
           if m[2] == span_trace.ITERATION]
    w = span_trace._window(host, [])
    assert w[0] < its[0][0]              # the bench.window span wins
    assert span_trace._window(_NoWindow(host), its) \
        == (its[0][0], max(m[1] for m in its))


class _NoWindow:
    """A host plane with the ``bench.window`` span left out."""

    def __init__(self, host):
        self.lines = [_Line([e for e in ln.events
                             if e.name != reduce_trace.WINDOW])
                      for ln in host.lines]


class _Line:
    def __init__(self, events):
        self.events = events


def test_main_prints_one_json_object(capsys):
    assert span_trace.main([str(SPANS), "--tokens", "24576"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["iterations"] == 6
    assert out["sync_bytes_per_token"] == 2 * ALL_MEAN_BYTES / 24576
