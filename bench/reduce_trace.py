"""Reduce a JAX profiler trace (``.xplane.pb``) of one benchmark window to
the plain record that the per-layer metric readers take.

The harness marks the window with a host span ``bench.window``, each
program dispatch with ``bench.dispatch.<action>`` (``step``, ``sync``) and
each input call with ``bench.input``.  Device planes are ``/device:TPU:<n>``
with the lines ``XLA Modules`` (one event per program run) and ``XLA Ops``.

* Busy time of a device is the union of its op intervals in the window;
  idle is the rest.
* A program run belongs to the dispatch in whose interval it ends: the
  trainer reads each step's loss and each sync's S_k back before it
  dispatches again, so a run ends before the next dispatch begins.
* Each idle gap is labelled by what the host was doing over most of it,
  or ``inside program`` where it falls inside a program run.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, Tuple

WINDOW = "bench.window"
DISPATCH = "bench.dispatch."
INPUT = "bench.input"

# host activity, most specific first
HOST_LABELS = (
    ("memory defragmentation", lambda n: "DefragmentMemory" in n),
    ("S_k read-back", lambda n: n.endswith(" record_sync")),
    ("loss read-back", lambda n: n.endswith("__float__")),
    ("input", lambda n: n == INPUT),
    ("step dispatch", lambda n: n == DISPATCH + "step"),
    ("sync dispatch", lambda n: n == DISPATCH + "sync"),
)
OTHER_HOST = "host loop"
IN_PROGRAM = "inside program"

_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64|f64)"
                    r"\[([\d,]*)\](\{[^}]*\})?")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
          "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
          "f64": 8}


def op_base(name: str) -> str:
    """``%fusion.12 = ...`` -> ``fusion``; fusions carry their kind."""
    head = name.split(" = ", 1)[0].lstrip("%")
    base = re.sub(r"(\.\d+|\.clone)+$", "", head)
    if base.startswith("fusion") or base.endswith("_fusion"):
        kind = re.search(r"kind=(k\w+)", name)
        return f"fusion({kind.group(1) if kind else '?'})"
    return base


def hbm_bytes(op_text: str) -> int:
    """Bytes of an op's results and operands that live in HBM (memory
    space 0), from the shapes and layouts in its HLO text.  Buffers that
    the compiler placed in another memory space (``S(1)`` in the layout)
    move no HBM bytes."""
    total = 0
    signature = op_text.split(", custom_call_target=")[0]
    for dtype, dims, layout in _SHAPE.findall(signature):
        if layout and re.search(r"S\([1-9]\d*\)", layout):
            continue
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _BYTES[dtype]
    return total


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def reduce(pd, kernels=("mean_and_sqdev",)) -> dict:
    """``pd`` is a ``jax.profiler.ProfileData``.  ``kernels`` are the op
    base names whose every call is kept with its HLO text."""
    host = pd.find_plane_with_name("/host:CPU")
    spans = []
    for line in host.lines:
        spans.extend(_events(line))
    win = [s for s in spans if s[0] == WINDOW]
    if not win:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    w0, w1 = win[0][1], win[0][2]
    inside = [s for s in spans if s[1] >= w0 and s[2] <= w1]
    disp = sorted((s[1], s[0][len(DISPATCH):]) for s in inside
                  if s[0].startswith(DISPATCH))
    starts = [d[0] for d in disp]
    n_disp: Dict[str, int] = defaultdict(int)
    for _, act in disp:
        n_disp[act] += 1
    inputs = [s for s in inside if s[0] == INPUT]

    labelled = []
    for name, s, e in inside:
        for rank, (label, match) in enumerate(HOST_LABELS):
            if match(name):
                labelled.append((s, e, rank))
                break
    labelled.sort()

    devices = []
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if "XLA Ops" not in lines:
            continue
        mods = sorted((m for m in _events(lines["XLA Modules"])
                       if m[2] > w0 and m[1] < w1), key=lambda m: m[1]) \
            if "XLA Modules" in lines else []
        module_ns: Dict[str, float] = defaultdict(float)
        mod_act = []
        for name, s, e in mods:
            i = bisect.bisect_right(starts, e) - 1
            act = disp[i][1] if i >= 0 else "before window"
            module_ns[act] += min(e, w1) - max(s, w0)
            mod_act.append((s, e, act))
        mstarts = [m[0] for m in mod_act]
        ops: Dict[str, float] = defaultdict(float)
        kept = []
        iv = []
        for name, s, e in _events(lines["XLA Ops"]):
            if e <= w0 or s >= w1:
                continue
            s, e = max(s, w0), min(e, w1)
            iv.append((s, e, name))
        # an op that encloses others (a while loop and its body) counts
        # only its own time
        iv.sort(key=lambda x: (x[0], -x[1]))
        self_ns = [e - s for s, e, _ in iv]
        stack = []
        for i, (s, e, _) in enumerate(iv):
            while stack and iv[stack[-1]][1] <= s:
                stack.pop()
            if stack and e <= iv[stack[-1]][1]:
                self_ns[stack[-1]] -= e - s
            stack.append(i)
        for (s, e, name), own in zip(iv, self_ns):
            j = bisect.bisect_right(mstarts, s) - 1
            act = mod_act[j][2] if j >= 0 and s < mod_act[j][1] else "?"
            base = op_base(name)
            ops[f"{act}:{base}"] += own
            if base in kernels:
                kept.append([base, e - s, hbm_bytes(name)])
        busy = _union([(s, e) for s, e, _ in iv])
        gaps: Dict[str, float] = defaultdict(float)
        prev = w0
        for s, e in busy + [(w1, w1)]:
            if s > prev:
                gaps[_label(prev, s, mod_act, mstarts, labelled)] += s - prev
            prev = max(prev, e)
        devices.append({
            "plane": plane.name,
            "busy_ns": sum(e - s for s, e in busy),
            "module_ns": dict(module_ns),
            "ops_ns": dict(ops),
            "kernels": kept,
            "gaps_ns": dict(gaps),
        })
    if not devices:
        raise ValueError("no TPU device plane with XLA Ops in the trace")
    return {
        "window_ns": [w0, w1],
        "dispatches": dict(n_disp),
        "input": {"n": len(inputs),
                  "ns": sum(e - s for _, s, e in inputs)},
        "devices": devices,
    }


def _label(g0, g1, mod_act, mstarts, labelled) -> str:
    j = bisect.bisect_right(mstarts, g0) - 1
    if j >= 0 and g1 <= mod_act[j][1]:
        return IN_PROGRAM
    # sweep the labelled host spans that overlap the gap: each instant
    # takes the most specific label covering it
    cover = []
    for s, e, r in labelled:
        if s >= g1:
            break
        if e > g0:
            cover.append((max(s, g0), min(e, g1), r))
    if not cover:
        return OTHER_HOST
    cuts = sorted({g0, g1, *[c[0] for c in cover], *[c[1] for c in cover]})
    tally: Dict[str, float] = defaultdict(float)
    for a, b in zip(cuts, cuts[1:]):
        ranks = [r for s, e, r in cover if s <= a and e >= b]
        tally[HOST_LABELS[min(ranks)][0] if ranks else OTHER_HOST] += b - a
    return max(tally, key=tally.get)


def breakdown(red: dict, top: int = 10) -> dict:
    """The device ops that took most time and the idle gaps by label, in
    seconds averaged over the devices."""
    n = len(red["devices"])
    ops: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    for d in red["devices"]:
        for k, v in d["ops_ns"].items():
            ops[k] += v / n / 1e9
        for k, v in d["gaps_ns"].items():
            gaps[k] += v / n / 1e9
    return {
        "device_ops": sorted(([k, v] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v] for k, v in gaps.items()),
                            key=lambda kv: -kv[1])[:top],
    }
