"""Reduce a JAX profiler trace (``.xplane.pb``) of the trainer by the
trainer's own host spans, and print what it reads.

The trainer marks its work with host spans ``repro.*``
(``runtime/engine.py``, ``backends/base.py``): ``repro.iteration`` around
each engine iteration, inside it ``repro.input``, ``repro.keys``,
``repro.program.<op>`` (stat ``bytes``: the op's wire bytes),
``repro.readback.loss``, ``repro.readback.s_k`` and
``repro.callback.<class>``, each with the stat ``step``.  Its programs
compile to the XLA modules ``jit_<op>``.  The window is the benchmark's
``bench.window`` span where the trace has one, else the stretch from the
first ``repro.iteration`` to the last.  From those:

* ``program_ns`` of a device is its device time per module name, the
  fingerprint in parentheses dropped.
* ``iterations`` counts the ``repro.iteration`` spans inside the window and
  ``sync_bytes`` sums the ``bytes`` of the ``repro.program.*`` spans there.
* ``program_idle_ns`` gives each instant of device idle in the window to
  the innermost ``repro.*`` span covering it, to ``inside program`` where a
  module run covers it, and to ``outside`` where neither does; averaged
  over the devices.

``per_iteration`` turns that into the device idle per iteration under
program calls, read-backs and the rest of the loop, and the wire bytes per
trained token.

Run ``python bench/span_trace.py TRACE.xplane.pb[.gz] [--tokens N]`` on a
trace of ``jax.profiler.trace`` around ``TrainerEngine.run`` (DESIGN.md
§6, "Tracing"); ``--tokens`` is the number of tokens trained in the
window.  It prints one JSON object.
"""
from __future__ import annotations

import argparse
import bisect
import gzip
import json
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from reduce_trace import IN_PROGRAM, WINDOW, _events, _union

SPAN = "repro."
ITERATION = "repro.iteration"
PROGRAM = "repro.program."
READBACK = "repro.readback."
OUTSIDE = "outside"


def reduce(pd) -> dict:
    """``pd`` is a ``jax.profiler.ProfileData``."""
    host = pd.find_plane_with_name("/host:CPU")
    marks = _marks(host)
    w0, w1 = _window(host, marks)
    marks = [m for m in marks if m[1] > w0 and m[0] < w1]
    cuts, owner = _innermost(marks, w0, w1)
    program_idle: Dict[str, float] = defaultdict(float)
    devices = []
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if "XLA Ops" not in lines:
            continue
        mods = [m for m in _events(lines["XLA Modules"])
                if m[2] > w0 and m[1] < w1] if "XLA Modules" in lines else []
        busy = _union([(max(s, w0), min(e, w1))
                       for _, s, e in _events(lines["XLA Ops"])
                       if e > w0 and s < w1])
        idle = []
        prev = w0
        for s, e in busy + [(w1, w1)]:
            if s > prev:
                idle.append((prev, s))
            prev = max(prev, e)
        runs = _union([(max(s, w0), min(e, w1)) for _, s, e in mods])
        free, in_program = _outside(idle, runs)
        program_idle[IN_PROGRAM] += in_program
        for label, ns in _assign(free, cuts, owner).items():
            program_idle[label] += ns
        program_ns: Dict[str, float] = defaultdict(float)
        for name, s, e in mods:
            program_ns[name.split("(", 1)[0]] += min(e, w1) - max(s, w0)
        devices.append({"plane": plane.name,
                        "busy_ns": sum(e - s for s, e in busy),
                        "program_ns": dict(program_ns)})
    if not devices:
        raise ValueError("no TPU device plane with XLA Ops in the trace")
    inner = [m for m in marks if m[0] >= w0 and m[1] <= w1]
    return {
        "window_ns": [w0, w1],
        "devices": devices,
        "iterations": sum(m[2] == ITERATION for m in inner),
        "sync_bytes": sum(m[3].get("bytes", 0) for m in inner
                          if m[2].startswith(PROGRAM)),
        "program_idle_ns": {k: v / len(devices)
                            for k, v in program_idle.items()},
    }


def per_iteration(red: dict, tokens: Optional[int] = None) -> dict:
    """Device idle per iteration in ms under the program calls
    (``dispatch_idle_ms``: launch cost and the allocator's
    defragmentation), the read-backs (``readback_idle_ms``) and the rest of
    the iteration's spans (``loop_idle_ms``: input, keys, callbacks and the
    loop itself), and with ``tokens`` the wire bytes per trained token
    (``sync_bytes_per_token``).  Empty where the trace holds no
    ``repro.iteration`` span."""
    n = red["iterations"]
    if not n:
        return {}
    idle = red["program_idle_ns"]

    def ms(keep):
        return sum(v for k, v in idle.items() if keep(k)) / n / 1e6

    out = {
        "dispatch_idle_ms": ms(lambda k: k.startswith(PROGRAM)),
        "readback_idle_ms": ms(lambda k: k.startswith(READBACK)),
        "loop_idle_ms": ms(lambda k: k.startswith(SPAN) and not
                           k.startswith((PROGRAM, READBACK))),
    }
    if tokens:
        out["sync_bytes_per_token"] = red["sync_bytes"] / tokens
    return out


def _marks(host):
    """The trainer's ``repro.*`` host spans as (start, end, name, stats),
    sorted."""
    out = []
    for line in host.lines:
        for e in line.events:
            if e.name.startswith(SPAN):
                s = float(e.start_ns)
                out.append((s, s + float(e.duration_ns), e.name,
                            dict(e.stats)))
    return sorted(out)


def _window(host, marks) -> Tuple[float, float]:
    for line in host.lines:
        for name, s, e in _events(line):
            if name == WINDOW:
                return s, e
    its = [m for m in marks if m[2] == ITERATION]
    if not its:
        raise ValueError(f"no {WINDOW!r} or {ITERATION!r} span in the trace")
    return its[0][0], max(m[1] for m in its)


def _innermost(marks, w0, w1):
    """Cut the window at every span edge; between cuts ``i`` and ``i + 1``
    the innermost span (latest start, then earliest end) is ``owner[i]``,
    or ``outside``."""
    cuts = sorted({w0, w1, *(min(max(t, w0), w1)
                             for s, e, _, _ in marks for t in (s, e))})
    owner = []
    active: List[tuple] = []
    j = 0
    for a in cuts[:-1]:
        while j < len(marks) and marks[j][0] <= a:
            active.append(marks[j])
            j += 1
        active = [m for m in active if m[1] > a]
        owner.append(max(active, key=lambda m: (m[0], -m[1]))[2]
                     if active else OUTSIDE)
    return cuts, owner


def _outside(iv, runs):
    """The parts of the sorted disjoint intervals ``iv`` that no interval
    of ``runs`` (sorted, disjoint) covers, and the length that they do."""
    free: List[Tuple[float, float]] = []
    covered = 0.0
    j = 0
    for s, e in iv:
        while j < len(runs) and runs[j][1] <= s:
            j += 1
        t = s
        i = j
        while i < len(runs) and runs[i][0] < e:
            rs, re_ = runs[i]
            if rs > t:
                free.append((t, rs))
            covered += min(re_, e) - max(rs, t)
            t = max(t, re_)
            i += 1
        if t < e:
            free.append((t, e))
    return free, covered


def _assign(iv, cuts, owner) -> Dict[str, float]:
    """Length of the intervals ``iv`` under each owner of ``_innermost``."""
    tally: Dict[str, float] = defaultdict(float)
    for s, e in iv:
        i = bisect.bisect_right(cuts, s) - 1
        while s < e and i < len(owner):
            b = min(e, cuts[i + 1])
            tally[owner[i]] += b - s
            s = b
            i += 1
    return tally


def load(path: Path):
    """A ``ProfileData`` from an ``.xplane.pb`` file, gzipped or not."""
    import jax
    raw = path.read_bytes()
    if path.suffix == ".gz":
        raw = gzip.decompress(raw)
    return jax.profiler.ProfileData.from_serialized_xspace(raw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", type=Path)
    ap.add_argument("--tokens", type=int, default=None,
                    help="tokens trained in the window")
    a = ap.parse_args(argv)
    red = reduce(load(a.trace))
    print(json.dumps({**red, **per_iteration(red, a.tokens)}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
