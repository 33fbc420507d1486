"""Reduction of a profiler trace to the numbers the per-layer metrics read.

``extract`` reads an ``.xplane.pb`` (JAX's profiler output) into plain
lists; ``reduce`` works on those lists alone, so it can be checked on a
small recorded trace (``tests/data``) without a chip.

  device  [[module, start_ns, dur_ns], ...]  "XLA Modules" of each chip
  ops     [[op, start_ns, dur_ns, chip], ...] "XLA Ops" of each chip
  host    [[span, start_ns, dur_ns], ...]    the benchmark's host spans
  calls   [[program, start_ns, dur_ns], ...] host dispatches of a jitted
                                             program (outermost only)

Device and host clocks of one trace can disagree by a millisecond or so.
Every program starts on the device after the host dispatched it, so the
device times are shifted by the largest amount that any pair of the
k-th dispatch and the k-th device run of one program is early by.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Any, Dict, List, Sequence, Tuple

SPANS = ("engine.step", "runner.chunk", "runner.prefill",
         "runner.warm_prefill", "runner.dispatch_decode",
         "runner.wait_decode")
OUTSIDE = "outside engine.step"


def module_name(event: str) -> str:
    """'jit__decode_impl(1234)' -> '_decode_impl'."""
    name = event.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


def op_name(event: str) -> str:
    """'%fusion.12 = bf16[..] fusion(..)' -> 'fusion.12'."""
    return event.split(" = ")[0].lstrip("%").strip()


def extract(path: str) -> Dict[str, Any]:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    out = {"device": [], "ops": [], "host": [], "calls": [], "chips": 0}
    pjit = re.compile(r"^PjitFunction\((.*)\)$")
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {l.name: l for l in plane.lines}
            if "XLA Modules" not in lines:
                continue
            chip = out["chips"]
            out["chips"] += 1
            for ev in lines["XLA Modules"].events:
                out["device"].append([module_name(ev.name), ev.start_ns,
                                      ev.duration_ns])
            for ev in lines.get("XLA Ops").events if "XLA Ops" in lines \
                    else ():
                out["ops"].append([op_name(ev.name), ev.start_ns,
                                   ev.duration_ns, chip])
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                last_end = {}
                for ev in line.events:
                    if ev.name in SPANS:
                        out["host"].append([ev.name, ev.start_ns,
                                            ev.duration_ns])
                        continue
                    m = pjit.match(ev.name)
                    if m:
                        fn, end = m.group(1), ev.start_ns + ev.duration_ns
                        if ev.start_ns < last_end.get(fn, -1):
                            continue           # nested inside the last one
                        last_end[fn] = end
                        out["calls"].append([fn, ev.start_ns,
                                             ev.duration_ns])
    return out


def _union(intervals: Sequence[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _overlap(intervals: Sequence[Tuple[float, float]], a: float,
             b: float) -> float:
    return sum(max(0.0, min(b, y) - max(a, x)) for x, y in intervals)


def _innermost(host, lo: float, hi: float) -> List[Tuple[float, float, str]]:
    """[lo, hi] cut into pieces, each named by the innermost benchmark
    span open over it (the spans of one thread nest), else OUTSIDE."""
    marks = []
    for name, s, d in host:
        marks.append((s, 1, name))
        marks.append((s + d, 0, name))
    marks.sort(key=lambda m: (m[0], m[1]))
    stack: List[str] = []
    out, at = [], lo
    for t, start, name in marks + [(hi, 0, None)]:
        t = min(max(t, lo), hi)
        if t > at:
            out.append((at, t, stack[-1] if stack else OUTSIDE))
            at = t
        if name is None:
            break
        if start:
            stack.append(name)
        elif name in stack:
            stack.reverse()
            stack.remove(name)
            stack.reverse()
    return out


def skew_ns(t: Dict[str, Any]) -> float:
    """How far the device clock runs early against the host's."""
    starts = defaultdict(list)
    for name, s, _ in sorted(t["calls"], key=lambda e: e[1]):
        starts[name].append(s)
    k = defaultdict(int)
    worst = 0.0
    for name, s, _ in sorted(t["device"], key=lambda e: e[1]):
        i = k[name]
        k[name] += 1
        if i < len(starts.get(name, ())):
            worst = min(worst, s - starts[name][i])
    return worst


def reduce(t: Dict[str, Any], top: int = 10) -> Dict[str, Any]:
    """Busy and idle time of the device over the traced steps, per-module
    device times, and where the idle time fell on the host."""
    shift = -skew_ns(t)
    steps = sorted((s, s + d) for n, s, d in t["host"] if n == "engine.step")
    if not steps:
        return {}
    lo, hi = steps[0][0], steps[-1][1]
    chips = max(1, t.get("chips", 1))
    per_chip = []
    for c in range(chips):
        ops = [(s + shift, s + shift + d) for _, s, d, k in t["ops"]
               if k == c]
        per_chip.append([(max(a, lo), min(b, hi)) for a, b in _union(ops)
                         if b > lo and a < hi])
    # idle gaps are those of the union over chips: no chip ran anything
    busy = _union([iv for c in per_chip for iv in c])
    modules: Dict[str, List[float]] = defaultdict(list)
    for name, s, d in t["device"]:
        if lo <= s + shift < hi:
            modules[name].append(d * 1e-9)
    step_ns = sum(b - a for a, b in steps)
    step_busy = sum(_overlap(c, a, b) for c in per_chip
                    for a, b in steps) / chips

    # device time per op, named by the program it ran in
    runs = sorted((s, s + d, n) for n, s, d in t["device"])
    starts = [r[0] for r in runs]
    per_op: Dict[str, float] = defaultdict(float)
    for name, s, d, _ in t["ops"]:
        if lo <= s + shift < hi:
            k = bisect.bisect_right(starts, s) - 1
            prog = runs[k][2] if k >= 0 and s < runs[k][1] else "?"
            per_op[f"{prog}:{name}"] += d * 1e-9 / chips
    # idle time inside [lo, hi], each part put under the innermost
    # benchmark span open over it
    idle: Dict[str, float] = defaultdict(float)
    gaps, edge = [], lo
    for a, b in busy + [(hi, hi)]:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)
    labels = _innermost(t["host"], lo, hi)
    i = 0
    for a, b in gaps:
        while i < len(labels) and labels[i][1] <= a:
            i += 1
        j = i
        while j < len(labels) and labels[j][0] < b:
            x, y, name = labels[j]
            idle[name] += (min(b, y) - max(a, x)) * 1e-9
            j += 1
    window = (hi - lo) * 1e-9
    busy_s = sum(b - a for c in per_chip for a, b in c) * 1e-9 / chips
    return {
        "window_s": window,
        "busy_s": busy_s,
        "step_s": step_ns * 1e-9,
        "step_idle_share": 1.0 - step_busy / step_ns if step_ns else None,
        "modules": dict(modules),
        "device_ops": sorted(per_op.items(), key=lambda x: -x[1])[:top],
        "idle_gaps": sorted(idle.items(), key=lambda x: -x[1])[:top],
        "skew_ns": -shift,
    }
