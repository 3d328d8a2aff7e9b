"""From a profiler trace to device busy time, idle gaps and op times.

``read_xplane`` pulls three kinds of events out of an ``.xplane.pb``:
device operations, device program executions ("modules") and the
benchmark's own host spans (``bench.*`` annotations).  ``reduce`` turns
them into numbers over the traced window, which is the ``bench.window``
span where the run recorded one.

Which planes and lines hold the device's events depends on the
platform; ``RULES`` says so for the TPU and for the CPU (the CPU rule
exists so that the reduction can be checked on a trace recorded here).
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)

_CPU_BOOKKEEPING = ("ThreadpoolListener", "SlinkyThreadPool", "end: ",
                    "ThunkExecutor")

RULES = {
    "tpu": {
        "device_plane": lambda p: p.startswith("/device:TPU:"),
        "op_line": lambda line, ev: line == "XLA Ops",
        "module_line": lambda line, ev: line == "XLA Modules",
    },
    "cpu": {
        "device_plane": lambda p: p == "/host:CPU",
        "op_line": lambda line, ev: (line.startswith("tf_XLA")
                                     and not ev.startswith(_CPU_BOOKKEEPING)),
        "module_line": lambda line, ev: ev == "PjRtCpuExecutable::Execute",
    },
}

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
# What the host was doing in an idle gap: the span kind that covers
# most of it, or, where no span covers most, the engine thread between
# ticks (its backoff, or Python outside ``tick``).
GAP_LABELS = ("bench.tick", "bench.submit", "bench.pump")
OUTSIDE = "engine_outside_tick"


def read_xplane(path: str, platform: str) -> Dict:
    """Events of one trace file: per device plane its ops and modules as
    ``(name, start_ns, end_ns)``, and the host spans."""
    from jax.profiler import ProfileData

    rule = RULES[platform]
    data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, list]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        is_dev = rule["device_plane"](plane.name)
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name.startswith(SPAN_PREFIX):
                    spans.append((name, ev.start_ns, ev.end_ns))
                    continue
                if not is_dev:
                    continue
                d = devices.setdefault(plane.name, {"ops": [], "modules": []})
                if rule["module_line"](line.name, name):
                    d["modules"].append((name, ev.start_ns, ev.end_ns))
                elif rule["op_line"](line.name, name):
                    d["ops"].append((name, ev.start_ns, ev.end_ns))
    return {"devices": devices, "spans": spans}


def op_name(text: str) -> str:
    """A TPU op event is named by its whole HLO instruction; keep the
    instruction's name and the start of its result type."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text
    return f"{name.lstrip('%')} {rest.split(' ', 1)[0].split('{', 1)[0]}"[:80]


def self_times(events: List[Tuple[str, float, float]]):
    """``(name, self_ns)`` of each event of one line: its length less
    the events nested inside it (a loop's body ops sit inside the
    loop's own event)."""
    out, stack = [], []          # stack of [name, start, end, child_ns]
    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= a:
            n, sa, sb, ch = stack.pop()
            out.append((n, (sb - sa) - ch))
        if stack:
            stack[-1][3] += b - a
        stack.append([name, a, b, 0.0])
    while stack:
        n, sa, sb, ch = stack.pop()
        out.append((n, (sb - sa) - ch))
    return out


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def complement(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def overlap(merged: List[Interval], starts: List[float], a: float,
            b: float) -> float:
    """Length of ``[a, b)`` covered by ``merged`` (sorted, disjoint)."""
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    got = 0.0
    while i < len(merged) and merged[i][0] < b:
        lo, hi = max(merged[i][0], a), min(merged[i][1], b)
        if hi > lo:
            got += hi - lo
        i += 1
    return got


def reduce(events: Dict, top: int = 10) -> Optional[Dict]:
    """Numbers over the traced window; None when no device operation
    ran in it."""
    spans = events["spans"]
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    devs = events["devices"]
    all_ops = [e for d in devs.values() for e in d["ops"]]
    if not all_ops:
        return None
    if win:
        lo, hi = win[0][1], win[0][2]
    else:
        lo = min(e[1] for e in all_ops)
        hi = max(e[2] for e in all_ops)
    window_ns = hi - lo
    by_label = {lab: union([(a, b) for n, a, b in spans if n == lab])
                for lab in GAP_LABELS}
    by_label[OUTSIDE] = union([(a, b) for n, a, b in spans
                               if n in GAP_LABELS])
    starts = {lab: [a for a, _ in iv] for lab, iv in by_label.items()}
    busy_ns, op_ns, gaps = [], defaultdict(float), []
    mod_n, mod_ns = 0, 0.0
    for d in devs.values():
        ops = clip([(a, b) for _, a, b in d["ops"]], lo, hi)
        busy = union(ops)
        busy_ns.append(sum(b - a for a, b in busy))
        inside = [(n, max(a, lo), min(b, hi)) for n, a, b in d["ops"]
                  if b > lo and a < hi]
        for name, t in self_times(inside):
            op_ns[op_name(name)] += t
        for a, b in complement(busy, lo, hi):
            cover = [(overlap(by_label[lab], starts[lab], a, b), lab)
                     for lab in GAP_LABELS]
            spanned = overlap(by_label[OUTSIDE], starts[OUTSIDE], a, b)
            cover.append(((b - a) - spanned, OUTSIDE))
            label = max(cover, key=lambda c: c[0])[1]
            gaps.append((label.replace(SPAN_PREFIX, "", 1), (b - a) * 1e-9))
        mods = clip([(a, b) for _, a, b in d["modules"]], lo, hi)
        mod_n += len(mods)
        mod_ns += sum(b - a for a, b in mods)
    n_dev = len(devs)
    busy_s = sum(busy_ns) / n_dev * 1e-9
    window_s = window_ns * 1e-9
    ops_sorted = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
    gaps.sort(key=lambda g: -g[1])
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "step_count": mod_n,
        "step_device_s": mod_ns * 1e-9 / n_dev,
        "breakdown": {
            "device_ops": [[n, t * 1e-9 / n_dev] for n, t in ops_sorted],
            "idle_gaps": [[lab, s] for lab, s in gaps[:top]],
        },
    }
