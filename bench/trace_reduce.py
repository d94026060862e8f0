"""Reduction of a profiler trace (``.xplane.pb``) to device busy time,
per-operation durations and idle gaps attributed to host spans.

The traced window is the host span ``bench:window`` that the kind opens
around the traced part of its run; device operations are the events of
each device plane's ``XLA Ops`` line. Busy time is the union of those
intervals inside the window, averaged over the devices used; an idle gap
is named after the innermost ``bench:`` host span that covers its middle.
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW = "bench:window"
OPS_LINE = "XLA Ops"


def find_trace(log_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return hits[-1]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _self_times(events):
    """``(name, seconds)`` per event, less the time of the events nested in
    it (a ``while`` op spans its body's ops on the same line)."""
    out, stack = [], []            # stack of [end, name, self_ns]
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            _, n, ns = stack.pop()
            out.append((n, ns * 1e-9))
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    out.extend((n, ns * 1e-9) for _, n, ns in stack)
    return out


def _clip(s: int, e: int, w0: int, w1: int) -> Optional[Tuple[int, int]]:
    s, e = max(s, w0), min(e, w1)
    return (s, e) if e > s else None


def load(path: str):
    """Host spans and device op events from one trace file:
    ``(spans, devices)`` with spans ``[(name, start_ns, end_ns)]`` and
    devices ``{plane: [(name, start_ns, end_ns)]}``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans, devices = [], {}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == OPS_LINE] or lines
            evs = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                   for ln in ops for e in ln.events]
            if evs:
                devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith("bench:"):
                        spans.append((e.name, int(e.start_ns),
                                      int(e.start_ns + e.duration_ns)))
    return spans, devices


def reduce_events(spans, devices, top: int = 10) -> dict:
    """Busy and idle time, op totals (self time) and attributed gaps, in
    seconds."""
    if not devices:
        raise ValueError("the trace has no device operations")
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if windows:
        w0, w1 = windows[-1]
    else:
        w0 = min(s for evs in devices.values() for _, s, _ in evs)
        w1 = max(e for evs in devices.values() for _, _, e in evs)
    inner = [(n, s, e) for n, s, e in spans if n != WINDOW]

    busy = []
    ops: Dict[str, List[float]] = collections.defaultdict(lambda: [0.0, 0])
    gaps_by_label: Dict[str, float] = collections.defaultdict(float)
    for dev_i, (plane, evs) in enumerate(sorted(devices.items())):
        clipped = []
        for name, s, e in evs:
            c = _clip(s, e, w0, w1)
            if c is not None:
                clipped.append((c[0], c[1], name))
        if dev_i == 0:
            for name, sec in _self_times(clipped):
                ops[name][0] += sec
                ops[name][1] += 1
        merged = _union([(s, e) for s, e, _ in clipped])
        busy.append(sum(e - s for s, e in merged))
        if dev_i == 0:
            prev = w0
            for s, e in merged + [(w1, w1)]:
                if s > prev:
                    gaps_by_label[_label(inner, (prev + s) // 2)] += \
                        (s - prev) * 1e-9
                prev = max(prev, e)
    window_ns = w1 - w0
    ranked_ops = sorted(ops.items(), key=lambda kv: -kv[1][0])
    ranked_gaps = sorted(gaps_by_label.items(), key=lambda kv: -kv[1])
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "ops": {name: {"seconds": v[0], "count": v[1]} for name, v in ops.items()},
        "gaps": dict(gaps_by_label),
        "breakdown": {
            "device_ops": [[short_name(name), v[0]]
                           for name, v in ranked_ops[:top]],
            "idle_gaps": [[label, sec] for label, sec in ranked_gaps[:top]],
        },
    }


def short_name(op: str) -> str:
    """``%fusion.187 = bf16[4,2048]{1,0:T(4,128)} fusion(...), ...`` ->
    ``fusion.187 bf16[4,2048] fusion``; other names pass unchanged."""
    if not op.startswith("%") or " = " not in op:
        return op
    name, rest = op[1:].split(" = ", 1)
    rest = re.sub(r"\{[^}]*\}", "", rest)        # drop layouts
    depth, end = 0, 0
    for i, ch in enumerate(rest):                # the result type ends at
        depth += ch == "("                       # the first space outside
        depth -= ch == ")"                       # parentheses
        if ch == " " and depth == 0:
            end = i
            break
    if not end:
        return name
    opcode = rest[end + 1:].split("(", 1)[0]
    kind = rest[:end] if len(rest[:end]) <= 48 else "(...)"
    return f"{name} {kind} {opcode}"


def _label(spans, t: int) -> str:
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0][len("bench:"):] if best else "host outside bench spans"


def reduce(path: str, top: int = 10) -> dict:
    spans, devices = load(path)
    return reduce_events(spans, devices, top)


def kernel_seconds(trace: dict, pattern: str) -> Tuple[float, int]:
    """Total device seconds and call count of the ops whose name (the HLO
    instruction as the trace gives it) matches the regular expression
    ``pattern``."""
    sec, n = 0.0, 0
    rx = re.compile(pattern)
    for name, v in trace["ops"].items():
        if rx.search(name):
            sec += v["seconds"]
            n += v["count"]
    return sec, n
