"""From a profiler trace (``.xplane.pb``) to device busy time, idle share,
the operations that took most time and what the host did in the longest
idle gaps. Read with ``jax.profiler.ProfileData`` and nothing else.

What a TPU trace holds (looked at by hand, PR 26): one plane a chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` has one event for every
operation that ran, and whose lines ``XLA Modules`` and ``Steps`` have
events that *enclose* those: counting them too would double the busy time.
The host is the plane ``/host:CPU``, one line a thread; the harness's own
``TraceAnnotation`` spans (``bench.*``) are events there, on the same clock.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."


def newest_xplane(trace_dir: str):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path: str) -> dict:
    """``{plane: {line: [(name, start_ns, end_ns), ...]}}``, events sorted
    by start."""
    from jax.profiler import ProfileData

    if path.endswith(".textproto"):  # the tests' hand-written fixture
        with open(path) as f:
            data = ProfileData.from_text_proto(f.read())
    else:
        data = ProfileData.from_file(path)
    planes = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            events.extend((e.name, float(e.start_ns),
                           float(e.start_ns) + float(e.duration_ns))
                          for e in line.events)
    for lines in planes.values():
        for events in lines.values():
            events.sort(key=lambda e: e[1])
    return planes


def describe(planes: dict, top: int = 6) -> list:
    """A hand's look at a trace: every plane and line, how many events,
    the span they cover and the names that took most time."""
    out = []
    for pname, lines in planes.items():
        for lname, events in lines.items():
            if not events:
                continue
            by_name = {}
            for name, s, e in events:
                by_name[name] = by_name.get(name, 0.0) + (e - s)
            names = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
            out.append({"plane": pname, "line": lname, "events": len(events),
                        "from_ns": events[0][1],
                        "to_ns": max(e for _, _, e in events),
                        "top": [[n, round(d / 1e9, 6)] for n, d in names]})
    return out


_HLO = re.compile(r"^%?\S+ = (.*?) ([a-z][\w\-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_KIND = re.compile(r"kind=(\w+)")


def op_signature(name: str) -> str:
    """An operation's kind and result type, without its number and its
    layouts: the trace names an operation by its whole HLO line, and the
    number in ``%fusion.205`` changes with every compile, so the 24 layers'
    copies of one fusion are summed under what they share."""
    m = _HLO.match(name)
    if not m:
        return name[:80]
    kind = _KIND.search(name)
    op = m.group(2) + ("/" + kind.group(1) if kind else "")
    return f"{op} {_LAYOUT.sub('', m.group(1))[:70]}"


def union(intervals, lo: float, hi: float) -> list:
    """The merged parts of ``intervals`` that lie inside [lo, hi]."""
    merged = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def whole_steps_window(modules: list):
    """First start to last end of the step program's whole runs: the module
    that took most time in all, its first and last run left out where the
    trace may have cut them (they are kept when there are under four)."""
    if not modules:
        return None
    total = {}
    for name, s, e in modules:
        total[name] = total.get(name, 0.0) + (e - s)
    step = max(total, key=total.get)
    runs = [(s, e) for name, s, e in modules if name == step]
    if len(runs) >= 4:
        runs = runs[1:-1]
    return runs[0][0], runs[-1][1], len(runs), step


def host_span_at(host_lines: dict, lo: float, hi: float) -> str:
    """The harness's span that covers most of [lo, hi] on the host."""
    best, cover = "outside_harness_spans", 0.0
    for events in host_lines.values():
        for name, s, e in events:
            if not name.startswith(SPAN_PREFIX) or e <= lo or s >= hi:
                continue
            c = min(e, hi) - max(s, lo)
            if c > cover:
                best, cover = name, c
    return best


def reduce(planes: dict, top: int = 10):
    """Busy and window seconds averaged over the chips in the trace, the
    idle share, and the contract's ``breakdown``. None where no operation
    ran on a device."""
    host = planes.get(HOST_PLANE, {})
    busy, window, steps = [], [], []
    ops_time, ops_count, gaps = {}, {}, []
    step_name = None
    for pname, lines in planes.items():
        if not pname.startswith(DEVICE_PLANE) or not lines.get(OPS_LINE):
            continue
        ops = lines[OPS_LINE]
        span = whole_steps_window(lines.get(MODULES_LINE, []))
        if span is None:
            span = (ops[0][1], max(e for _, _, e in ops), 0, None)
        lo, hi, n, step_name = span
        merged = union(((s, e) for _, s, e in ops), lo, hi)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        window.append((hi - lo) / 1e9)
        steps.append(n)
        for name, s, e in ops:
            if s >= lo and e <= hi:
                sig = op_signature(name)
                ops_time[sig] = ops_time.get(sig, 0.0) + (e - s) / 1e9
                ops_count[sig] = ops_count.get(sig, 0) + 1
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for g_lo, g_hi in zip(edges[0::2], edges[1::2]):
            if g_hi > g_lo:
                gaps.append((g_hi - g_lo, g_lo, g_hi))
    if not busy or sum(busy) <= 0:
        return None
    chips = len(busy)
    by_span = {}
    for length, g_lo, g_hi in sorted(gaps, reverse=True)[:200]:
        name = host_span_at(host, g_lo, g_hi)
        by_span[name] = by_span.get(name, 0.0) + length / 1e9
    busy_s, window_s = sum(busy) / chips, sum(window) / chips
    return {
        "busy_s": busy_s, "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "chips": chips, "steps": min(steps), "step_module": step_name,
        "breakdown": {
            "device_ops": [[f"{n} x{ops_count[n]}", t / chips]
                           for n, t in sorted(ops_time.items(),
                                              key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[n, t / chips] for n, t in sorted(
                by_span.items(), key=lambda kv: -kv[1])[:top]],
        },
    }


if __name__ == "__main__":  # python3 -m benchmark.lib.trace <file.xplane.pb>
    import json
    import sys

    _planes = load(sys.argv[1])
    print(json.dumps({"lines": describe(_planes), "reduced": reduce(_planes)},
                     indent=1))
