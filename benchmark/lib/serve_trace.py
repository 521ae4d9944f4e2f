"""From a profiler trace of a serving engine under load to device busy
time, idle share and the device time of a decode step and of a prefill
chunk. Built on ``lib.trace``'s reading of the file.

A serving trace holds many runs of several compiled programs (a decode
entry a bucket, one prefill entry), not one step program, so the window is
the first start to the last end of all device programs' runs, the first and
the last run left out where the trace may have cut them. Which kind a run
was: by its module's name where that says ``decode`` or ``prefill``; else by
the harness's annotation (``bench.decode_round``, ``bench.prefill_chunk``,
put around the scheduler's two rounds for the traced stretch) under which
the run's middle lies on the host's clock.
"""
from __future__ import annotations

import bisect

from benchmark.lib import trace

KINDS = {"decode": "bench.decode_round", "prefill": "bench.prefill_chunk"}


def _spans(host: dict, name: str) -> list:
    return sorted((s, e) for events in host.values()
                  for n, s, e in events if n == name)


def _inside(spans: list, t: float) -> bool:
    i = bisect.bisect_right(spans, (t, float("inf"))) - 1
    return i >= 0 and spans[i][0] <= t <= spans[i][1]


def kind_of(name: str, mid: float, host_spans: dict):
    low = name.lower()
    for kind in KINDS:
        if kind in low:
            return kind
    for kind, spans in host_spans.items():
        if _inside(spans, mid):
            return kind
    return None


def reduce(planes: dict, top: int = 10):
    """``lib.trace.reduce``'s keys (busy and window seconds averaged over
    the chips, idle share, breakdown) and ``runs``: the device milliseconds
    of every whole run of a kind, ``{"decode": [...], "prefill": [...],
    "other": [...]}``. None where no operation ran on a device."""
    host = planes.get(trace.HOST_PLANE, {})
    host_spans = {kind: _spans(host, name) for kind, name in KINDS.items()}
    busy, window, gaps = [], [], []
    ops_time, ops_count = {}, {}
    runs = {"decode": [], "prefill": [], "other": []}
    for pname, lines in planes.items():
        if (not pname.startswith(trace.DEVICE_PLANE)
                or not lines.get(trace.OPS_LINE)):
            continue
        ops = lines[trace.OPS_LINE]
        modules = lines.get(trace.MODULES_LINE, [])
        whole = modules[1:-1] if len(modules) >= 4 else modules
        if whole:
            lo, hi = whole[0][1], max(e for _, _, e in whole)
        else:
            lo, hi = ops[0][1], max(e for _, _, e in ops)
        for name, s, e in whole:
            kind = kind_of(name, (s + e) / 2.0, host_spans) or "other"
            runs[kind].append((e - s) / 1e6)
        merged = trace.union(((s, e) for _, s, e in ops), lo, hi)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        window.append((hi - lo) / 1e9)
        for name, s, e in ops:
            if s >= lo and e <= hi:
                sig = trace.op_signature(name)
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
    for length, g_lo, g_hi in sorted(gaps, reverse=True)[:400]:
        name = trace.host_span_at(host, g_lo, g_hi)
        by_span[name] = by_span.get(name, 0.0) + length / 1e9
    busy_s, window_s = sum(busy) / chips, sum(window) / chips
    return {
        "busy_s": busy_s, "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s, "chips": chips,
        "runs": runs,
        "breakdown": {
            "device_ops": [[f"{n} x{ops_count[n]}", t / chips]
                           for n, t in sorted(ops_time.items(),
                                              key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[n, t / chips] for n, t in sorted(
                by_span.items(), key=lambda kv: -kv[1])[:top]],
        },
    }


def whole(traced):
    """``traced`` as it came, or an error where it holds device runs and
    none could be told to be a decode step: the metrics that read the
    kinds must not fall silent because the program renamed what the
    harness tells them apart by."""
    if traced is not None and not traced["runs"]["decode"]:
        raise RuntimeError(
            "the trace holds device runs and none could be told to be a "
            f"decode step: { {k: len(v) for k, v in traced['runs'].items()} }")
    return traced


def mean_ms(traced, kind: str):
    """Mean device milliseconds a run of ``kind``; nothing where the trace
    holds none."""
    if not traced or not traced["runs"].get(kind):
        return None
    values = traced["runs"][kind]
    return sum(values) / len(values)
