"""The device's idle time in a trace of a serving engine under load, booked
to the host phase of the program's token scheduler that held the chip.

The scheduler (``inference/serving/decode.py::DecodeScheduler``) opens its
own spans, ``pt.serve.*`` events on the host's plane of the trace, on the
device's clock: ``serve.iter`` around an iteration, a round span around
each pass that runs a compiled entry, and inside them phases, one open
innermost at every moment: ``admit``, ``blocks``, ``arrays`` (the feed and
its ``device_put``), ``dispatch`` (the jitted call until it returns),
``fetch`` (the tokens' copy to the host, waiting for the device first),
``tokens`` and ``retire``.

Over ``serve_trace.reduce``'s window (first to last whole run of any
compiled entry, the first and the last run left out), every idle stretch of
a chip (no ``XLA Ops`` event open) is split: the part that lies inside a
run of the ``XLA Modules`` line goes to ``in_step`` (gaps between the
operations of one compiled step, which no host change removes); the rest,
piece by piece, to the innermost ``pt.serve.*`` span open on the host over
that piece. A gap between two runs is not booked whole to one name: it
spans the tail of a fetch, the tokens, the retirement, the admission, the
blocks, the arrays and the dispatch of the next run. Idle time under no
phase (outside every ``pt.serve.*`` span, or in an iteration or a round
with no phase open) is ``unspanned``: the check that the split is whole.

Times are milliseconds a whole decode run of the window (a run's kind by
``serve_trace.kind_of``), averaged over the chips. A program whose
scheduler opens no such span (the parent of the PR that adds them) gives
nothing to read, and every reader returns None; so does a run off the TPU.
"""
from __future__ import annotations

import heapq
import os
import statistics

from benchmark.lib import report, scopes, serve_trace, trace

PREFIX = "pt.serve."
IN_STEP = "in_step"
UNSPANNED = "unspanned"
PHASES = ("admit", "blocks", "arrays", "dispatch", "fetch", "tokens",
          "retire")
# what each reader sums: a phase's span names
GROUPS = {"arrays": ("arrays",), "dispatch": ("dispatch",),
          "fetch": ("fetch",), "books": ("admit", "blocks", "tokens",
                                         "retire")}


def innermost(spans: list) -> list:
    """``[(start, end, name)]``: the stretches where some span is open,
    cut where any span opens or closes, each named by the innermost span
    open over it (the one opened last)."""
    ivs = sorted((s, e, n) for n, s, e in spans if e > s)
    points = sorted({p for s, e, _ in ivs for p in (s, e)})
    out, heap, i = [], [], 0
    for a, b in zip(points, points[1:]):
        while i < len(ivs) and ivs[i][0] <= a:
            heapq.heappush(heap, (-ivs[i][0], ivs[i][1], ivs[i][2]))
            i += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)  # closed: dropped once it is on top
        if not heap:
            continue
        name = heap[0][2]
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def label(pieces: list, segments: list, default) -> list:
    """``pieces`` (sorted, disjoint ``(start, end)``) cut by ``segments``
    (sorted, disjoint ``(start, end, name)``): each part named by the
    segment over it, or ``default`` where none is."""
    out, j = [], 0
    for a, b in pieces:
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        t, k = a, j
        while k < len(segments) and segments[k][0] < b:
            s, e = max(segments[k][0], a), min(segments[k][1], b)
            if s > t:
                out.append((t, s, default))
            out.append((s, e, segments[k][2]))
            t, k = e, k + 1
        if b > t:
            out.append((t, b, default))
    return out


def book(idle: list, runs: list, spans: list) -> dict:
    """Idle ns by name: ``in_step`` inside ``runs`` (merged), else the
    phase of the innermost span open over it, else ``unspanned``."""
    parts = label(idle, [(s, e, IN_STEP) for s, e in runs], None)
    host = [(a, b) for a, b, name in parts if name is None]
    out = {IN_STEP: 0.0}
    for a, b, name in [p for p in parts if p[2]] + label(
            host, innermost(spans), None):
        if name != IN_STEP:
            phase = name and name[len(PREFIX):]
            name = phase if phase in PHASES else UNSPANNED
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def reduce(planes: dict):
    """``{"decode_runs", "chips", "idle_ms", "idle_ms_total", "span_ms",
    "span_count", "modules"}``: idle ms booked by name and in all over the
    window, each span's median ms and count there, and the whole runs of
    each compiled module, per chip; None where the trace has no
    ``pt.serve.*`` span or no device ran anything."""
    host = planes.get(trace.HOST_PLANE, {})
    spans = [ev for events in host.values() for ev in events
             if ev[0].startswith(PREFIX)]
    if not spans:
        return None
    kinds = {kind: sorted((s, e) for events in host.values()
                          for n, s, e in events if n == span)
             for kind, span in serve_trace.KINDS.items()}
    idle_ns, total_ns, durations, by_module = {}, 0.0, {}, {}
    decode_runs = chips = 0
    for pname, lines in planes.items():
        ops = lines.get(trace.OPS_LINE)
        if not pname.startswith(trace.DEVICE_PLANE) or not ops:
            continue
        modules = lines.get(trace.MODULES_LINE, [])
        whole = modules[1:-1] if len(modules) >= 4 else modules
        if whole:
            lo, hi = whole[0][1], max(e for _, _, e in whole)
        else:
            lo, hi = ops[0][1], max(e for _, _, e in ops)
        chips += 1
        for n, s, e in whole:
            decode_runs += serve_trace.kind_of(n, (s + e) / 2.0,
                                               kinds) == "decode"
            by_module[n] = by_module.get(n, 0) + 1
        busy = trace.union(((s, e) for _, s, e in ops), lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        total_ns += sum(b - a for a, b in idle)
        runs = trace.union(((s, e) for _, s, e in modules), lo, hi)
        for name, ns in book(idle, runs, spans).items():
            idle_ns[name] = idle_ns.get(name, 0.0) + ns
        for name, s, e in spans:
            if s >= lo and e <= hi:
                durations.setdefault(name, []).append((e - s) / 1e6)
    if not chips:
        return None
    return {
        "decode_runs": decode_runs / chips, "chips": chips,
        "idle_ms": {n: ns / 1e6 / chips for n, ns in idle_ns.items()},
        "idle_ms_total": total_ns / 1e6 / chips,
        "span_ms": {n: statistics.median(v) for n, v in durations.items()},
        "span_count": {n: len(v) // chips for n, v in durations.items()},
        "modules": {n: c // chips for n, c in by_module.items()},
    }


_reduced = {}  # path of the raw trace -> its reduction


def of_run(run: dict):
    """The reduction of the trace this run has just written, printed once
    as a note. None off the TPU, where the run made no trace, and where
    the trace holds no span of the scheduler's."""
    if not run.get("trace") or run["device"]["platform"] != "tpu":
        return None
    path = scopes.newest_raw_trace()
    if path is None:
        return None
    if path not in _reduced:
        got = _reduced[path] = reduce(trace.load(path))
        if got:
            runs = max(got["decode_runs"], 1)
            report.note(
                "serve_spans", file=os.path.relpath(path, scopes.ROOT),
                decode_runs=got["decode_runs"],
                idle_ms_a_decode_run=got["idle_ms_total"] / runs,
                spans={n[len(PREFIX):]: {
                    "median_ms": v, "count": got["span_count"][n],
                    "idle_ms_a_decode_run": got["idle_ms"].get(
                        n[len(PREFIX):], 0.0) / runs}
                    for n, v in sorted(got["span_ms"].items())},
                in_step_ms_a_decode_run=got["idle_ms"][IN_STEP] / runs,
                modules=got["modules"],
                unspanned_ms_a_decode_run=got["idle_ms"].get(
                    UNSPANNED, 0.0) / runs)
    return _reduced[path]


def idle_ms(run: dict, *names: str):
    """Idle ms a whole decode run booked to ``names`` (``in_step``,
    ``unspanned``, or phases); None where there is nothing to read."""
    got = of_run(run)
    if not got or not got["decode_runs"]:
        return None
    return sum(got["idle_ms"].get(n, 0.0) for n in names) \
        / got["decode_runs"]


def unspanned_pct(run: dict):
    """The share of the window's idle time booked to no phase."""
    got = of_run(run)
    if not got or not got["idle_ms_total"]:
        return None
    return 100.0 * got["idle_ms"].get(UNSPANNED, 0.0) / got["idle_ms_total"]
