"""Device time a decode step, or a prefill chunk, under a name that lies
*inside* one of the six scopes, in a trace of a serving engine under load:
``ssm`` (within ``self_attn``: a state-space mixer's state and tail read and
written, its convolution, gates, recurrence and gated norm).

Built on what is there: ``inner_scopes.innermost(path, names=...)`` matches
a name in an ``op_name`` path by ``scopes.scope_of``'s rule, ``scopes.
op_paths`` gives the map from an event to its path, and ``serve_trace.
kind_of`` tells a decode step from a prefill chunk. The runs taken are
``serve_trace``'s whole ones: every device program's run but the first and
the last, which the trace may have cut. An operation belongs to the run it
lies inside; a kind's time is the sum over its runs' operations under the
name, over the number of those runs (a run with none counts as zero).

A reader is handed no configuration: the cell is recovered from the raw
trace's path (``.bench_out/<cell>/trace/...``), as ``inner_scopes`` does.
A program that names no such scope (the parent of the PR that adds it, the
cells of other families) gives nothing to read, and every reader returns
None.
"""
from __future__ import annotations

import bisect
import os

from benchmark.lib import inner_scopes, scopes, serve_trace, trace

INNER = ("ssm",)


def reduce(planes: dict, paths: dict, names=INNER):
    """``{name: {kind: device ms a run of that kind}}`` for the names some
    operation carries; None where no device ran anything."""
    host = planes.get(trace.HOST_PLANE, {})
    host_spans = {kind: sorted((s, e) for events in host.values()
                               for n, s, e in events if n == span)
                  for kind, span in serve_trace.KINDS.items()}
    total, runs, chips = {}, {}, 0
    for pname, lines in planes.items():
        ops = lines.get(trace.OPS_LINE)
        if not pname.startswith(trace.DEVICE_PLANE) or not ops:
            continue
        modules = lines.get(trace.MODULES_LINE, [])
        whole = modules[1:-1] if len(modules) >= 4 else modules
        chips += 1
        by_path = paths.get(pname, {})
        starts = [s for _, s, _ in ops]
        for mname, lo, hi in whole:
            kind = serve_trace.kind_of(mname, (lo + hi) / 2.0, host_spans)
            if kind is None:
                continue
            runs[kind] = runs.get(kind, 0) + 1
            for name, s, e in ops[bisect.bisect_left(starts, lo):]:
                if s >= hi:
                    break
                inner = inner_scopes.innermost(by_path.get(name, ""), names)
                if inner and e <= hi:
                    of = total.setdefault(inner, {})
                    of[kind] = of.get(kind, 0.0) + (e - s) / 1e6
    if not chips:
        return None
    return {name: {kind: ms / runs[kind] for kind, ms in of.items()}
            for name, of in total.items()}


_reduced = {}  # path of the raw trace -> (its reduction, its cell's name)


def _of_run(run: dict):
    if not run.get("trace") or run["device"]["platform"] != "tpu":
        return None
    path = scopes.newest_raw_trace()
    if path is None:
        return None
    if path not in _reduced:
        with open(path, "rb") as f:
            paths = scopes.op_paths(f.read())
        cell = os.path.relpath(path, os.path.join(
            scopes.ROOT, ".bench_out")).split(os.sep)[0]
        _reduced[path] = (reduce(trace.load(path), paths), cell)
    return _reduced[path]


def device_ms(run: dict, name: str, kind: str):
    """Device milliseconds a run of ``kind`` (``decode``, ``prefill``) of
    the operations whose innermost inner name is ``name``; None where the
    trace has none."""
    got = _of_run(run)
    if not got or not got[0]:
        return None
    return got[0].get(name, {}).get(kind)


def cell_of(run: dict):
    """The name of the cell whose trace this run has just written."""
    got = _of_run(run)
    return got[1] if got else None
