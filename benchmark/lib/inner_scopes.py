"""Device time a step under a name that lies *inside* one of the six scopes
of ``lib/scopes.py``: ``kda`` (within ``self_attn``: the short convolutions,
the decay and write gates and the chunked delta rule of a linear-attention
layer, forward and backward) and ``moe`` (within ``mlp``: router, top-k,
sort, grouped products and scatter of an expert layer; the shared expert
stays ``mlp``'s). ``scopes.scope_of`` books an event to the innermost of
its own six names, so these readers change nothing of that split: they take
the same raw trace, the same window of whole steps and the same map from an
event to its ``op_name`` path, and match their own names by the same rule
(a whole component, bare or inside JAX's transform wrappers; the innermost
wins).

A reader is handed no configuration. For the roofline shares, the cell is
recovered from the raw trace's path (``.bench_out/<cell>/trace/...``) and
loaded through ``lib/manifest.py``; the operation and byte counts are the
family's (``kda_work``, ``moe_work``), the peaks ``lib/peaks.py``'s.

A program that names no such scope (every cell's parent, and the cells of
other families) gives nothing to read, and every reader returns None.
"""
from __future__ import annotations

import os

from benchmark.lib import manifest, peaks, scopes, trace

INNER = ("kda", "moe")


def innermost(path: str, names=INNER):
    """The innermost of ``names`` in an ``op_name`` path, or None."""
    found, heads, token = None, [], ""
    for ch in path + "/":
        if ch not in "()/":
            token += ch
            continue
        if ch == "(":
            heads.append(token)
        elif token in names and all(h in scopes.TRANSFORMS for h in heads):
            found = token
        if ch == ")" and heads:
            heads.pop()
        token = ""
    return found


def reduce(planes: dict, paths: dict):
    """``{name: device ms a step}`` over the window of ``lib.trace``, for
    the names some event carries. None where no operation ran."""
    ms, steps, chips = {}, [], 0
    for pname, lines in planes.items():
        ops = lines.get(trace.OPS_LINE)
        span = trace.whole_steps_window(lines.get(trace.MODULES_LINE, []))
        if not pname.startswith(trace.DEVICE_PLANE) or not ops or not span:
            continue
        lo, hi, n, _ = span
        chips += 1
        steps.append(n)
        names = paths.get(pname, {})
        for name, s, e in ops:
            if s >= lo and e <= hi:
                inner = innermost(names.get(name, ""))
                if inner:
                    ms[inner] = ms.get(inner, 0.0) + (e - s) / 1e6
    if not chips:
        return None
    return {name: v / chips / min(steps) for name, v in ms.items()}


_reduced = {}  # path of the raw trace -> (its reduction, its cell's name)


def _of_run(run: dict):
    traced = run.get("trace")
    if not traced or run["device"]["platform"] != "tpu":
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


def device_ms(run: dict, name: str):
    """Device time a step of the events whose innermost inner name is
    ``name``; None where the trace has none."""
    got = _of_run(run)
    return got[0].get(name) if got and got[0] else None


def roofline_pct(run: dict, name: str):
    """The least time the chip could take for a step's work under ``name``
    (the larger of operations over peak FLOP/s and bytes over peak
    bytes/s, by the family's ``<name>_work`` of the shapes alone), as a
    share of the time the trace reads. None where either is missing."""
    ms = device_ms(run, name)
    if not ms:
        return None
    try:
        found = manifest.load("BENCHMARK.json", _of_run(run)[1])
    except SystemExit:
        return None
    work = getattr(manifest.family(found["config"]["family"]),
                   name + "_work", None)
    if work is None:
        return None
    need = work(found["config"], found["cell"]["traffic"])
    peak = peaks.peak(run["device"]["kind"])
    least_ms = 1e3 * max(need["flops"] / peak["flops_per_s"],
                         need["bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * least_ms / ms
