"""Device time a decode step, or a prefill chunk, under the inner name
``ffn`` of a served model whose blocks hold dense feed-forwards beside an
expert layer (``text/models/longcat_flash.py``: the two dense gated
feed-forwards of a double layer, each within ``mlp``, a sibling of ``moe``).

``serve_scopes.reduce`` does the reading (an operation booked to the whole
run it lies inside, a run's kind by ``serve_trace.kind_of``); this file
hands it the name, keeps the newest raw trace's reduction, and finds the
cell the trace was written for, as ``latent_scopes`` does for ``mla`` and
``moe``. A program that names no such scope (the parent of the PR that adds
it, the cells of other families) gives nothing to read, and every reader
returns None.
"""
from __future__ import annotations

import os

from benchmark.lib import manifest, scopes, serve_scopes, trace

NAMES = ("ffn",)

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
        _reduced[path] = (serve_scopes.reduce(trace.load(path), paths,
                                              names=NAMES), cell)
    return _reduced[path]


def device_ms(run: dict, kind: str):
    """Device milliseconds a run of ``kind`` (``decode``, ``prefill``) of
    the operations whose innermost inner name is ``ffn``; None where the
    trace has none."""
    got = _of_run(run)
    if not got or not got[0]:
        return None
    return got[0].get("ffn", {}).get(kind)


def cell_and_family(run: dict):
    """(the cell's file, its configuration, its serve family) of the cell
    whose trace this run has just written; None where there is none, or
    where the family counts nothing for this scope."""
    got = _of_run(run)
    if not got:
        return None
    try:
        found = manifest.load("BENCHMARK.json", got[1])
    except SystemExit:
        return None
    family = manifest.family(found["config"]["family"] + "_serve")
    if not hasattr(family, "ffn_step_bytes"):
        return None
    return found["cell"], found["config"], family
