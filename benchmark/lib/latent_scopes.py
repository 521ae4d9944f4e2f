"""Device time a decode step, or a prefill chunk, under the two inner names
of a served latent-attention expert model: ``mla`` (within ``self_attn``:
the absorbed or expanded walk over a layer's latent pages,
``ops/attention.py::mla_paged_attention``) and ``moe`` (within ``mlp``: the
router, the top-k, the sort by expert, the grouped products, the combine
and the shared expert, ``text/models/pangu_ultra_moe.py``).

``serve_scopes.reduce`` does the reading (an operation booked to the whole
run it lies inside, a run's kind by ``serve_trace.kind_of``); this file
hands it the names, keeps the newest raw trace's reduction, and finds the
cell the trace was written for, as ``serve_scopes`` does for ``ssm``.

One thing it mends on the way: the v5e's compiler turns a
``jax.lax.ragged_dot`` into a Mosaic custom call whose ``op_name`` it
writes anew (``ragged-dot-none``), so the grouped products, most of an
expert layer's time, carry no scope at all. A served step's only grouped
products are the experts' (``incubate.moe.held_experts_part``), so an
operation whose path begins ``ragged-dot`` is booked to ``moe``. A
program that names no such scope (the parent of the PR that adds them, the
cells of other families) gives nothing to read, and every reader returns
None.
"""
from __future__ import annotations

import os

from benchmark.lib import manifest, scopes, serve_scopes, trace

NAMES = ("mla", "moe")

_reduced = {}  # path of the raw trace -> (its reduction, its cell's name)


def _mended(path: str) -> str:
    """The grouped product's own path under the scope it lost."""
    return "moe/" + path if path.startswith("ragged-dot") else path


def _of_run(run: dict):
    if not run.get("trace") or run["device"]["platform"] != "tpu":
        return None
    path = scopes.newest_raw_trace()
    if path is None:
        return None
    if path not in _reduced:
        with open(path, "rb") as f:
            paths = {plane: {name: _mended(p) for name, p in ops.items()}
                     for plane, ops in scopes.op_paths(f.read()).items()}
        cell = os.path.relpath(path, os.path.join(
            scopes.ROOT, ".bench_out")).split(os.sep)[0]
        _reduced[path] = (serve_scopes.reduce(trace.load(path), paths,
                                              names=NAMES), cell)
    return _reduced[path]


def device_ms(run: dict, name: str, kind: str):
    """Device milliseconds a run of ``kind`` (``decode``, ``prefill``) of
    the operations whose innermost inner name is ``name``; None where the
    trace has none."""
    got = _of_run(run)
    if not got or not got[0]:
        return None
    return got[0].get(name, {}).get(kind)


def cell_and_family(run: dict):
    """(the cell's file, its configuration, its serve family) of the cell
    whose trace this run has just written; None where there is none, or
    where the family counts nothing for these scopes."""
    got = _of_run(run)
    if not got:
        return None
    try:
        found = manifest.load("BENCHMARK.json", got[1])
    except SystemExit:
        return None
    family = manifest.family(found["config"]["family"] + "_serve")
    if not hasattr(family, "mla_step_work"):
        return None
    return found["cell"], found["config"], family
