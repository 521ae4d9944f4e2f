"""The Kimi-Linear family through the harness end to end at a tiny size
(its own manifest under ``rehearsal_kimi/``: five layers in the published
pattern, 4 of 16 experts held): a well-formed last line, ``correct`` true
on the CPU, and the planted half-batch fault caught by the cell's limits.
"""
import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT
from benchmark.lib import compare, manifest
from benchmark.reference import common

MANIFEST = "benchmark/tests/rehearsal_kimi/BENCHMARK.json"
CELL = "kimi-tiny.rehearsal"


@pytest.mark.parametrize("trace", [0, 1])
def test_last_line(trace):
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--manifest", MANIFEST,
         "--workload", CELL, "--seed", str(2**31 + 7), "--seconds", "0.3",
         "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True, timeout=1200,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    # no device metric's name on a CPU number; the four new per-layer
    # readers find no chip and say nothing
    want = ({"rehearsal.dispatch_ms.train"} if trace else
            {"rehearsal.train_tokens_per_s", "rehearsal.setup_s"})
    assert set(line["metrics"]) == want
    held = {n: c for n, c in line["compared"].items()
            if c["limit"] is not None}
    assert {"grad_norm_gap", "change_norm_gap"} <= set(held)
    for name, c in held.items():
        assert c["value"] <= c["limit"], name


def test_half_batch_is_caught_and_same_seed_same_inputs():
    found = manifest.load(MANIFEST, CELL)
    cell, config = found["cell"], found["config"]
    family = manifest.family(config["family"])
    plan = cell["reference"]
    a, b, c = (family.make_batches(config, cell["traffic"], s, 3)
               for s in (5, 5, 6))
    for x, y, z in zip(a, b, c):
        assert all((x[k] == y[k]).all() for k in x)
        assert any((x[k] != z[k]).any() for k in x)
        assert x["ids"].max() < config["vocab_size"]
    follow = lambda batches: common.three_steps(  # noqa: E731
        family.reference, config, cell["optimizer"], 5, batches,
        rows_per_block=plan["rows_per_block"])
    want = follow(a)
    half = follow([{k: v[:len(v) // 2] for k, v in x.items()} for x in a])
    correct, compared = compare.verdict(compare.numbers(half, want),
                                        cell["limits"])
    assert not correct, compared
    # and a state left unchanged reads 1 on both norm gaps
    still = {"losses": want["losses"],
             "grad_norms": {n: 0 * v for n, v in want["grad_norms"].items()},
             "change_norms": {n: 0 * v
                              for n, v in want["change_norms"].items()}}
    assert not compare.verdict(compare.numbers(still, want),
                               cell["limits"])[0]
