"""The harness end to end at a tiny size: a well-formed last line, the
numbers compared on standard error, and no device metric's name on a
number that no device produced."""
import json
import os
import subprocess
import sys

import pytest

from conftest import CELLS, REHEARSAL, ROOT


@pytest.mark.parametrize("family", sorted(CELLS))
@pytest.mark.parametrize("trace", [0, 1])
def test_last_line(family, trace):
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--manifest", REHEARSAL,
         "--workload", CELLS[family], "--seed", str(2**31 + 5), "--seconds",
         "0.3", "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "compared"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    # a CPU number never bears a device metric's name, and the two that
    # only a chip can give are not there at all
    assert all(name.startswith("rehearsal.") for name in line["metrics"])
    want = ({"rehearsal.dispatch_ms.train"} if trace else
            {"rehearsal.train_tokens_per_s", "rehearsal.setup_s"})
    assert set(line["metrics"]) == want
    assert all(m["value"] > 0 for m in line["metrics"].values())
    tail = proc.stderr.strip().splitlines()[-len(line["compared"]):]
    assert all(t.startswith("compared ") and " limit " in t for t in tail)
    held = {n: c for n, c in line["compared"].items()
            if c["limit"] is not None}
    assert {"loss_gap", "grad_norm_gap", "change_norm_gap"} <= set(held)
    for name, c in held.items():
        assert c["value"] <= c["limit"], name


def test_same_seed_same_inputs():
    from benchmark.lib import manifest

    for cell in CELLS.values():
        found = manifest.load(REHEARSAL, cell)
        family = manifest.family(found["config"]["family"])
        a, b, c = (family.make_batches(found["config"],
                                       found["cell"]["traffic"], s, 4)
                   for s in (5, 5, 6))
        for x, y, z in zip(a, b, c):
            assert all((x[k] == y[k]).all() for k in x)
            assert any((x[k] != z[k]).any() for k in x)
        rows = [r.tobytes() for x in a for r in x["ids"]]
        assert len(set(rows)) == len(rows)  # rows that all differ


def test_unknown_workload_prints_no_result():
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--manifest", REHEARSAL,
         "--workload", "nothing.here", "--seed", "1", "--seconds", "1"],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_a_real_cell_refuses_to_run_without_a_tpu():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr
