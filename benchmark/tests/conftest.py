"""The benchmark's own CPU tests (not tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402

REHEARSAL = "benchmark/tests/rehearsal/BENCHMARK.json"
CELLS = {"gpt": "gpt2-tiny.rehearsal", "bert": "bert-tiny.rehearsal"}


@pytest.fixture
def run_cell(capfd):
    """Run one rehearsal cell in this process through ``run.main`` and
    return (result line, standard error)."""
    def run(family, seed=11, seconds=0.3, trace=0):
        from benchmark import run as entry

        code = entry.main(["--manifest", REHEARSAL, "--workload",
                           CELLS[family], "--seed", str(seed), "--seconds",
                           str(seconds), "--trace", str(trace)])
        out, err = capfd.readouterr()
        assert code == 0
        return json.loads(out.strip().splitlines()[-1]), err
    return run
