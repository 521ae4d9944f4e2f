"""Readings of the control and of a planted fault for a serving cell, at
the cell's own size and load.

    python3 benchmark/tools/serve_control.py --workload <cell> \
        --seeds 1 2 3 --seconds 16 [--faults none block_table_mixup ...]

Run by hand, on the chip, when a limit of ``correct`` is set or looked at
again; the benchmark's own runs never run it. For every seed it drives the
cell as a run does, for a short window, and then, over the same sample of
served prompts and tokens that decides ``correct``:

- the program's numbers (``lib.compare_serve``);
- ``control``: the plain reference with every matmul one precision down
  from what the configuration states (``float8`` under bfloat16), put in
  the program's place: at every position compared, the gap of the token
  that precision puts first. It does not decode.

For each name after ``--faults`` the program runs again with that fault of
``lib.faults_serve`` planted (``none``: as it is, with the control read
beside it), and its numbers are the fault's reading. One ``reading`` line
each, with the verdict ``lib.compare.verdict`` gives it under the cell's
limits; the control's is on the ``control`` note of the run it was read in.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--manifest", default="BENCHMARK.json")
    ap.add_argument("--control", default="float8")
    ap.add_argument("--faults", nargs="+", default=["none"])
    args = ap.parse_args(argv)

    from benchmark.lib import compare, manifest

    found = manifest.load(args.manifest, args.workload)
    cell = {**found["cell"], "chips": found["entry"]["chips"]}
    import paddle_tpu  # noqa: F401  (places the compile cache)

    for seed in args.seeds:
        for fault in args.faults:
            fault = None if fault == "none" else fault
            job = {"cell": cell, "config": found["config"], "seed": seed,
                   "seconds": args.seconds, "trace": False,
                   "t0": time.perf_counter(), "trace_dir": None,
                   "controls": () if fault else (args.control,),
                   "fault": fault}
            result = manifest.driver(cell["driver"]).run(job)
            ok, _ = compare.verdict(result["numbers"], cell["limits"])
            print(json.dumps({
                "reading": fault or "program", "seed": seed, "correct": ok,
                "attempted": result["attempted"],
                "failed": result["failed"], **result["numbers"]}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
