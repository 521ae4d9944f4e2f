"""Readings of the control and of a planted fault at a cell's own size.

    python3 benchmark/tools/control.py --workload <cell> --seeds 1 2 3

Run by hand, on the chip, when a limit of ``correct`` is set or looked at
again; the benchmark's own runs never run it. For every seed the plain
reference follows the first steps of training, and in its place:

- ``control``: the same reference with every matmul one precision down
  from what the configuration states (``float8`` under bfloat16);
- ``half_batch``: the reference with half of every batch left out and the
  mean taken over the rest.

Each prints the numbers ``lib.compare`` would hold against the limits.
(A step that returns its state unchanged reads 1 by that measure, its
first moment being 0, and needs no run.)
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--manifest", default="BENCHMARK.json")
    ap.add_argument("--control", default="float8")
    args = ap.parse_args(argv)

    from benchmark.lib import compare, manifest
    from benchmark.reference import common

    found = manifest.load(args.manifest, args.workload)
    cell, config = found["cell"], found["config"]
    family = manifest.family(config["family"])
    plan = cell["reference"]

    def follow(seed, batches, precision):
        return common.three_steps(
            family.reference, config, cell["optimizer"], seed, batches,
            precision=precision, rows_per_block=plan["rows_per_block"])

    for seed in args.seeds:
        # the batches of the benchmark's own run on this seed
        batches = family.make_batches(config, cell["traffic"], seed,
                                      cell["traffic"]["pool"])[:plan["steps"]]
        want = follow(seed, batches, plan.get("precision", "float32"))
        half = [{k: v[:len(v) // 2] for k, v in b.items()} for b in batches]
        for what, got in (("control", follow(seed, batches, args.control)),
                          ("half_batch", follow(seed, half, "float32"))):
            nums = compare.numbers(got, want)
            print(json.dumps({"seed": seed, "what": what, **nums}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
