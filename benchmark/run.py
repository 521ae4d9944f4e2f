"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell, its configuration, its driver, its family and its metrics
by the names in ``BENCHMARK.json``; fails without the chips the cell asks
for; prints notes as JSON lines, the numbers compared with their limits on
standard error, and the result as the last line of standard output.
``--manifest`` names another manifest: the tiny rehearsal under
``benchmark/tests/`` runs on any backend, and its metrics carry the prefix
``rehearsal.`` so that no CPU number bears a device metric's name.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()  # process start, near enough: set-up counts from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def require_chips(chips: int, rehearsal: bool) -> float:
    """Fail without the chips the cell asks for. Returns the seconds the
    runtime took to start (the first ``jax.devices()``)."""
    import jax

    t = time.perf_counter()
    devices = jax.devices()
    runtime_start_s = time.perf_counter() - t
    if rehearsal:
        return runtime_start_s
    if devices[0].platform != "tpu" or jax.default_backend() != "tpu":
        raise SystemExit(f"benchmark: needs a TPU, found "
                         f"{devices[0].platform} ({devices[0].device_kind})")
    if len(devices) < chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chips, "
                         f"this machine has {len(devices)}")
    return runtime_start_s


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default="BENCHMARK.json")
    args = ap.parse_args(argv)

    from benchmark.lib import compare, manifest, report

    found = manifest.load(args.manifest, args.workload)
    cell, entry = found["cell"], found["entry"]
    rehearsal = bool(cell.get("rehearsal"))
    import paddle_tpu  # noqa: F401  (places the compile cache in the checkout)

    runtime_start_s = require_chips(entry["chips"], rehearsal)
    cell = {**cell, "chips": entry["chips"]}
    job = {
        "cell": cell, "config": found["config"],
        "family": manifest.family(found["config"]["family"]),
        "seed": args.seed, "seconds": args.seconds, "trace": bool(args.trace),
        # the TPU runtime's own start-up swings by 10 s from run to run on
        # one machine and no PR to the program can move it: timed apart,
        # printed, and left out of setup_s (PERF.md section 2)
        "t0": T0 + runtime_start_s,
        "trace_dir": os.path.join(ROOT, ".bench_out", args.workload, "trace"),
    }
    report.note("start", workload=args.workload, seed=args.seed,
                seconds=args.seconds, trace=args.trace,
                runtime_start_s=runtime_start_s,
                device=report.device_record())
    result = manifest.driver(cell["driver"]).run(job)

    correct, compared = compare.verdict(result["numbers"], cell["limits"])
    device = {"memory_peak_bytes": result["memory_peak_bytes"]}
    prefix = "rehearsal." if rehearsal else ""
    metrics = {}
    traced = result["layers"]["trace"]
    if args.trace:
        layers = {**result["layers"], "device": report.device_record()}
        for m in manifest.metrics_of(found["manifest"], args.workload,
                                     "per_layer"):
            value = manifest.metric_reader(m["name"])(layers)
            if value is not None:
                metrics[prefix + m["name"]] = {"value": value,
                                               "unit": m["unit"]}
        if traced:
            device["busy_s"] = traced["busy_s"]
            device["window_s"] = traced["window_s"]
    else:
        for m in manifest.metrics_of(found["manifest"], args.workload,
                                     "end_to_end"):
            metrics[prefix + m["name"]] = {
                "value": result["end_to_end"][m["name"]], "unit": m["unit"]}
    report.last_lines(
        correct, result["attempted"], result["failed"], metrics,
        report.device_record(**device), compared,
        breakdown=traced["breakdown"] if traced else None)
    return 0


if __name__ == "__main__":
    sys.exit(main())
