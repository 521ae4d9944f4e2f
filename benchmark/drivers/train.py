"""Traffic of kind ``train``: one compiled train step, driven as a user's
loop drives it, one new batch a step from a pool made from the seed.

Set-up builds the one object that is timed, drives it from the seed's
weights through its first steps (reading, between them, what the optimizer
holds), warms the loop up and hands the same object to the window. After
the window the program's state is freed and the family's plain reference
follows the same first steps from the same seed; ``lib.compare`` decides
``correct`` from the two sets of readings.
"""
from __future__ import annotations

import functools
import gc
import math
import os
import shutil
import time

import jax
import numpy as np

from benchmark.lib import compare, layout, report, trace
from benchmark.lib.window import StepLoop
from benchmark.reference import common

WARM_STEPS = 6     # pipelined steps before the window opens
TRACE_STEPS = 16   # the traced stretch, after the window has closed


@functools.partial(jax.jit, static_argnums=(0, 1))
def _leaf_norm(frozen_specs, leaf, x):
    return common.leaf_norms({leaf: x}, dict(frozen_specs))[leaf]


def optimizer_state(step, kind: str) -> dict:
    """``kind`` ('moment1', 'master') of every parameter the step holds."""
    return {name: state[kind] for name, state in step._opt_state.items()}


def first_steps(step, loop, family, specs, opt, seed, n, mark) -> dict:
    """Drive the timed object through its first ``n`` steps, one at a time,
    and read what the comparison needs: each loss, every leaf's norm of the
    first gradient (Adam's first moment after one step is (1 - beta1) times
    it) and every leaf's norm of the masters' change after the ``n``."""
    frozen = common.specs_key(specs)
    grad_norms = None
    for _ in range(n):
        loop.step()
        loop.drain()
        if grad_norms is None:
            mark("first_step_trace_compile_or_load")
            moments = optimizer_state(step, "moment1")
            grad_norms = {
                leaf: jax.device_get(_leaf_norm(
                    frozen, leaf, layout.from_program(
                        family.NAMES, specs, moments, leaf)))
                / (1.0 - opt["beta1"]) for leaf in specs}
            del moments
            mark("read_first_gradient")
    mark("steps_2_to_n")
    masters = optimizer_state(step, "master")
    change = {leaf: jax.device_get(common.change_norm(
        specs, leaf, layout.from_program(family.NAMES, specs, masters, leaf),
        seed)) for leaf in specs}
    mark("read_change")
    return {"losses": list(loop.losses[:n]), "grad_norms": grad_norms,
            "change_norms": change}


def traced_stretch(loop, out_dir: str):
    """Trace ``TRACE_STEPS`` steps of the loop as it runs on after the
    window, and reduce the trace."""
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    jax.profiler.start_trace(out_dir)
    try:
        loop.run_steps(TRACE_STEPS)
        loop.drain()
    finally:
        jax.profiler.stop_trace()
    path = trace.newest_xplane(out_dir)
    if path is None:
        return None
    return trace.reduce(trace.load(path))


def attention_tiers() -> dict:
    """The attention tier verdicts the program keeps beside its compile
    cache: which tier each measured shape compiled, and every time."""
    import json

    from paddle_tpu.ops import tier_policy

    path = tier_policy.cache_path()
    try:
        with open(path) as f:
            verdicts = json.load(f)
    except (OSError, TypeError, ValueError):
        return {"path": path, "verdicts": None}
    return {"path": path, "mode": tier_policy.policy_mode(), "verdicts": {
        k: {f: v.get(f) for f in ("tier", "timings_ms")}
        for k, v in verdicts.items() if isinstance(v, dict)}}


def run(job: dict) -> dict:
    cell, config, family = job["cell"], job["config"], job["family"]
    traffic, opt, seed = cell["traffic"], cell["optimizer"], job["seed"]
    ref_plan = cell["reference"]
    from jax.sharding import Mesh

    clock, marks = time.perf_counter, [("start", job["t0"])]
    mark = lambda name: marks.append((name, clock()))  # noqa: E731
    devices = jax.devices()[:cell["chips"]]
    mark("imports")
    mesh = Mesh(np.array(devices), ("dp",))
    specs = family.reference.param_specs(config)
    named = layout.to_program(family.NAMES, specs,
                              common.init_params(specs, seed))
    mark("weights_from_seed")
    step = family.build(config, cell, mesh, named)
    del named
    mark("build_model_and_engine")
    pool = family.make_batches(config, traffic, seed, traffic["pool"])
    mark("batches_from_seed")
    loop = StepLoop(lambda i: pool[i % len(pool)],
                    lambda batch: family.call(step, batch),
                    lambda loss: float(loss.numpy()))

    got = first_steps(step, loop, family, specs, opt, seed,
                      ref_plan["steps"], mark)
    loop.run_steps(WARM_STEPS)
    mark("warm_up")
    compiles_before = step._jitted.tracker.compiles
    setup_s = loop.done_at[-1] - job["t0"]
    window = loop.run_window(job["seconds"])
    compiles_in_window = step._jitted.tracker.compiles - compiles_before
    traced = traced_stretch(loop, job["trace_dir"]) if job["trace"] else None
    loop.drain()

    losses = loop.losses[window["first"]:window["last"]]
    early_losses = loop.losses[:ref_plan["steps"] + WARM_STEPS]
    # the dispatch of step i + 1 comes before the fetch of step i
    dispatch_ms = loop.dispatch_ms[window["first"] + 1:window["last"] + 1]
    done = np.asarray(loop.done_at[window["first"] - 1:window["last"]])
    step_ms = np.diff(done) * 1e3
    memory = {str(d.id): d.memory_stats() or {} for d in devices}
    peak = max((m.get("peak_bytes_in_use", 0) for m in memory.values()),
               default=0)
    report.note("setup_phases_s", **{
        name: round(t - before, 3)
        for (name, t), (_, before) in zip(marks[1:], marks)})
    report.note("window", steps=window["steps"], seconds=window["seconds"],
                compiles_in_window=compiles_in_window,
                loss_first=losses[0], loss_last=losses[-1],
                step_ms_median=float(np.median(step_ms)),
                step_ms_max=float(np.max(step_ms)),
                total_compiles=step._jitted.tracker.compiles)
    report.note("memory", stats={k: {f: m.get(f) for f in (
        "bytes_in_use", "peak_bytes_in_use", "bytes_limit")}
        for k, m in memory.items()})
    report.note("attention_tiers", **attention_tiers())

    # the program's state goes before the reference comes: a process's
    # peak never falls again, and both do not fit at once
    del step, loop
    gc.collect()
    t_ref = clock()
    want = common.three_steps(
        family.reference, config, opt, seed, pool[:ref_plan["steps"]],
        precision=ref_plan.get("precision", "float32"),
        rows_per_block=ref_plan["rows_per_block"])
    nums = compare.numbers(got, want)
    report.note("reference", seconds=round(clock() - t_ref, 3))
    labels, gaps = compare.leaf_gaps(got["change_norms"],
                                     want["change_norms"])
    report.note("first_steps", program_losses=got["losses"],
                program_losses_through_warm_up=early_losses,
                reference_losses=want["losses"], worst=nums["worst"],
                widest_change_gaps=[(labels[i], float(gaps[i]))
                                    for i in np.argsort(-gaps)[:6]],
                numbers={k: v for k, v in nums.items() if k != "worst"})

    bad = sum(not math.isfinite(x) for x in losses)
    tokens = family.tokens_per_step(traffic)
    return {
        "numbers": nums,
        "attempted": window["steps"],
        "failed": min(window["steps"], bad + compiles_in_window),
        "end_to_end": {
            "train_tokens_per_s": window["steps"] * tokens
            / window["seconds"],
            "setup_s": setup_s},
        "memory_peak_bytes": peak,
        # what the per-layer readers read
        "layers": {
            "dispatch_ms": dispatch_ms,
            "window_tokens": window["steps"] * tokens,
            "window_seconds": window["seconds"],
            "flops_per_token": family.flops_per_token(config, traffic),
            "chips": cell["chips"],
            "trace": traced,
        },
    }
