"""Traffic of kind ``serve``: a model behind the program's token-serving
engine, under load from the benchmark's own generator (``lib.loadgen``).

Set-up makes the seed's weights in the type they are served in, builds the
model and the engine as the cell's file states them, lets the engine warm
every compiled entry up, and draws the whole schedule from the seed. Load
then runs for the cell's ramp, the window opens and is ``--seconds`` long;
at its close the load stops. The window's requests are those that ended
inside it (``attempted``; ``failed`` those of them in any status but OK);
what the close leaves in flight is counted apart and not waited for. Then
the engine is shut down and freed, and the family's plain reference goes
once over a sample of what the window finished (``lib.compare_serve``).

The paged attention tier is the one the cell's file names
(``paged_tier``), set through the program's own forced mode before the
engine is built: nothing is raced on the machine that runs first.
"""
from __future__ import annotations

import gc
import os
import shutil
import time

import jax
import numpy as np

from benchmark.lib import (compare, compare_serve, faults_serve, loadgen,
                           manifest, report, serve_trace, trace)
from paddle_tpu.profiler.telemetry import get_telemetry

OK = "ok"
TIER_ENV = "PADDLE_TPU_ATTN_PAGED_POLICY"
COUNTERS = ("serve/tokens_generated", "serve/decode_steps",
            "serve/prefill_chunks", "serve/kv_evictions",
            "serve/admission_rejects")
HISTS = ("serve/decode_ms", "serve/prefill_ms")


def engine_counters(engine_cfg: dict):
    """A reader of the engine's own counters and histograms, and of how
    many times each of its compiled entries has compiled."""
    tel = get_telemetry()
    entries = [f"compile_ms/serve.decode.b{b}"
               for b in engine_cfg["decode_buckets"]]
    entries.append(f"compile_ms/serve.prefill.c{engine_cfg['prefill_chunk']}")

    def read() -> dict:
        out = {name: tel.counter_value(name) for name in COUNTERS}
        for name in HISTS:
            s = tel.hist_summary(name) or {"count": 0, "sum": 0.0}
            out[name + ".count"], out[name + ".sum"] = s["count"], s["sum"]
        out["compiles"] = sum((tel.hist_summary(n) or {"count": 0})["count"]
                              for n in entries)
        return out

    return read


def engine_gauges(eng):
    def read() -> dict:
        out = {"kv_occupancy": eng.pool.occupancy()}
        queue = getattr(eng, "_queue", None)
        if queue is not None:
            out["queue_depth"] = len(queue)
        return out

    return read


class Annotated:
    """For a traced stretch: the scheduler's two device-facing rounds under
    a ``bench.*`` annotation each, put on from outside and taken off again.
    Every compiled entry of the engine is named ``jit_step`` in a trace;
    the annotation a run lies under says which kind it was."""

    NAMES = {"_decode_round": "bench.decode_round",
             "_prefill_chunk": "bench.prefill_chunk"}

    def __init__(self, eng):
        self._sched = eng._scheduler
        self._put = []

    def __enter__(self):
        from jax.profiler import TraceAnnotation

        for attr, name in self.NAMES.items():
            # a round that the program has renamed fails the traced run
            # here: its metrics must not fall silent
            inner = getattr(self._sched, attr)

            def outer(*a, _inner=inner, _name=name, **kw):
                with TraceAnnotation(_name):
                    return _inner(*a, **kw)

            setattr(self._sched, attr, outer)
            self._put.append(attr)
        return self

    def __exit__(self, *exc):
        for attr in self._put:
            delattr(self._sched, attr)  # the class's method shows again


def traced_stretch(eng, load, plan: dict, out_dir: str):
    """Trace ``plan['seconds']`` of the steady window, from
    ``plan['after_s']`` past its opening; (reduced trace, its span on the
    generator's clock)."""
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    pause = load.t_open + plan["after_s"] - load.clock()
    if pause > 0:
        time.sleep(pause)
    with Annotated(eng):
        lo = load.clock()
        jax.profiler.start_trace(out_dir)
        try:
            time.sleep(plan["seconds"])
        finally:
            hi = load.clock()
            jax.profiler.stop_trace()
    path = trace.newest_xplane(out_dir)
    if path is None:
        return None, (lo, hi)
    traced = serve_trace.whole(serve_trace.reduce(trace.load(path)))
    return traced, (lo, hi)


def request_record(sent) -> dict:
    """What the harness keeps of a request once the program is gone."""
    r = sent.req
    emitted = (np.asarray(r.outputs[0], np.int32) if r.outputs
               else np.asarray(getattr(r, "generated", ()), np.int32))
    return {"index": sent.index, "due": sent.due, "ended": sent.ended,
            "status": r.status,
            "prompt": np.asarray(r.prompt, np.int32), "emitted": emitted,
            "first": r.first_token_at, "last": r.last_token_at}


def served_work(records: list, lo: float, hi: float) -> dict:
    """What was forwarded inside [lo, hi), from the requests' stamps. A
    prompt counts where its first token fell (the last chunk of its prefill
    ends there, and gives that token); a request's later tokens, each one
    more position forwarded, are spread evenly between its first and its
    last. ``attended`` counts pairs of a forwarded position and a cached
    position it attends to, itself among them; ``live_tokens`` is the mean
    over [lo, hi) of the cached positions under decode."""
    prompt = first = later = attended = 0
    live_area = 0.0
    for r in records:
        n_p, n_e = len(r["prompt"]), len(r["emitted"])
        if r["first"] is None or n_e == 0:
            continue
        if lo <= r["first"] < hi:
            prompt += n_p
            first += 1
            attended += n_p * (n_p + 1) // 2
        if n_e < 2 or r["last"] <= r["first"]:
            continue
        gap = (r["last"] - r["first"]) / (n_e - 1)
        # token j (1 .. n_e - 1) comes at first + j * gap, from a forward
        # of one position that attends to n_p + j cached ones
        j0 = max(1, int(np.ceil((lo - r["first"]) / gap)))
        j1 = min(n_e - 1, int(np.ceil((hi - r["first"]) / gap)) - 1)
        if j1 >= j0:
            n = j1 - j0 + 1
            pairs = n * n_p + (j0 + j1) * n // 2
            later += n
            attended += pairs
            live_area += gap * pairs
    return {"prompt_tokens": prompt, "first_tokens": first,
            "later_tokens": later, "attended": attended,
            "live_tokens": live_area / (hi - lo)}


def percentile(values, q):
    return float(np.percentile(values, q)) if len(values) else None


def mean(values):
    return float(np.mean(values)) if len(values) else None


def set_up(job: dict, family, mark):
    """The engine, warm, and the run's load, ready to start."""
    cell, config, seed = job["cell"], job["config"], job["seed"]
    named = family.weights(config, seed, cell["weights_dtype"])
    jax.block_until_ready(named)
    mark("weights_from_seed")
    model = family.build_model(config, named)
    eng = family.build_engine(model, cell["engine"])
    del named
    mark("build_model_and_engine")
    eng.start(warmup=True)
    mark("engine_warm_up")
    plan = loadgen.schedule(cell["traffic"], seed, config["vocab_size"],
                            job["seconds"])
    mark("schedule_from_seed")
    tel = get_telemetry()
    load = loadgen.LoadRun(
        cell["traffic"], plan, job["seconds"],
        submit=lambda prompt, new: eng.submit(prompt, max_new_tokens=new,
                                              eos_id=None),
        observe=engine_gauges(eng), counters=engine_counters(cell["engine"]),
        edge=lambda: tel.counter_value("serve/tokens_generated"),
        before_submit=job.get("before_submit"))
    return model, eng, load


def window_readings(records: list, in_window: set, load, delta: dict,
                    max_running: int) -> dict:
    """What the window held, from the requests' stamps, the generator's
    samples and the engine's counters between the window's two ends."""
    window = [r for r in records if r["index"] in in_window]
    ok = [r for r in window if r["status"] == OK and len(r["emitted"]) > 0]
    t_open, t_close = load.at_open[0], load.at_close[0]
    seconds = t_close - t_open
    steps = max(delta["serve/decode_steps"], 1)
    work = served_work(records, t_open, t_close)
    queue = load.window_samples("queue_depth")
    lag = load.lag_ms()
    lost = sum(len(r["emitted"]) for r in window if r["status"] != OK)
    tpot = [(r["last"] - r["first"]) / (len(r["emitted"]) - 1) * 1e3
            for r in ok if len(r["emitted"]) > 1]
    half = (t_open + t_close) / 2.0
    kv = load.window_samples("kv_occupancy")
    decode_sum, prefill_sum = (delta["serve/decode_ms.sum"],
                               delta["serve/prefill_ms.sum"])
    return {
        "ok": ok, "work": work, "seconds": seconds, "window": window,
        "t_open": t_open,
        "attempted": len(window), "failed": len(window) - len(ok),
        "ttft": [(r["first"] - r["due"]) * 1e3 for r in ok],
        "tpot": tpot,
        "ended_by_half": [sum(r["ended"] < half for r in window),
                          sum(r["ended"] >= half for r in window)],
        "tokens": delta["serve/tokens_generated"] - lost,
        "statuses": {s: sum(r["status"] == s for r in window)
                     for s in sorted({r["status"] for r in window})},
        "lag_p95": percentile(lag, 95),
        "lag_max": float(lag.max()) if len(lag) else None,
        "queue_halves": [mean(queue[:len(queue) // 2]),
                         mean(queue[len(queue) // 2:])],
        "layers": {
            "window_seconds": seconds,
            "decode_steps": delta["serve/decode_steps"],
            "sched_iter_ms": seconds * 1e3 / steps,
            "sched_decode_ms": decode_sum
            / max(delta["serve/decode_ms.count"], 1),
            "sched_prefill_ms": (prefill_sum / delta["serve/prefill_ms.count"]
                                 if delta["serve/prefill_ms.count"] else None),
            "sched_host_ms": (seconds * 1e3 - decode_sum - prefill_sum)
            / steps,
            "batch_occupancy": (delta["serve/tokens_generated"]
                                - work["first_tokens"]) / steps / max_running,
            "kv_occupancy": mean(kv),
            "kv_occupancy_by_half": [mean(kv[:len(kv) // 2]),
                                     mean(kv[len(kv) // 2:])],
            "tpot_p95_ms": percentile(tpot, 95),
        },
    }


def reference_numbers(job: dict, family, ok: list) -> dict:
    """The numbers compared: the plain reference over a sample of what the
    window finished. ``job['controls']`` (``tools/serve_control.py`` alone
    sets it) names lower precisions to read in the program's place."""
    cell, config, seed = job["cell"], job["config"], job["seed"]
    plan = cell["reference"]
    pairs = compare_serve.sample(
        [(r["prompt"], r["emitted"]) for r in ok], seed, plan["requests"])
    if not pairs:
        return compare_serve.numbers(np.zeros((0, 1)),
                                     np.zeros((0, 1), bool))
    ids, served, mask = compare_serve.rows(
        pairs, plan.get("positions", config["n_positions"]))
    precisions = ("float32",) + tuple(job.get("controls", ()))
    gaps = family.reference_margins(config, seed, ids, served,
                                    precisions=precisions,
                                    rows_per_block=plan["rows_per_block"])
    for precision, g in zip(precisions[1:], gaps[1:]):
        nums = compare_serve.numbers(g, mask)
        ok, _ = compare.verdict(nums, cell["limits"])
        report.note("control", precision=precision, seed=seed, correct=ok,
                    numbers=nums)
    return compare_serve.numbers(gaps[0], mask)


def run(job: dict) -> dict:
    cell, config = job["cell"], job["config"]
    engine_cfg = cell["engine"]
    family = manifest.family(config["family"] + "_serve")
    os.environ[TIER_ENV] = cell["paged_tier"]  # before any step is traced

    clock, marks = time.perf_counter, [("start", job["t0"])]
    mark = lambda name: marks.append((name, clock()))  # noqa: E731
    devices = jax.devices()[:cell["chips"]]
    mark("imports")
    model, eng, load = set_up(job, family, mark)
    setup_s = clock() - job["t0"]
    report.note("setup_phases_s", **{
        name: round(t - before, 3)
        for (name, t), (_, before) in zip(marks[1:], marks)})
    report.note("engine", paged_tier=os.environ[TIER_ENV],
                warmup_ms={k: round(v, 1) for k, v in eng.warmup_ms.items()},
                max_seq_len=eng.max_seq_len, **engine_cfg)

    traced, traced_span = None, None
    # a fault is planted by the tests and by tools/serve_control.py alone
    with faults_serve.plant(job.get("fault"), eng, config):
        load.start()
        if job["trace"]:
            traced, traced_span = traced_stretch(eng, load, cell["trace"],
                                                 job["trace_dir"])
        load.join()
    memory = {str(d.id): d.memory_stats() or {} for d in devices}
    peak = max((m.get("peak_bytes_in_use", 0) for m in memory.values()),
               default=0)
    tiers = tier_gauges()
    records = [request_record(s) for s in load.sent]
    accounting = eng.shutdown()
    kv_accounting = eng.kv_accounting()
    in_window = {s.index for s in load.attempted()}
    left = [load.at_close[0] - s.submitted for s in load.in_flight()]
    delta = {k: load.at_close[1][k] - load.at_open[1][k]
             for k in load.at_close[1]}
    if not in_window:
        raise RuntimeError("no request ended inside the window: nothing to "
                           "time and nothing to compare")
    w = window_readings(records, in_window, load, delta,
                        engine_cfg["max_running"])
    # the program's state goes before the reference comes
    del eng, model, load
    gc.collect()

    work = w["work"]
    report.note(
        "window", seconds=w["seconds"], attempted=w["attempted"],
        ok=len(w["ok"]), failed=w["failed"],
        compiles_in_window=delta["compiles"], statuses=w["statuses"],
        tokens_generated=delta["serve/tokens_generated"],
        tokens_by_stamps=work["first_tokens"] + work["later_tokens"],
        prompt_tokens_prefilled=work["prompt_tokens"],
        decode_steps=delta["serve/decode_steps"],
        prefill_chunks=delta["serve/prefill_chunks"],
        kv_evictions=delta["serve/kv_evictions"],
        admission_rejects=delta["serve/admission_rejects"],
        ttft_ms_median=percentile(w["ttft"], 50),
        ttft_ms_p95=percentile(w["ttft"], 95),
        tpot_ms_median=percentile(w["tpot"], 50),
        tpot_ms_p95=percentile(w["tpot"], 95),
        generator_lag_ms_p95=w["lag_p95"], generator_lag_ms_max=w["lag_max"],
        queue_depth_by_half=w["queue_halves"],
        ended_by_half=w["ended_by_half"],
        kv_occupancy_by_half=w["layers"]["kv_occupancy_by_half"],
        in_flight_at_close=len(left),
        oldest_in_flight_s=max(left, default=0.0),
        submitted_in_all=len(records))
    # every request of the window, in ms from its opening: when it was
    # due, its first and last token, when it was seen ended; its lengths
    report.note("requests", columns=[
        "due", "first", "last", "ended", "prompt", "emitted"], rows=[
        [*(None if r[k] is None else round((r[k] - w["t_open"]) * 1e3)
           for k in ("due", "first", "last", "ended")),
         len(r["prompt"]), len(r["emitted"])] for r in w["window"]])
    report.note("memory", stats={k: {f: m.get(f) for f in (
        "bytes_in_use", "peak_bytes_in_use", "bytes_limit")}
        for k, m in memory.items()})
    report.note("paged_tier", forced=cell["paged_tier"], gauges=tiers)
    report.note("accounting", engine=accounting, kv=kv_accounting)

    t_ref = clock()
    nums = reference_numbers(job, family, w["ok"])
    report.note("reference", seconds=round(clock() - t_ref, 3), numbers=nums)

    layers = {
        **w["layers"], "trace": traced, "chips": cell["chips"],
        "flops": family.forward_flops(
            config, work["prompt_tokens"] + work["later_tokens"],
            work["attended"], work["first_tokens"] + work["later_tokens"]),
        "decode_step_bytes": None,
    }
    if traced_span is not None:
        live = served_work(records, *traced_span)["live_tokens"]
        layers["decode_step_bytes"] = family.decode_step_bytes(
            config, engine_cfg["kv_dtype"], live)
        report.note(
            "traced", span_s=traced_span[1] - traced_span[0],
            live_tokens=live,
            **({k: v for k, v in traced.items()
                if k not in ("breakdown", "runs")} if traced else {}),
            runs={k: {"n": len(v), "mean_ms": sum(v) / len(v),
                      "max_ms": max(v)}
                  for k, v in (traced or {}).get("runs", {}).items() if v})
    return {
        "numbers": nums, "attempted": w["attempted"],
        "failed": min(w["attempted"], w["failed"] + delta["compiles"]),
        "end_to_end": {"setup_s": setup_s,
                       "tpot_p95_ms": percentile(w["tpot"], 95),
                       "ttft_p95_ms": percentile(w["ttft"], 95),
                       "serve_tokens_per_s": w["tokens"] / w["seconds"]},
        "memory_peak_bytes": peak, "layers": layers}


def tier_gauges() -> dict:
    """The paged tier every traced step took, as the program's own gauges
    (``gauge/attn/tier.paged.*``) name it."""
    from paddle_tpu.ops import tier_policy

    ids = {v: k for k, v in tier_policy.TIER_IDS.items()}
    gauges = get_telemetry().snapshot()["gauges"]
    return {k: ids.get(int(v), v) for k, v in gauges.items()
            if k.startswith("attn/tier.paged")}
