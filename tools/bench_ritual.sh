#!/usr/bin/env bash
# The round's model-benchmark ritual — the counterpart of the reference's
# tools/test_model_benchmark.sh CI loop:
#   1. re-measure every config (bench_all.py, real backend)
#   2. GATE: fail (exit 1, tools/_gate.py conventions) if any config
#      regressed >5% vs the last PASSING baseline
#      (BENCH_extra.prev.json) or the whole-history trajectory gate
#      trips (check_bench_trajectory.py)
#   3. on PASS only, advance the baseline to this run
# Run from the repo root on the bench rig:  bash tools/bench_ritual.sh
set -e
cd "$(dirname "$0")/.."

# BENCH_extra.prev.json is the LAST PASSING baseline: it is only advanced
# AFTER the gate passes, so re-running a failed ritual cannot ratchet a
# regression into the baseline.
python bench_all.py "$@"

# bench runs must always emit machine-readable telemetry: validate the
# scalar log bench_all.py wrote against the documented schema (README
# "Observability") before the perf gate even runs
python tools/check_telemetry_schema.py TELEMETRY.jsonl

# retrace-budget gate: a bench run whose feed shapes drift recompiles a
# jitted entry per step (the silent JAX throughput cliff). Each entry's
# compile counter must stay within budget — shape bucketing
# (io.ShapeBuckets / DevicePrefetcher) is the fix when this fires.
python tools/check_retrace_budget.py TELEMETRY.jsonl --budget 6

# attribution gate: every bench config must carry cost attribution —
# non-zero compile/flops and compile/peak_hbm_bytes from the XLA cost
# model plus a live gauge/mfu. Perf numbers without a denominator are
# how a rig quietly settles at 8% MFU; this keeps the denominator wired.
# Also the TIER gate: attention-bearing records must carry the selected
# gauge/attn/tier.* verdict and ZERO counter/attn/tier_fallbacks — a
# shape silently streaming through blockwise is a ~10x cliff that fails
# the ritual instead of hiding in a log line.
python tools/check_attribution.py TELEMETRY.jsonl

# bench-trajectory gate: the WHOLE recorded history — every BENCH_r*
# round plus the BENCH_extra prev->candidate pair — per metric vs both
# the previous and the best-ever round, so a slow multi-round bleed
# fails as loudly as a cliff. On regression the failure names the
# suspect from the attribution delta (which entry's MFU / profile
# fraction / step time moved). Lenet tolerance mirrors the model gate's
# r5 variance study (tools/profiles/r5_lenet_variance.txt).
python tools/check_bench_trajectory.py \
  --tol-override lenet_mnist_dygraph_samples_per_sec=0.25

# tpu-lint gate: the STATIC twin of the retrace-budget gate — AST
# analysis over the framework for tracer-safety hazards (R1-R8: tracer
# concretization, data-dependent control flow, retrace signatures,
# per-leaf H2D loops, host syncs, trace-time mutation, float64,
# telemetry-under-trace). Ratcheting: pre-existing findings live in the
# committed baseline and burn down; anything NEW fails the ritual.
python tools/tpu_lint.py paddle_tpu --baseline tools/tpu_lint_baseline.json

# hlo-lint gate: the COMPILED-artifact twin of tpu-lint — H1-H8 static
# analysis (MXU padding waste, dtype hazards, layout copies, host
# round-trips in device loops, collective anti-patterns, unmapped
# collectives, missed sharding, dead outputs) over every program this
# very bench run compiled (bench_all.py dumped them to HLO_SNAPSHOTS/
# with per-config mesh+amp manifests). Same ratchet: committed debt in
# tools/hlo_lint_baseline.json burns down, anything NEW fails. The
# injection self-test then proves the gate can still SEE a regression:
# a forced-f32 matmul under a bf16 policy and a forced-replicated
# mesh parameter must both be flagged by name, or the ritual fails.
python tools/hlo_lint.py HLO_SNAPSHOTS --baseline tools/hlo_lint_baseline.json
python tools/hlo_lint.py --verify-injection

# resilience gate: end-to-end recovery on a tiny CPU run — one injected
# NaN step (skip + rollback) and one delivered SIGTERM (emergency
# checkpoint → exit 77 → capped relaunch) must still reach the
# uninjected run's final step count, leave resilience/* telemetry, and
# quarantine a batch that replays non-finite in isolation.
JAX_PLATFORMS=cpu python tools/check_resilience.py

# cluster-resilience gate: the multi-process twin — a 2-rank run with a
# SIGKILLed rank (supervisor detection + elastic relaunch) and a
# bit-flipped committed checkpoint (manifest-verified fallback, one
# generation back) must reach the clean run's final step AND loss, with
# resilience/job_restarts and ckpt/manifest_fallbacks in the telemetry.
JAX_PLATFORMS=cpu python tools/check_cluster_resilience.py

# silent-corruption gate: a 2-process run with an injected in-device
# bit flip (bitflip_param@3:1 — finite, tiny, invisible to the NaN/Inf
# sweep) must be DETECTED by the cross-rank fingerprint exchange within
# one fingerprint interval, repaired from the healthy rank, and reach
# the clean run's final loss bit-identically, with
# resilience/sdc_detected and sdc_repaired in the telemetry.
JAX_PLATFORMS=cpu python tools/check_sdc.py

# serving overload gate: the deployment-side acceptance — a calibrated
# 2x-offered-load run with injected stragglers (slow_req), a deadline
# storm, a dropped result, and a mid-load SIGTERM must shed via explicit
# admission rejects + deadline expiry (bounded p99 for admitted work),
# leave ZERO requests without a terminal status, and drain + exit 77
# through the preemption relaunch path.
JAX_PLATFORMS=cpu python tools/check_serving.py

# ops-plane gate: the live-operations acceptance — during a real serving
# load, /metrics + /healthz scrapes must parse cleanly and RECONCILE
# with the accounting ledger and the flushed JSONL (every serve counter
# equal at drain), /healthz must flip 503 on the drain latch, a sampled
# request must export one submit→admit→queue→batch→terminal timeline
# under one trace id, and an injected slow_req storm must trip the SLO
# burn-rate alert (telemetry_agg --fail-on-alert finding) while the
# clean phase raises zero alerts.
JAX_PLATFORMS=cpu python tools/check_ops_server.py

# cluster-timeline gate: the cross-rank twin of the ops plane — a
# 2-process run with a rank-scoped injected stall (slow_rank@5:1:…)
# must produce a LATE-RANK finding naming the stalled rank ("rank 1
# late 750 ms into all_gather_object #5"), the per-rank trace/
# collective/clock artifacts must fuse into ONE chrome timeline with
# per-rank tracks, flow arrows, and monotonic aligned timestamps, the
# clean run must raise ZERO findings, and the static per-axis collective
# inventory (compiled dp×tp HLO → gauge/collective/<axis>/*) must pass
# the schema gate — all with zero new retraces.
JAX_PLATFORMS=cpu python tools/check_cluster_timeline.py

# goodput gate: exhaustive wall-clock attribution — on a clean
# 2-process run every job second must land in exactly one category of
# the closed goodput vocabulary (sum == wall within 1%, honest
# unattributed remainder < 5%), and a fault-injected run
# (nan@3,sigterm@6 under a relaunch budget) must book REAL
# rollback_recovery and restart_downtime seconds while the stitched
# cross-restart job view still conserves.
JAX_PLATFORMS=cpu python tools/check_goodput.py

# decode gate: the token-level twin — paged-KV greedy decode must be
# token-identical to the dense recompute-the-prefix reference (logits
# within tolerance), and a mixed prefill+decode load with injected
# stragglers plus a mid-generation SIGTERM must drain with every request
# terminal exactly once, bounded TTFT p99, zero leaked KV blocks
# (alloc == free across the whole run), and zero attention-tier
# fallbacks.
JAX_PLATFORMS=cpu python tools/check_decode.py

if [ -f BENCH_extra.prev.json ]; then
  # LeNet's step is dispatch-bound: the r5
  # variance study (tools/profiles/r5_lenet_variance.txt) measured CV 7.6%
  # within-process but ~19% worst-case deviation ACROSS processes (which
  # is what this gate compares) -> tolerance 0.25
  python tools/check_model_benchmark_result.py BENCH_extra.prev.json \
    BENCH_extra.json --tol 0.05 \
    --tol-override lenet_mnist_dygraph_samples_per_sec=0.25
  echo "model benchmark gate: PASS"
else
  echo "model benchmark gate: no previous baseline, first run recorded"
fi
cp BENCH_extra.json BENCH_extra.prev.json  # only reached on PASS (set -e)
