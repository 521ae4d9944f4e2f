#!/usr/bin/env python
"""Validate a telemetry JSONL scalar log against the documented schema.

Every line must be a JSON object of the shape

    {"ts": <float unix seconds>, "step": <int|null>, "tag": <str>,
     "scalars": {<str>: <finite number>}}

(the format ``Telemetry.to_jsonl`` and the hapi ``TelemetryLogger``
emit — see README.md "Observability"). The bench ritual
(tools/bench_ritual.sh) runs this over the TELEMETRY.jsonl each bench
run writes, so benchmark telemetry stays machine-readable by
construction.

Usage:
    python tools/check_telemetry_schema.py LOG.jsonl \
        [--require counter/engine/steps] [--min-records 1]

``--require NAME`` (repeatable) additionally demands that at least one
record carries that scalar; ``--require-prefix PREFIX`` (repeatable)
demands that at least one scalar whose name starts with PREFIX appears
in some record (e.g. ``--require-prefix counter/resilience/`` asserts a
run left a resilience trace without naming each counter). Exit 0 on
pass; exit 1 with the first violation's line number and reason on fail.

Name contracts (beyond the generic shape): ``gauge/mfu*`` ∈ [0, 100];
``gauge/compile/*`` ≥ 0; the resilience counters
(``counter/resilience/*`` — incl. the cluster-level ``job_restarts``,
``rank_failures``/``rank_failures.rank<i>``, ``collective_timeouts``,
and the silent-corruption ``sdc_detected``/``sdc_repaired``/
``sdc_repaired.rank<i>``) and the coordinated-checkpoint accounting
(``counter/ckpt/*``, ``hist/ckpt/commit_ms/*``) are ≥ 0 — a negative
restart/commit count means a producer is writing deltas where totals
belong.

Integrity contracts (``resilience.integrity``): a record carrying
``gauge/integrity/fingerprint_every`` (the interval — recorded so gates
can reason about detection latency) must carry it ≥ 1 AND carry all
three ``gauge/integrity/fingerprint.{sum,abs_sum,xor}`` scalars — an
interval without fingerprints means the engine claims fingerprinting it
never published; ``fingerprint.xor`` is a uint32 word, so ∈ [0, 2^32);
and within one record ``counter/resilience/sdc_repaired`` ≤
``sdc_detected`` (every repair is preceded by its detection).

Serving contracts (``inference.serving``): ``counter/serve/*`` are
monotone request totals ≥ 0 (this covers the KV-cache block accounting
``counter/serve/kv_blocks_{alloc,free}`` too); latency/batch/token
histograms (``hist/serve/latency_ms*``, ``hist/serve/batch_ms*``, and
the token-level ``hist/serve/{ttft_ms,tpot_ms,decode_ms,prefill_ms,
verify_ms,draft_ms}*``) carry only non-negative fields;
``hist/serve/batch_occupancy*`` fields sit in [0, 1] except count/sum;
and within one record ``gauge/serve/queue_depth`` must sit in
[0, ``gauge/serve/queue_capacity``] — a depth past the configured
capacity means the bounded admission queue is not actually bounded.

SLO/alert contracts (``profiler.slo``): ``counter/alert/*`` (burn-alert
episodes) and ``gauge/slo/*`` (burn rates) are ≥ 0, and
``gauge/slo/<obj>/alerting`` ∈ {0, 1}. Histogram accounting:
``hist/*/count`` is a non-negative integer, and within one record a
positive count requires its ``hist/*/sum`` (with ``mean`` ==
``sum/count`` when present) — the ops-plane exposition and burn-rate
math difference count/sum between snapshots, so a torn triple is a
broken consistent-cut promise.

Device-profile contracts (``profiler.device_profile`` /
``profiler.bottleneck``): every ``gauge/profile/*`` scalar is ≥ 0;
the decomposition fractions (``gauge/profile/<cat>_frac.<entry>``,
cat ∈ {compute, collective, transfer, host_gap}) are each ∈ [0, 1]
AND within one record the fractions of one entry must sum ≤ 1 (they
partition the window's wall time — a sum past 1 means the decomposition
double-counts); ``gauge/bottleneck/<entry>`` must be an id from the
CLOSED verdict vocabulary {0 compute_bound, 1 memory_bound,
2 comm_bound, 3 input_bound, 4 host_bound}. A record carrying the
structured top-level ``"profile"`` object (the capture's top-K op/line
tables) must be well-formed: ``top_ops``/``top_lines`` lists whose rows
carry a non-empty op/src, a category from the closed set, non-negative
``ms``/``ms_per_step``, and ``frac`` ∈ [0, 1].

Per-axis collective contracts (``profiler.collective_attrib`` +
the eager recorder in ``distributed.communication``): every
``gauge/collective/<axis>/{bytes,ms,count}.<entry>`` scalar is ≥ 0; the
``<axis>`` token must come from the registered-axis vocabulary — each
``+``-joined component in {dp, mp, tp, pp, sp, sharding, world}, or the
honest ``unmapped`` degrade (an invented axis name means attribution is
guessing); the field must be one of bytes/ms/count. Cross-field: within
one record the summed per-axis collective ``ms`` of a captured entry
must not exceed the same record's ``gauge/profile/device_total_ms`` —
collectives are a subset of the device's captured window (the
cumulative ``eager`` entry is exempt: it counts process totals, not a
capture window). The bottleneck verdict vocabulary extension rides the
same gauges: a ``comm_bound`` verdict (id 2 — the numeric closed set is
unchanged) whose entry carries per-axis collective gauges reports
``comm_bound:<axis>`` wherever verdicts are strings (telemetry_agg
rows, ``bench_all.py`` bottleneck columns).

HLO-lint contracts (``analysis.hlo`` via the ``PADDLE_TPU_HLO_LINT``
compile-time hook): ``counter/hlolint/findings.<rule>`` counts the
static findings per rule across every program compiled this run; the
``<rule>`` token must come from the CLOSED H1-H8 vocabulary (keep in
sync with ``paddle_tpu/analysis/hlo/hlo_rules.py``) and the count is a
monotone total ≥ 0.

Goodput-ledger contracts (``profiler.goodput``): every
``gauge/goodput/<name>`` must be ``fraction``, ``wall_s``, or
``<category>_s`` with the category from the CLOSED goodput vocabulary
(keep in sync with ``paddle_tpu/profiler/goodput.py``) — an invented
category means a producer is booking seconds the ledger cannot conserve;
all ``*_s`` values are seconds ≥ 0 and ``fraction`` ∈ [0, 1].
Cross-field: a record carrying ``gauge/goodput/wall_s`` must conserve —
the summed ``<category>_s`` equals the wall within max(1% of wall,
0.05 s), because the ledger's whole contract is that every second lands
in exactly one category. A record carrying the structured top-level
``"goodput"`` table (what ``Telemetry.to_jsonl`` attaches) must be
well-formed: ``wall_s`` ≥ 0, ``fraction`` ∈ [0, 1], ``attempt`` a
non-negative integer, ``categories`` keys ⊆ the closed vocabulary with
values ≥ 0 summing to ``wall_s`` within the same tolerance.

Token-level serving contracts (``inference.serving.decode``):
``gauge/serve/kv_occupancy``, ``gauge/serve/state_occupancy`` (the
recurrent-state slots' share in use) and
``gauge/serve/spec_accept_rate`` ∈ [0, 1] (fractions by definition);
``gauge/serve/kv_blocks_{total,used}`` and
``gauge/serve/state_slots_{total,used}`` ≥ 0; and within one
record ``kv_blocks_used`` ≤ ``kv_blocks_total`` AND ``kv_occupancy``
must equal ``used/total`` (small tolerance) — an occupancy gauge that
disagrees with the block ledger it summarizes means the pool's
accounting and its telemetry have split, which is exactly how a block
leak hides.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _gate import add_gate_args, finish  # noqa: E402

# profiler.bottleneck's closed verdict vocabulary (keep in sync)
BOTTLENECK_IDS = {0, 1, 2, 3, 4}
_PROFILE_CATEGORIES = {"compute", "collective", "transfer"}
_FRAC_CATEGORIES = _PROFILE_CATEGORIES | {"host_gap"}
# profiler.collective_attrib's registered-axis vocabulary (keep in
# sync with KNOWN_AXIS_TOKENS there): "+"-joined components of a
# multi-axis group each come from this set; "unmapped" stands alone
_COLLECTIVE_AXIS_TOKENS = {"dp", "mp", "tp", "pp", "sp", "sharding",
                           "world"}
_COLLECTIVE_FIELDS = {"bytes", "ms", "count"}
# analysis.hlo's closed rule vocabulary (keep in sync with HLO_RULES
# there): hlo-lint finding counters are keyed per rule id
_HLOLINT_RULES = {"H1", "H2", "H3", "H4", "H5", "H6", "H7", "H8"}
# profiler.goodput's closed wall-clock vocabulary (keep in sync with
# CATEGORIES there): every job second lands in exactly one of these
_GOODPUT_CATEGORIES = (
    "startup", "productive_step", "compile", "input_wait",
    "checkpoint_save", "checkpoint_restore", "rollback_recovery",
    "eval", "drain_shutdown", "restart_downtime", "unattributed",
)
_GOODPUT_SCALARS = {"fraction", "wall_s"} | {
    f"{c}_s" for c in _GOODPUT_CATEGORIES}


def _goodput_tolerance(wall):
    return max(0.01 * wall, 0.05)


def _validate_goodput_table(table, lineno):
    """Shape + conservation check of the structured ``"goodput"`` table."""
    if not isinstance(table, dict):
        return f"line {lineno}: 'goodput' must be an object"
    wall = table.get("wall_s")
    if not isinstance(wall, (int, float)) or isinstance(wall, bool) \
            or not math.isfinite(float(wall)) or float(wall) < 0:
        return (f"line {lineno}: goodput.wall_s = {wall!r} must be a "
                f"finite number >= 0")
    frac = table.get("fraction")
    if frac is not None and (not isinstance(frac, (int, float))
                             or isinstance(frac, bool)
                             or not (0 <= float(frac) <= 1)):
        return f"line {lineno}: goodput.fraction = {frac!r} outside [0, 1]"
    attempt = table.get("attempt")
    if attempt is not None and (not isinstance(attempt, int)
                                or isinstance(attempt, bool)
                                or attempt < 0):
        return (f"line {lineno}: goodput.attempt = {attempt!r} must be "
                f"an integer >= 0")
    cats = table.get("categories", {})
    if not isinstance(cats, dict):
        return f"line {lineno}: goodput.categories must be an object"
    booked = 0.0
    for cat, secs in cats.items():
        if cat not in _GOODPUT_CATEGORIES:
            return (f"line {lineno}: goodput category {cat!r} outside "
                    f"the closed vocabulary {list(_GOODPUT_CATEGORIES)}")
        if isinstance(secs, bool) or not isinstance(secs, (int, float)) \
                or not math.isfinite(float(secs)) or float(secs) < 0:
            return (f"line {lineno}: goodput.categories[{cat!r}] = "
                    f"{secs!r} must be a finite number >= 0")
        booked += float(secs)
    if abs(booked - float(wall)) > _goodput_tolerance(float(wall)):
        return (f"line {lineno}: goodput categories sum to {booked:.3f}s "
                f"but wall_s = {float(wall):.3f}s — the ledger must "
                f"conserve (every second in exactly one category)")
    return None


def _collective_axis_ok(axis):
    if axis == "unmapped":
        return True
    parts = axis.split("+")
    return bool(parts) and all(p in _COLLECTIVE_AXIS_TOKENS for p in parts)


def _validate_profile_table(profile, lineno):
    """Shape check of the structured ``"profile"`` report object."""
    if not isinstance(profile, dict):
        return f"line {lineno}: 'profile' must be an object"
    for key in ("top_ops", "top_lines"):
        rows = profile.get(key, [])
        if not isinstance(rows, list):
            return f"line {lineno}: profile.{key} must be a list"
        for i, row in enumerate(rows):
            if not isinstance(row, dict):
                return f"line {lineno}: profile.{key}[{i}] not an object"
            label = row.get("op" if key == "top_ops" else "src")
            if not isinstance(label, str) or not label:
                return (f"line {lineno}: profile.{key}[{i}] lacks a "
                        f"non-empty {'op' if key == 'top_ops' else 'src'}")
            if key == "top_ops" and row.get("category") \
                    not in _PROFILE_CATEGORIES:
                return (f"line {lineno}: profile.top_ops[{i}] category "
                        f"{row.get('category')!r} outside the closed set "
                        f"{sorted(_PROFILE_CATEGORIES)}")
            for fld in ("ms", "ms_per_step"):
                v = row.get(fld)
                if v is not None and (not isinstance(v, (int, float))
                                      or isinstance(v, bool)
                                      or not math.isfinite(float(v))
                                      or float(v) < 0):
                    return (f"line {lineno}: profile.{key}[{i}].{fld} = "
                            f"{v!r} must be a finite number >= 0")
            fr = row.get("frac")
            if fr is not None and (not isinstance(fr, (int, float))
                                   or isinstance(fr, bool)
                                   or not (0 <= float(fr) <= 1)):
                return (f"line {lineno}: profile.{key}[{i}].frac = {fr!r} "
                        f"outside [0, 1]")
    return None


def validate_record(rec, lineno):
    if not isinstance(rec, dict):
        return f"line {lineno}: record is {type(rec).__name__}, not an object"
    for key in ("ts", "step", "tag", "scalars"):
        if key not in rec:
            return f"line {lineno}: missing required key {key!r}"
    if not isinstance(rec["ts"], (int, float)) or isinstance(rec["ts"], bool):
        return f"line {lineno}: 'ts' must be a number, got {rec['ts']!r}"
    if rec["step"] is not None and (
            not isinstance(rec["step"], int) or isinstance(rec["step"], bool)):
        return f"line {lineno}: 'step' must be int or null, got {rec['step']!r}"
    if not isinstance(rec["tag"], str) or not rec["tag"]:
        return f"line {lineno}: 'tag' must be a non-empty string"
    scalars = rec["scalars"]
    if not isinstance(scalars, dict):
        return f"line {lineno}: 'scalars' must be an object"
    for name, value in scalars.items():
        if not isinstance(name, str) or not name:
            return f"line {lineno}: scalar name {name!r} is not a string"
        if isinstance(value, bool) or not isinstance(value, (int, float)):
            return (f"line {lineno}: scalar {name!r} value {value!r} "
                    f"is not a number")
        if not math.isfinite(float(value)):
            return f"line {lineno}: scalar {name!r} is not finite: {value!r}"
        # attribution-layer name contracts (profiler.xla_cost): MFU is a
        # percentage of peak — a value past 100 means the flops, the
        # step histogram, and the chip-peak registry disagree about
        # units; compile/* accounting can never be negative
        if name == "gauge/mfu" or name.startswith("gauge/mfu/"):
            if not (0 <= float(value) <= 100):
                return (f"line {lineno}: scalar {name!r} = {value!r} "
                        f"outside [0, 100] (MFU is a % of chip peak)")
        if name.startswith("gauge/compile/") and float(value) < 0:
            return (f"line {lineno}: scalar {name!r} = {value!r} "
                    f"is negative (flops/bytes accounting)")
        # SLO/alert contracts (profiler.slo): alert counters count
        # rising-edge episodes and burn-rate gauges are ratios of
        # non-negative quantities — a negative value means a producer
        # wrote deltas or garbage into the operator-facing funnel
        if (name.startswith("counter/alert/")
                or name.startswith("gauge/slo/")) and float(value) < 0:
            return (f"line {lineno}: scalar {name!r} = {value!r} "
                    f"is negative (alert episodes / burn rates are >= 0)")
        if name.startswith("gauge/slo/") and name.endswith("/alerting") \
                and float(value) not in (0.0, 1.0):
            return (f"line {lineno}: scalar {name!r} = {value!r} "
                    f"not in {{0, 1}} (alerting is a state flag)")
        # histogram accounting: count is a monotone total (and the
        # denominator of every mean/burn computation) — never negative,
        # never fractional
        if name.startswith("hist/") and name.endswith("/count"):
            if float(value) < 0:
                return (f"line {lineno}: scalar {name!r} = {value!r} "
                        f"is negative (histogram counts are monotone)")
            if float(value) != int(float(value)):
                return (f"line {lineno}: scalar {name!r} = {value!r} "
                        f"is fractional (a histogram count is a number "
                        f"of observations)")
        # cluster-resilience name contracts: restart/rank-failure
        # counters and checkpoint-commit accounting are monotone totals
        if (name.startswith("counter/resilience/")
                or name.startswith("counter/ckpt/")
                or name.startswith("hist/ckpt/commit_ms")) \
                and float(value) < 0:
            return (f"line {lineno}: scalar {name!r} = {value!r} "
                    f"is negative (resilience/ckpt totals are monotone)")
        # serving contracts: request totals and latency/batch histograms
        # can never go negative; occupancy is a fraction of the bucket
        if (name.startswith("counter/serve/")
                or name.startswith("hist/serve/latency_ms")
                or name.startswith("hist/serve/batch_ms")
                or name.startswith("hist/serve/ttft_ms")
                or name.startswith("hist/serve/tpot_ms")
                or name.startswith("hist/serve/decode_ms")
                or name.startswith("hist/serve/prefill_ms")
                or name.startswith("hist/serve/verify_ms")
                or name.startswith("hist/serve/draft_ms")
                or name.startswith("hist/serve/draft_prefill_ms")
                or name in ("gauge/serve/kv_blocks_total",
                            "gauge/serve/kv_blocks_used",
                            "gauge/serve/state_slots_total",
                            "gauge/serve/state_slots_used")) \
                and float(value) < 0:
            return (f"line {lineno}: scalar {name!r} = {value!r} "
                    f"is negative (serve totals/latencies are >= 0)")
        # token-serving fractions: occupancy of the KV pool and the
        # speculative acceptance rate are [0, 1] by definition
        if name in ("gauge/serve/kv_occupancy",
                    "gauge/serve/state_occupancy",
                    "gauge/serve/spec_accept_rate") \
                and not (0 <= float(value) <= 1):
            return (f"line {lineno}: scalar {name!r} = {value!r} "
                    f"outside [0, 1]")
        if name.startswith("hist/serve/batch_occupancy") \
                and not name.endswith(("/count", "/sum")) \
                and not (0 <= float(value) <= 1):
            return (f"line {lineno}: scalar {name!r} = {value!r} "
                    f"outside [0, 1] (occupancy = batch size / bucket)")
        # device-profile decomposition: every profile gauge is a
        # non-negative quantity, and the per-entry fractions are of the
        # window's wall time — [0, 1] by definition
        if name.startswith("gauge/profile/"):
            if float(value) < 0:
                return (f"line {lineno}: scalar {name!r} = {value!r} "
                        f"is negative (profile decomposition)")
            rest = name[len("gauge/profile/"):]
            if "_frac." in rest:
                cat = rest.split("_frac.", 1)[0]
                if cat in _FRAC_CATEGORIES and not (0 <= float(value) <= 1):
                    return (f"line {lineno}: scalar {name!r} = {value!r} "
                            f"outside [0, 1] (a fraction of window wall)")
        # per-axis collective attribution: non-negative quantities under
        # an axis token from the registered vocabulary — an invented
        # axis or field name means attribution is guessing
        if name.startswith("gauge/collective/"):
            rest = name[len("gauge/collective/"):]
            axis, sep, tail = rest.partition("/")
            field = tail.split(".", 1)[0]
            if not sep or field not in _COLLECTIVE_FIELDS:
                return (f"line {lineno}: scalar {name!r} malformed — "
                        f"expected gauge/collective/<axis>/"
                        f"{{bytes,ms,count}}.<entry>")
            if not _collective_axis_ok(axis):
                return (f"line {lineno}: scalar {name!r} axis {axis!r} "
                        f"outside the registered-axis vocabulary "
                        f"{sorted(_COLLECTIVE_AXIS_TOKENS)} "
                        f"(+-joined) / 'unmapped'")
            if float(value) < 0:
                return (f"line {lineno}: scalar {name!r} = {value!r} "
                        f"is negative (collective bytes/ms/count)")
        # hlo-lint finding counters: keyed per rule id from the CLOSED
        # H1-H8 vocabulary (an invented rule token means a producer and
        # the analyzer disagree on what exists), and counts of findings
        # are monotone totals >= 0
        if name.startswith("counter/hlolint/"):
            rest = name[len("counter/hlolint/"):]
            if not rest.startswith("findings."):
                return (f"line {lineno}: scalar {name!r} malformed — "
                        f"expected counter/hlolint/findings.<rule>")
            rule = rest[len("findings."):]
            if rule not in _HLOLINT_RULES:
                return (f"line {lineno}: scalar {name!r} rule {rule!r} "
                        f"outside the hlo-lint rule vocabulary "
                        f"{sorted(_HLOLINT_RULES)}")
            if float(value) < 0:
                return (f"line {lineno}: scalar {name!r} = {value!r} "
                        f"is negative (finding counts are monotone)")
        # goodput ledger: names come from the CLOSED wall-clock
        # vocabulary (an invented category is seconds the ledger cannot
        # conserve); seconds are >= 0 and the fraction is in [0, 1]
        if name.startswith("gauge/goodput/"):
            rest = name[len("gauge/goodput/"):]
            if rest not in _GOODPUT_SCALARS:
                return (f"line {lineno}: scalar {name!r} outside the "
                        f"goodput vocabulary — expected fraction, "
                        f"wall_s, or <category>_s with category in "
                        f"{list(_GOODPUT_CATEGORIES)}")
            if rest == "fraction":
                if not (0 <= float(value) <= 1):
                    return (f"line {lineno}: scalar {name!r} = {value!r} "
                            f"outside [0, 1] (goodput is a fraction of "
                            f"job wall-clock)")
            elif float(value) < 0:
                return (f"line {lineno}: scalar {name!r} = {value!r} "
                        f"is negative (wall-clock seconds)")
        # bottleneck verdicts come from a CLOSED vocabulary — any other
        # value means a producer invented a verdict the dashboards and
        # gates cannot name
        if name.startswith("gauge/bottleneck/") \
                and float(value) not in BOTTLENECK_IDS:
            return (f"line {lineno}: scalar {name!r} = {value!r} not a "
                    f"known verdict id {sorted(BOTTLENECK_IDS)} "
                    f"(0 compute_bound, 1 memory_bound, 2 comm_bound, "
                    f"3 input_bound, 4 host_bound)")
        # integrity contracts: the fingerprint interval is a count of
        # steps (>= 1 when fingerprinting is on — 0/off publishes no
        # gauge at all); the XOR fold is a uint32 word
        if name == "gauge/integrity/fingerprint_every" and float(value) < 1:
            return (f"line {lineno}: scalar {name!r} = {value!r} "
                    f"< 1 (the interval is only published when "
                    f"fingerprinting is enabled)")
        if name == "gauge/integrity/fingerprint.xor" \
                and not (0 <= float(value) < 2 ** 32):
            return (f"line {lineno}: scalar {name!r} = {value!r} "
                    f"outside [0, 2^32) (uint32 XOR fold)")
    # cross-field: fingerprinting enabled (interval present) must come
    # with the fingerprints themselves — detection latency can only be
    # reasoned about when both are in the record
    if "gauge/integrity/fingerprint_every" in scalars:
        for part in ("sum", "abs_sum", "xor"):
            if f"gauge/integrity/fingerprint.{part}" not in scalars:
                return (f"line {lineno}: gauge/integrity/fingerprint_every "
                        f"present but gauge/integrity/fingerprint.{part} "
                        f"missing — fingerprinting claimed but not "
                        f"published")
    # cross-field: a repair can only follow a detection
    det = scalars.get("counter/resilience/sdc_detected")
    rep = scalars.get("counter/resilience/sdc_repaired")
    if rep is not None and float(rep) > float(det or 0):
        return (f"line {lineno}: counter/resilience/sdc_repaired = {rep!r} "
                f"exceeds sdc_detected = {det!r} (every repair is "
                f"preceded by its detection)")
    # cross-field: the KV pool's occupancy gauge must agree with the
    # block ledger it summarizes — a drifting pair is how a leak hides
    used = scalars.get("gauge/serve/kv_blocks_used")
    total = scalars.get("gauge/serve/kv_blocks_total")
    occ = scalars.get("gauge/serve/kv_occupancy")
    if used is not None and total is not None:
        if float(used) > float(total):
            return (f"line {lineno}: gauge/serve/kv_blocks_used = {used!r} "
                    f"exceeds gauge/serve/kv_blocks_total = {total!r} "
                    f"(the pool is a fixed allocation)")
        if occ is not None and float(total) > 0 \
                and abs(float(occ) - float(used) / float(total)) > 1e-6:
            return (f"line {lineno}: gauge/serve/kv_occupancy = {occ!r} "
                    f"inconsistent with kv_blocks_used/total = "
                    f"{used!r}/{total!r}")
    slots_used = scalars.get("gauge/serve/state_slots_used")
    slots = scalars.get("gauge/serve/state_slots_total")
    if slots_used is not None and slots is not None \
            and float(slots_used) > float(slots):
        return (f"line {lineno}: gauge/serve/state_slots_used = "
                f"{slots_used!r} exceeds gauge/serve/state_slots_total = "
                f"{slots!r} (a sequence holds one slot)")
    # cross-field: the admission queue is BOUNDED — its observed depth
    # can never exceed the capacity the same record reports
    depth = scalars.get("gauge/serve/queue_depth")
    cap = scalars.get("gauge/serve/queue_capacity")
    if depth is not None:
        if float(depth) < 0:
            return (f"line {lineno}: gauge/serve/queue_depth = {depth!r} "
                    f"is negative")
        if cap is not None and float(depth) > float(cap):
            return (f"line {lineno}: gauge/serve/queue_depth = {depth!r} "
                    f"exceeds gauge/serve/queue_capacity = {cap!r} "
                    f"(the admission queue must be bounded)")
    # cross-field: one entry's decomposition fractions partition (a
    # subset of) the window wall — their sum cannot exceed 1
    frac_sums = {}
    for name, value in scalars.items():
        if not name.startswith("gauge/profile/"):
            continue
        rest = name[len("gauge/profile/"):]
        if "_frac." not in rest:
            continue
        cat, entry = rest.split("_frac.", 1)
        if cat in _FRAC_CATEGORIES:
            frac_sums[entry] = frac_sums.get(entry, 0.0) + float(value)
    for entry, total in frac_sums.items():
        if total > 1.0 + 1e-6:
            return (f"line {lineno}: profile fractions for entry "
                    f"{entry!r} sum to {total:.6f} > 1 — the "
                    f"decomposition double-counts the window")
    # cross-field: a captured entry's summed per-axis collective ms is a
    # SUBSET of the captured device window — it cannot exceed the same
    # record's device total. The cumulative "eager" entry is exempt
    # (process totals, not a window).
    device_total = scalars.get("gauge/profile/device_total_ms")
    if device_total is not None:
        comm_sums = {}
        for name, value in scalars.items():
            if not name.startswith("gauge/collective/"):
                continue
            rest = name[len("gauge/collective/"):]
            axis, _, tail = rest.partition("/")
            if not tail.startswith("ms."):
                continue
            entry = tail[len("ms."):]
            if entry == "eager":
                continue
            comm_sums[entry] = comm_sums.get(entry, 0.0) + float(value)
        for entry, total in comm_sums.items():
            if total > float(device_total) * (1 + 1e-6) + 1e-9:
                return (f"line {lineno}: collective ms for entry "
                        f"{entry!r} sum to {total:.6f} > captured "
                        f"device total {float(device_total):.6f} ms — "
                        f"the per-axis join double-counts the window")
    # cross-field: a record that reports the goodput wall must conserve
    # it — the categories partition the wall by construction, so a gap
    # past tolerance means a producer double-booked or dropped seconds
    goodput_wall = scalars.get("gauge/goodput/wall_s")
    if goodput_wall is not None:
        booked = sum(float(v) for name, v in scalars.items()
                     if name.startswith("gauge/goodput/")
                     and name.endswith("_s")
                     and name != "gauge/goodput/wall_s")
        if abs(booked - float(goodput_wall)) \
                > _goodput_tolerance(float(goodput_wall)):
            return (f"line {lineno}: gauge/goodput/*_s sum to "
                    f"{booked:.3f}s but wall_s = "
                    f"{float(goodput_wall):.3f}s — the ledger must "
                    f"conserve (every second in exactly one category)")
    # structured top-K table (device_profile captures attach it)
    if "profile" in rec:
        err = _validate_profile_table(rec["profile"], lineno)
        if err:
            return err
    # structured goodput ledger table (Telemetry.to_jsonl attaches it)
    if "goodput" in rec:
        err = _validate_goodput_table(rec["goodput"], lineno)
        if err:
            return err
    # cross-field: histogram count/sum/mean must agree within one record
    # — the Prometheus exposition and the SLO burn-rate math difference
    # count/sum between snapshots, so a torn triple means the histogram
    # snapshot is not the consistent cut Telemetry promises
    for name, value in scalars.items():
        if not (name.startswith("hist/") and name.endswith("/count")):
            continue
        base = name[:-len("/count")]
        cnt = float(value)
        total = scalars.get(base + "/sum")
        if cnt > 0 and total is None:
            return (f"line {lineno}: {name} = {cnt:.0f} but {base}/sum "
                    f"is missing — count without sum breaks every "
                    f"rate/mean derivation downstream")
        mean = scalars.get(base + "/mean")
        if cnt > 0 and total is not None and mean is not None:
            expect = float(total) / cnt
            if abs(float(mean) - expect) > 1e-6 * max(1.0, abs(expect)):
                return (f"line {lineno}: {base}/mean = {mean!r} "
                        f"inconsistent with sum/count = "
                        f"{float(total)!r}/{cnt:.0f}")
    return None


def validate_file(path, require=(), min_records=1, require_prefix=()):
    """Returns (n_records, error_message_or_None)."""
    missing = set(require)
    missing_prefixes = set(require_prefix)
    n = 0
    try:
        with open(path) as f:
            for lineno, line in enumerate(f, 1):
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError as e:
                    return n, f"line {lineno}: invalid JSON: {e}"
                err = validate_record(rec, lineno)
                if err:
                    return n, err
                n += 1
                missing -= set(rec["scalars"])
                if missing_prefixes:
                    missing_prefixes = {
                        p for p in missing_prefixes
                        if not any(name.startswith(p)
                                   for name in rec["scalars"])}
    except OSError as e:
        return 0, f"cannot read {path}: {e}"
    if n < min_records:
        return n, f"{path}: {n} record(s), expected at least {min_records}"
    if missing:
        return n, f"{path}: required scalar(s) never appeared: {sorted(missing)}"
    if missing_prefixes:
        return n, (f"{path}: no scalar with required prefix(es): "
                   f"{sorted(missing_prefixes)}")
    return n, None


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Validate a telemetry JSONL scalar log")
    ap.add_argument("path")
    ap.add_argument("--require", action="append", default=[],
                    help="scalar name that must appear in >=1 record")
    ap.add_argument("--require-prefix", action="append", default=[],
                    help="scalar-name prefix that must match >=1 scalar "
                         "in >=1 record (e.g. counter/resilience/)")
    ap.add_argument("--min-records", type=int, default=1)
    add_gate_args(ap)
    args = ap.parse_args(argv)
    n, err = validate_file(args.path, args.require, args.min_records,
                           require_prefix=args.require_prefix)
    payload = {"records": n, "path": args.path}
    if err:
        return finish("telemetry schema", False, err, payload=payload,
                      json_mode=args.json)
    return finish("telemetry schema", True,
                  f"{n} records, {args.path}", payload=payload,
                  json_mode=args.json)


if __name__ == "__main__":
    sys.exit(main())
