"""XLA cost attribution: per-executable FLOPs/HBM accounting, MFU, roofline.

The benchmarks report samples/s with no denominator: nothing in the repo
can say how fast the hardware *allows*. This module closes that gap by
capturing XLA's own cost model for every compiled executable — hooked
where compiles already funnel (``tracked_jit`` wraps every jitted entry:
``jit.TrainStep/EvalStep``, ``fleet.ParallelTrainStep``,
``static.Executor._compile``/``_compile_multi``) — and combining it with
the measured ``*step_ms`` histograms and a per-chip peak registry into:

- ``gauge/compile/flops``, ``gauge/compile/bytes_accessed``,
  ``gauge/compile/peak_hbm_bytes`` — the most recently compiled
  executable, plus per-entry ``gauge/compile/<entry>/...`` twins;
- ``gauge/mfu`` (+ per-entry ``gauge/mfu/<entry>``) — model FLOPs
  utilization, % of the chip's peak;
- ``gauge/hbm_gbps/<entry>`` — achieved HBM bytes/s;
- ``gauge/roofline/<entry>`` — 1 when the program's arithmetic intensity
  (flops / bytes accessed) exceeds the machine balance point
  (peak flops / HBM bandwidth), i.e. compute-bound; 0 = memory-bound.

Capture modes (``PADDLE_TPU_COST_ANALYSIS``):

- ``1`` (default) — ``jitted.lower(...).cost_analysis()``: HLO-level
  flops/bytes with NO second XLA compile (~10 ms host work per fresh
  compile); peak HBM is *estimated* as argument+output bytes from the
  call's own leaves (no temp term — a lower bound, flagged
  ``estimated``).
- ``full`` — ``lowered.compile()`` → optimized ``cost_analysis()`` +
  ``memory_analysis()``: exact peak HBM (argument+output+temp−alias) at
  the price of a second XLA compile per fresh signature. ``bench_all.py``
  runs in this mode (the persistent compilation cache absorbs the cost
  on rigs that configure it).
- ``0`` — off.

Per-chip peaks come from a device-kind registry with env overrides:
``PADDLE_TPU_PEAK_FLOPS`` (absolute FLOP/s) and ``PADDLE_TPU_HBM_GBPS``
(GB/s). Defaults are bf16 systolic peaks; running fp32 matmuls halves
real attainable — override when that matters.

Steps-per-call: a windowed executable (``executor.run_steps``,
``fleet.train_step_multi``) runs N train steps per invocation while the
step histograms record per-step time, so the engines register their
window length via ``set_steps_per_call`` and MFU divides the program's
flops by it.
"""
from __future__ import annotations

import dataclasses
import logging
import os
import threading
import time
from typing import Dict, List, Optional

from .telemetry import Telemetry, get_telemetry

__all__ = [
    "CostRecord", "CostRegistry", "cost_registry", "capture",
    "record_compile", "set_steps_per_call", "chip_peaks", "publish_mfu",
    "roofline_verdict", "reset", "cost_analysis_mode",
    "hbm_capacity_bytes",
]

logger = logging.getLogger("paddle_tpu.profiler")

# device_kind substring (lowercased) -> (peak FLOP/s bf16, HBM bytes/s).
# Ordered: first match wins, so the more specific kinds come first.
_CHIP_PEAKS = (
    ("v5 lite", (197e12, 819e9)),   # v5e
    ("v5litepod", (197e12, 819e9)),
    ("v5e", (197e12, 819e9)),
    ("v5p", (459e12, 2765e9)),
    ("v6 lite", (918e12, 1640e9)),  # Trillium
    ("v6e", (918e12, 1640e9)),
    ("v4", (275e12, 1228e9)),
    ("v3", (123e12, 900e9)),
    ("v2", (45e12, 700e9)),
    # CPU test mesh: a nominal per-process peak so MFU math stays
    # exercised end-to-end off-TPU (absolute value is not meaningful —
    # override with PADDLE_TPU_PEAK_FLOPS for a calibrated host).
    ("cpu", (5e11, 50e9)),
)

# device_kind substring (lowercased) -> HBM capacity in bytes. Same
# first-match-wins ordering as _CHIP_PEAKS. The CPU entry is a nominal
# host budget so remat='auto' resolves to "fits, no remat" on test rigs
# unless a test pins PADDLE_TPU_DEVICE_HBM_BYTES down to force the
# escalation ladder.
_CHIP_HBM = (
    ("v5 lite", 16e9), ("v5litepod", 16e9), ("v5e", 16e9),
    ("v5p", 95e9),
    ("v6 lite", 32e9), ("v6e", 32e9),
    ("v4", 32e9), ("v3", 32e9), ("v2", 16e9),
    ("cpu", 64e9),
)


def _lookup(table, kind: str, what: str):
    """First row of ``table`` whose key is a substring of ``kind``. A
    device the table does not know is an error, never a default: a made-up
    peak would publish a made-up utilization."""
    for sub, row in table:
        if sub in kind:
            return row
    raise ValueError(
        f"xla_cost: no {what} on record for device_kind {kind!r} — add a "
        f"row to the table in {__name__} (with its source)")


_peaks_cache = None
_peaks_lock = threading.Lock()


def hbm_capacity_bytes() -> float:
    """Per-device HBM capacity in bytes — the budget ``ops.remat_policy``
    sizes checkpoint policies against. ``PADDLE_TPU_DEVICE_HBM_BYTES``
    overrides; else the device's own ``memory_stats()['bytes_limit']``
    when the backend reports one; else the device-kind registry."""
    try:
        ov = float(os.environ.get("PADDLE_TPU_DEVICE_HBM_BYTES") or 0)
        if ov > 0:
            return ov
    except ValueError:
        pass
    import jax

    dev = jax.local_devices()[0]
    limit = (dev.memory_stats() or {}).get("bytes_limit", 0)
    if limit and limit > 0:
        return float(limit)
    return _lookup(_CHIP_HBM, str(dev.device_kind).lower(), "HBM capacity")


def cost_analysis_mode() -> str:
    """"off" | "on" | "full" (see module docstring)."""
    v = os.environ.get("PADDLE_TPU_COST_ANALYSIS", "1").strip().lower()
    if v in ("0", "false", "off", "no"):
        return "off"
    return "full" if v == "full" else "on"


def chip_peaks() -> Dict[str, float]:
    """{"flops": peak FLOP/s, "bytes_per_s": HBM bytes/s, "kind": str}.

    Env overrides beat the registry; the registry matches the first
    device's ``device_kind`` substring. Cached after first resolution
    (env is re-read only via ``reset()``)."""
    global _peaks_cache
    if _peaks_cache is not None:
        return _peaks_cache
    with _peaks_lock:
        if _peaks_cache is not None:
            return _peaks_cache
        import jax

        kind = str(jax.devices()[0].device_kind).lower()
        flops, bps = _lookup(_CHIP_PEAKS, kind, "peak FLOP/s")
        # non-positive overrides are rejected (kept at the registry
        # default): a zero would turn every MFU division into a crash,
        # and "0 to disable" belongs to PADDLE_TPU_COST_ANALYSIS
        try:
            ov = float(os.environ.get("PADDLE_TPU_PEAK_FLOPS") or 0)
            if ov > 0:
                flops = ov
        except ValueError:
            pass
        try:
            ov = float(os.environ.get("PADDLE_TPU_HBM_GBPS") or 0)
            if ov > 0:
                bps = ov * 1e9
        except ValueError:
            pass
        _peaks_cache = {"flops": flops, "bytes_per_s": bps, "kind": kind}
    return _peaks_cache


@dataclasses.dataclass
class CostRecord:
    """One compiled executable's cost profile."""

    entry: str                  # tracked_jit entry name (compile/<entry>)
    bucket: str                 # shape-bucket key (abstract signature)
    flops: float = 0.0
    bytes_accessed: float = 0.0
    peak_hbm_bytes: float = 0.0
    argument_bytes: float = 0.0
    output_bytes: float = 0.0
    temp_bytes: float = 0.0
    alias_bytes: float = 0.0
    estimated: bool = True      # True: peak_hbm has no temp term (no compile)
    ts: float = 0.0

    def intensity(self) -> Optional[float]:
        """Arithmetic intensity, FLOP per HBM byte."""
        if self.bytes_accessed > 0:
            return self.flops / self.bytes_accessed
        return None


class CostRegistry:
    """Per-entry, per-shape-bucket cost records.

    ``latest`` keeps the most recent record per entry (the live program
    — what MFU is computed against); ``entries()`` exposes every bucket
    for offline attribution."""

    def __init__(self):
        self._lock = threading.Lock()
        self._buckets: Dict[str, Dict[str, CostRecord]] = {}
        self._latest: Dict[str, CostRecord] = {}
        self._steps_per_call: Dict[str, int] = {}
        self._last_entry: Optional[str] = None

    def add(self, rec: CostRecord) -> None:
        with self._lock:
            self._buckets.setdefault(rec.entry, {})[rec.bucket] = rec
            self._latest[rec.entry] = rec
            self._last_entry = rec.entry

    def latest(self) -> Dict[str, CostRecord]:
        with self._lock:
            return dict(self._latest)

    def entries(self) -> Dict[str, Dict[str, CostRecord]]:
        with self._lock:
            return {k: dict(v) for k, v in self._buckets.items()}

    def last_entry(self) -> Optional[str]:
        return self._last_entry

    def set_steps_per_call(self, entry: str, n: int) -> None:
        with self._lock:
            self._steps_per_call[entry] = max(int(n), 1)

    def steps_per_call(self, entry: str) -> int:
        with self._lock:
            return self._steps_per_call.get(entry, 1)

    def reset(self) -> None:
        with self._lock:
            self._buckets.clear()
            self._latest.clear()
            self._steps_per_call.clear()
            self._last_entry = None


_registry = CostRegistry()
_mfu_overflow_warned: set = set()  # entries already warned about >100% MFU


def cost_registry() -> CostRegistry:
    return _registry


def set_steps_per_call(entry: str, n: int) -> None:
    """Engines running N train steps per invocation (scan windows)
    register N so MFU divides the program's flops accordingly."""
    _registry.set_steps_per_call(entry, n)


def reset() -> None:
    """Drop all records and the cached chip peaks (tests re-read env).
    The compiled-HLO registry feeding device-profile attribution resets
    with the cost records — both describe the same compiles."""
    global _peaks_cache
    _registry.reset()
    _mfu_overflow_warned.clear()
    _lint_warned.clear()
    with _peaks_lock:
        _peaks_cache = None
    try:
        from . import hlo_attrib

        hlo_attrib.hlo_registry().reset()
    except Exception:
        pass
    try:
        # the per-axis collective attribution layer caches parses of (and
        # registers the mesh for) the same compiles — same lifetime
        from . import collective_attrib

        collective_attrib.reset()
    except Exception:
        pass


# -- capture ---------------------------------------------------------------

def _leaf_bytes(tree) -> float:
    import jax
    import numpy as np

    total = 0.0
    for leaf in jax.tree_util.tree_leaves(tree):
        nbytes = getattr(leaf, "nbytes", None)
        if nbytes is None and hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            # ShapeDtypeStruct (eval_shape output) carries no nbytes
            try:
                nbytes = int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
            except Exception:
                nbytes = None
        if nbytes is not None:
            total += float(nbytes)
    return total


def _bucket_key(args, kwargs) -> str:
    """Readable shape-bucket key from the call's array leaves, bounded
    length (a large pytree collapses to a prefix + leaf count)."""
    import jax

    leaves, _ = jax.tree_util.tree_flatten((args, kwargs))
    parts = []
    n_arrays = 0
    for leaf in leaves:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            n_arrays += 1
            if len(parts) < 6:
                shape = ",".join(str(d) for d in leaf.shape)
                parts.append(f"{leaf.dtype}[{shape}]")
    key = " ".join(parts) or "scalar"
    if n_arrays > 6:
        key += f" +{n_arrays - 6} more"
    return key


def _normalize_cost(ca) -> dict:
    """``cost_analysis`` returns a dict (Lowered) or a per-device list of
    dicts (Compiled); either way the per-device view is what MFU wants
    (per-chip flops against per-chip peak)."""
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca or {})


def record_compile(entry: str, flops: float, bytes_accessed: float = 0.0,
                   argument_bytes: float = 0.0, output_bytes: float = 0.0,
                   temp_bytes: float = 0.0, alias_bytes: float = 0.0,
                   bucket: str = "default", estimated: bool = True,
                   telemetry: Optional[Telemetry] = None) -> CostRecord:
    """Register one executable's cost profile and publish the
    ``compile/*`` gauges. Public seam: ``capture`` feeds it from live
    jits; tests and offline tools feed it numbers directly."""
    peak_hbm = max(argument_bytes + output_bytes + temp_bytes
                   - alias_bytes, 0.0)
    rec = CostRecord(entry=entry, bucket=bucket, flops=float(flops),
                     bytes_accessed=float(bytes_accessed),
                     peak_hbm_bytes=peak_hbm,
                     argument_bytes=float(argument_bytes),
                     output_bytes=float(output_bytes),
                     temp_bytes=float(temp_bytes),
                     alias_bytes=float(alias_bytes),
                     estimated=estimated, ts=time.time())
    _registry.add(rec)
    tel = telemetry or get_telemetry()
    for suffix, value in (("flops", rec.flops),
                          ("bytes_accessed", rec.bytes_accessed),
                          ("peak_hbm_bytes", rec.peak_hbm_bytes)):
        tel.gauge(f"compile/{suffix}", value)
        tel.gauge(f"compile/{entry}/{suffix}", value)
    return rec


def capture(entry: str, jitted, args, kwargs) -> Optional[CostRecord]:
    """Cost-analyze the executable a fresh ``tracked_jit`` compile just
    produced. Best-effort by contract: attribution must never break a
    training step, so every failure degrades to a debug log. Called
    AFTER the triggering call returned — ``lower`` only reads avals, so
    donated (already-deleted) argument buffers are safe."""
    if cost_analysis_mode() == "off":
        return None
    try:
        lowered = jitted.lower(*args, **kwargs)
        bucket = _bucket_key(args, kwargs)
        if cost_analysis_mode() == "full":
            compiled = lowered.compile()
            ca = _normalize_cost(compiled.cost_analysis())
            mem = compiled.memory_analysis()
            _stash_hlo(entry, compiled=compiled)
            return record_compile(
                entry, flops=ca.get("flops", 0.0),
                bytes_accessed=ca.get("bytes accessed", 0.0),
                argument_bytes=getattr(mem, "argument_size_in_bytes", 0),
                output_bytes=getattr(mem, "output_size_in_bytes", 0),
                temp_bytes=getattr(mem, "temp_size_in_bytes", 0),
                alias_bytes=getattr(mem, "alias_size_in_bytes", 0),
                bucket=bucket, estimated=False)
        ca = _normalize_cost(lowered.cost_analysis())
        out_bytes = 0.0
        try:
            # out_info carries the output avals of the lowering we already
            # have; eval_shape would re-trace the whole step function
            out_bytes = _leaf_bytes(lowered.out_info)
        except Exception:
            try:
                out_bytes = _leaf_bytes(jitted.eval_shape(*args, **kwargs))
            except Exception:
                pass
        _stash_hlo(entry, lowered=lowered)
        return record_compile(
            entry, flops=ca.get("flops", 0.0),
            bytes_accessed=ca.get("bytes accessed", 0.0),
            argument_bytes=_leaf_bytes((args, kwargs)),
            output_bytes=out_bytes, bucket=bucket, estimated=True)
    except Exception as e:
        logger.debug("xla_cost: cost analysis failed for %s: %s", entry, e)
        return None


def _stash_hlo(entry: str, compiled=None, lowered=None) -> None:
    """Feed the device-profile attribution layer the compiled HLO this
    capture already holds: optimized text in full mode (no extra work —
    the compile happened above), the in-hand Lowered otherwise
    (hlo_attrib compiles it to text only if a profile is ever taken).
    Best-effort like everything else in this module."""
    try:
        from . import hlo_attrib

        if compiled is not None:
            hlo_attrib.hlo_registry().put_text(entry, compiled.as_text())
        elif lowered is not None:
            hlo_attrib.hlo_registry().put_lowered(entry, lowered)
    except Exception as e:  # noqa: BLE001
        logger.debug("xla_cost: HLO stash failed for %s: %s", entry, e)
    _maybe_lint(entry)


# -- the optimized-HLO-text access path + opt-in compile-time lint ---------

def hlo_text_for(entry: str) -> Optional[str]:
    """THE access path to an entry's optimized HLO text — full mode
    returns the text the compile already produced; the default mode
    compiles the stored Lowered on demand (counted ``profile/
    hlo_compiles`` — the one place attribution pays a compile). Both
    ``hlo_attrib`` consumers and the hlo-lint hook/CLI go through here:
    there is exactly one asymmetry and this is where it lives."""
    from . import hlo_attrib

    return hlo_attrib.hlo_registry().text_for(entry)


def hlo_texts(entries: Optional[List[str]] = None) -> Dict[str, str]:
    """``{entry: optimized HLO text}`` over the registry (or the given
    entries) via :func:`hlo_text_for`'s contract."""
    from . import hlo_attrib

    return hlo_attrib.hlo_registry().texts(entries)


def hlo_lint_enabled() -> bool:
    """Opt-in: ``PADDLE_TPU_HLO_LINT=1`` lints every fresh compile."""
    v = os.environ.get("PADDLE_TPU_HLO_LINT", "").strip().lower()
    return v in ("1", "true", "on", "yes")


# (entry, rule) pairs already warned about — the log gets ONE line per
# program/rule, the counters keep counting every finding
_lint_warned: set = set()


def _maybe_lint(entry: str) -> None:
    """The compile-time hook: when ``PADDLE_TPU_HLO_LINT`` is set, run
    the H-rules over the program this capture just stashed, publish
    ``counter/hlolint/findings.<rule>`` per finding, and warn once per
    (entry, rule). Best-effort like every attribution hook — lint must
    never break the compile it is judging."""
    if not hlo_lint_enabled():
        return
    try:
        from ..analysis.hlo import AnalysisContext, analyze_hlo_text
        from . import collective_attrib

        text = hlo_text_for(entry)
        if not text:
            return
        bf16 = False
        try:
            from ..amp.auto_cast import amp_state

            state = amp_state()
            bf16 = bool(getattr(state, "enabled", False)) and \
                "bf16" in str(getattr(state, "dtype", "")).replace(
                    "bfloat16", "bf16")
        except Exception:  # noqa: BLE001
            pass
        ctx = AnalysisContext(entry=entry,
                              mesh_axes=collective_attrib.registered_axes(),
                              bf16_policy=bf16)
        tel = get_telemetry()
        for f in analyze_hlo_text(text, ctx):
            tel.counter(f"hlolint/findings.{f.rule}")
            if (entry, f.rule) not in _lint_warned:
                _lint_warned.add((entry, f.rule))
                logger.warning(
                    "hlo-lint: %s (%s) in compiled entry %r at HLO line "
                    "%d [%s]: %s", f.rule, f.severity, entry, f.line,
                    f.context, f.message)
    except Exception as e:  # noqa: BLE001
        logger.debug("xla_cost: hlo lint failed for %s: %s", entry, e)


# -- MFU / roofline --------------------------------------------------------

# entry -> the step-latency histogram that entry's OWN engine records
# (divided per-step by the producer for windowed entries). Exact names
# only: a prefix rule would hand e.g. fleet.pipeline_step (whose engine
# records no step_ms) the data-parallel engine's latency and publish a
# meaningless MFU. Entries without a producer-owned histogram get none.
_STEP_HISTS = {
    "jit.train_step": "jit/step_ms",
    "executor.train_step": "executor/step_ms",
    "executor.run_steps": "executor/step_ms",
    "fleet.train_step": "engine/step_ms",
    "fleet.train_step_multi": "engine/step_ms",
}


def step_hist_for(entry: str) -> Optional[str]:
    # serving buckets: each "serve.step.b<N>" entry owns the
    # "serve/batch_ms.b<N>" histogram its scheduler records — per-bucket
    # MFU denominators, same producer-owned-exact-name principle as the
    # engine table above (the suffix IS the producer's suffix)
    if entry.startswith("serve.step"):
        return "serve/batch_ms" + entry[len("serve.step"):]
    # token-level serving (inference.serving.decode): every compiled
    # decode/prefill/verify entry owns the wall-time histogram the
    # decode scheduler records under the same bucket suffix, so
    # decode-STEP MFU is attributed per executable (the decode bench's
    # headline column)
    # draft_prefill must match before draft (shared prefix)
    for stem, hist in (("serve.decode", "serve/decode_ms"),
                       ("serve.prefill", "serve/prefill_ms"),
                       ("serve.verify", "serve/verify_ms"),
                       ("serve.draft_prefill", "serve/draft_prefill_ms"),
                       ("serve.draft", "serve/draft_ms")):
        if entry.startswith(stem):
            return hist + entry[len(stem):]
    return _STEP_HISTS.get(entry)


def roofline_verdict(rec: CostRecord) -> Optional[str]:
    """"compute-bound" | "memory-bound" | None (no byte count)."""
    intensity = rec.intensity()
    if intensity is None:
        return None
    peaks = chip_peaks()
    if peaks["bytes_per_s"] <= 0 or peaks["flops"] <= 0:
        return None  # degenerate peaks: no balance point to compare to
    balance = peaks["flops"] / peaks["bytes_per_s"]
    return "compute-bound" if intensity >= balance else "memory-bound"


def publish_mfu(telemetry: Optional[Telemetry] = None) -> Dict[str, dict]:
    """Combine the cost records with the live ``*step_ms`` histograms
    into ``gauge/mfu`` (+ per-entry twins), achieved HBM GB/s, and the
    roofline verdict. Returns ``{entry: {mfu_pct, hbm_gbps, verdict,
    flops_per_step, step_ms_p50}}`` for programmatic callers
    (``bench_all.py`` columns). Cheap and side-effect-free beyond gauge
    stores — ``Telemetry.to_jsonl`` calls it so every exported record
    carries a fresh MFU."""
    tel = telemetry or get_telemetry()
    latest = _registry.latest()
    if not latest:
        # nothing compiled here (e.g. a launcher parent flushing its
        # telemetry): do not look up peaks, which would open a device
        # this process never used and its children need
        return {}
    peaks = chip_peaks()
    if peaks["flops"] <= 0:
        return {}  # no peak to normalize against — publish nothing
    out: Dict[str, dict] = {}
    headline_entry = _registry.last_entry()
    for entry, rec in latest.items():
        hist = step_hist_for(entry)
        if hist is None:
            continue
        summary = tel.hist_summary(hist)
        if not summary or not summary.get("count"):
            continue
        p50_ms = summary.get("p50")
        if not p50_ms or p50_ms <= 0:
            continue
        spc = _registry.steps_per_call(entry)
        flops_step = rec.flops / spc
        bytes_step = rec.bytes_accessed / spc
        step_s = p50_ms / 1e3
        mfu = 100.0 * flops_step / step_s / peaks["flops"]
        if mfu > 100.0:
            # >100% of peak means the flops, the step histogram, and the
            # peak registry disagree about units (a TFLOP/s value in
            # PADDLE_TPU_PEAK_FLOPS, a missing set_steps_per_call) — OR a
            # nominal fallback peak on a strong CPU host. Clamping keeps
            # the schema contract, but silently reporting exactly 100
            # would mask the defect: the raw value is preserved in
            # gauge/mfu_raw/<entry> (outside the [0,100]-checked
            # namespace) and warned about once per entry.
            tel.gauge(f"mfu_raw/{entry}", mfu)
            if entry not in _mfu_overflow_warned:
                _mfu_overflow_warned.add(entry)
                logger.warning(
                    "xla_cost: MFU for %r computed %.0f%% of peak — flops, "
                    "step_ms, and the peak-FLOPs registry disagree about "
                    "units (check PADDLE_TPU_PEAK_FLOPS is absolute FLOP/s "
                    "and windowed entries registered steps_per_call); "
                    "publishing clamped 100, raw in gauge/mfu_raw/%s",
                    entry, mfu, entry)
        mfu = min(max(mfu, 0.0), 100.0)  # schema: gauge/mfu* ∈ [0, 100]
        bps = bytes_step / step_s
        verdict = roofline_verdict(rec)
        tel.gauge(f"mfu/{entry}", mfu)
        tel.gauge(f"hbm_gbps/{entry}", bps / 1e9)
        if verdict is not None:
            tel.gauge(f"roofline/{entry}",
                      1.0 if verdict == "compute-bound" else 0.0)
        out[entry] = {"mfu_pct": mfu, "hbm_gbps": bps / 1e9,
                      "verdict": verdict, "flops_per_step": flops_step,
                      "step_ms_p50": p50_ms,
                      "peak_hbm_bytes": rec.peak_hbm_bytes}
    if out:
        # headline = the most recently compiled entry when it has a step
        # hist, else a deterministic fallback among those that do
        pick = headline_entry if headline_entry in out else sorted(out)[0]
        tel.gauge("mfu", out[pick]["mfu_pct"])
    return out


def headline(telemetry: Optional[Telemetry] = None) -> Optional[dict]:
    """The most recently compiled entry's attribution row, or None."""
    entry = _registry.last_entry()
    if entry is None:
        return None
    rec = _registry.latest().get(entry)
    if rec is None:
        return None
    row = {"entry": entry, "flops": rec.flops,
           "bytes_accessed": rec.bytes_accessed,
           "peak_hbm_bytes": rec.peak_hbm_bytes,
           "estimated": rec.estimated,
           "verdict": roofline_verdict(rec)}
    mfu = publish_mfu(telemetry).get(entry)
    if mfu:
        row.update({"mfu_pct": mfu["mfu_pct"], "hbm_gbps": mfu["hbm_gbps"]})
    return row
