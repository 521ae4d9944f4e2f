"""Telemetry core — counters, gauges, and streaming histograms/timers.

The runtime-observability layer the reference builds from
platform/monitor.h (StatRegistry int64 stats) + platform/profiler.h
(RecordEvent spans feeding a tuning loop). Here one ``Telemetry`` object
unifies three primitives:

- **counters** — monotonically accumulated int64s, layered directly on the
  existing ``core.monitor.StatRegistry`` so ``stat_add``/``all_stats`` and
  telemetry snapshots always agree;
- **gauges** — last-value scalars (loss, tokens/s, live device bytes).
  A gauge accepts anything float-convertible and coerces at *snapshot*
  time, so hot paths may store a not-yet-ready ``jax.Array`` without
  forcing a device sync;
- **histograms** — streaming distributions (step latency, compile time):
  running count/sum/min/max, an EMA, and p50/p95/p99 over a bounded
  sliding window (exact percentiles over unbounded streams would hold
  every sample; a window is what production step-latency dashboards use).

One JSONL sink (``to_jsonl``) emits flat scalar records — the schema
``tools/check_telemetry_schema.py`` validates:

    {"ts": <float unix seconds>, "step": <int|null>, "tag": <str>,
     "scalars": {<str>: <finite number>}}

Scalar names are namespaced: ``counter/<name>``, ``gauge/<name>``, and
``hist/<name>/{count,sum,min,max,mean,ema,p50,p95,p99}``.

Counter families by producer: ``engine/*`` ``executor/*`` ``reader/*``
``prefetch/*`` ``compile/*`` ``checkpoint/*`` ``device/*`` and the
recovery runtime's ``resilience/{nonfinite_steps,rollbacks,
quarantined_batches,worker_respawns,restarts,watchdog_dumps,io_retries,
spills,resumes,preempt_exits}`` (README "Fault tolerance";
``tools/check_telemetry_schema.py --require-prefix counter/resilience/``
asserts a run left a recovery trace).
"""
from __future__ import annotations

import json
import math
import os
import threading
import time
from collections import deque
from typing import Dict, Optional

import numpy as np

from ..core import monitor

__all__ = ["Histogram", "Telemetry", "get_telemetry", "sample_device_memory",
           "start_periodic_flush", "stop_periodic_flush",
           "start_device_memory_sampler", "stop_device_memory_sampler"]

_HIST_WINDOW = 1024  # sliding-window size backing the percentile estimates


class Histogram:
    """Streaming scalar distribution: running aggregates + EMA + windowed
    percentiles. Thread-safe; ``observe`` is O(1)."""

    def __init__(self, window: int = _HIST_WINDOW, ema_alpha: float = 0.1):
        self._lock = threading.Lock()
        self._window: deque = deque(maxlen=window)
        self._alpha = float(ema_alpha)
        self.count = 0
        self.sum = 0.0
        self.min = math.inf
        self.max = -math.inf
        self.ema = None

    def observe(self, value: float) -> None:
        v = float(value)
        with self._lock:
            self.count += 1
            self.sum += v
            self.min = v if v < self.min else self.min
            self.max = v if v > self.max else self.max
            self.ema = v if self.ema is None else (
                self._alpha * v + (1.0 - self._alpha) * self.ema)
            self._window.append(v)

    def percentile(self, q) -> float:
        """Linear-interpolated percentile(s) over the sliding window."""
        with self._lock:
            if not self._window:
                return float("nan")
            return float(np.percentile(np.asarray(self._window), q))

    def recent_above(self, bound: float, n: int) -> tuple:
        """``(above, considered)`` over the most recent ``min(n, window)``
        samples — the SLO monitor's bad-event estimator (fraction of new
        observations past an objective's latency bound). O(n), off the
        hot path (called at the monitor tick, never per observe)."""
        with self._lock:
            win = list(self._window)[-int(n):] if n > 0 else []
        return sum(1 for v in win if v > bound), len(win)

    def summary(self) -> Dict[str, float]:
        with self._lock:
            if self.count == 0:
                # count/sum must survive even the empty snapshot: the
                # Prometheus exposition and burn-rate math difference
                # consecutive snapshots, and a missing field reads as
                # "metric disappeared", not zero
                return {"count": 0, "sum": 0.0}
            # copy aggregates under the same lock as the window: an
            # in-flight observe() on another thread must not tear
            # count/sum apart (mean would be wrong in the export)
            count, total, lo, hi, ema = (self.count, self.sum, self.min,
                                         self.max, self.ema)
            win = np.asarray(self._window)
        p50, p95, p99 = np.percentile(win, [50, 95, 99])
        return {
            "count": count, "sum": total, "min": lo, "max": hi,
            "mean": total / count, "ema": ema,
            "p50": float(p50), "p95": float(p95), "p99": float(p99),
        }


class _Timer:
    """Context manager feeding a histogram in milliseconds."""

    def __init__(self, telemetry: "Telemetry", name: str):
        self._tel = telemetry
        self._name = name
        self.elapsed_ms = None

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, *exc):
        self.elapsed_ms = (time.perf_counter() - self._t0) * 1e3
        # a failed operation's partial time is not a sample of the
        # operation's duration — recording it would desync paired
        # metrics (e.g. checkpoint/write_ms count vs writes counter)
        if exc_type is None:
            self._tel.observe(self._name, self.elapsed_ms)
        return False


def _coerce_scalar(v) -> Optional[float]:
    """Best-effort float of a gauge value (may be a deferred jax.Array)."""
    try:
        f = float(np.asarray(v).ravel()[0])
    except Exception:
        return None
    return f if math.isfinite(f) else None


class Telemetry:
    """Process-wide metric hub. All mutators are cheap and thread-safe;
    disabling via ``PADDLE_TPU_TELEMETRY=0`` turns them into no-ops."""

    def __init__(self):
        self._lock = threading.Lock()
        self._gauges: Dict[str, object] = {}
        self._hists: Dict[str, Histogram] = {}
        self._counter_names: set = set()
        self.enabled = os.environ.get("PADDLE_TPU_TELEMETRY", "1") not in (
            "0", "false", "off")

    # -- primitives ------------------------------------------------------
    def counter(self, name: str, value: int = 1) -> None:
        if not self.enabled:
            return
        self._counter_names.add(name)
        monitor.stat_add(name, int(value))

    def counter_value(self, name: str) -> int:
        return monitor.stat_get(name)

    def gauge(self, name: str, value) -> None:
        if not self.enabled:
            return
        with self._lock:
            self._gauges[name] = value

    def remove_gauges(self, match) -> int:
        """Drop every gauge whose name satisfies ``match(name)`` and
        return how many were dropped. For WINDOWED gauges (a device-
        profile capture's per-entry decomposition): a new window must
        retract the old window's values for entries it did not observe,
        or stale numbers outlive the capture that produced them and
        poison cross-field contracts."""
        with self._lock:
            stale = [n for n in self._gauges if match(n)]
            for n in stale:
                del self._gauges[n]
        return len(stale)

    def observe(self, name: str, value) -> None:
        if not self.enabled:
            return
        self.histogram(name).observe(value)

    def histogram(self, name: str) -> Histogram:
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = Histogram()
            return h

    def hist_summary(self, name: str) -> Optional[Dict[str, float]]:
        """Summary of an existing histogram, or None — never creates
        one (readers like the MFU publisher must not seed empty hists
        into every snapshot)."""
        with self._lock:
            h = self._hists.get(name)
        return h.summary() if h is not None else None

    def timer(self, name: str) -> _Timer:
        return _Timer(self, name)

    def observe_interval(self, name: str, dt_ms: float) -> bool:
        """Record an inter-call interval as a steady-state step time,
        REJECTING pauses: an interval wildly above the running EMA is
        host work between steps (eval, checkpoint, data stall), not a
        step — recording it would make p99/max measure checkpoint
        cadence. One shared filter so the engine and executor step_ms
        metrics cannot drift apart. Returns True when recorded."""
        ema = self.histogram(name).ema
        if ema is not None and dt_ms >= 50 * ema + 1e3:
            return False
        self.observe(name, dt_ms)
        return True

    # -- snapshots -------------------------------------------------------
    def snapshot(self) -> dict:
        """Structured view: {'counters': .., 'gauges': .., 'histograms': ..}.
        Counters come from the shared StatRegistry, so stats bumped via
        ``monitor.stat_add`` directly appear too."""
        counters = {k: v for k, v in monitor.all_stats().items()}
        with self._lock:
            gauges = dict(self._gauges)
            hists = dict(self._hists)
        return {
            "counters": counters,
            "gauges": {k: g for k, g in (
                (k, _coerce_scalar(v)) for k, v in gauges.items())
                if g is not None},
            "histograms": {k: h.summary() for k, h in hists.items()},
        }

    def counter_scalars(self) -> Dict[str, int]:
        """Flat counters-only view (``counter/<name>``). This is the
        cheap snapshot the per-step chrome instant events use: it never
        coerces gauges (which may hold not-yet-ready device arrays — a
        ``float()`` there would block the async pipeline mid-profile)
        and never computes histogram percentiles."""
        return {f"counter/{k}": int(v)
                for k, v in monitor.all_stats().items()}

    def scalars(self) -> Dict[str, float]:
        """Flat ``{namespaced_name: number}`` view — the JSONL payload."""
        snap = self.snapshot()
        out: Dict[str, float] = {}
        for k, v in snap["counters"].items():
            out[f"counter/{k}"] = int(v)
        for k, v in snap["gauges"].items():
            out[f"gauge/{k}"] = v
        for k, s in snap["histograms"].items():
            for field, v in s.items():
                if v is not None and math.isfinite(float(v)):
                    out[f"hist/{k}/{field}"] = float(v)
        return out

    def to_jsonl(self, path: str, step: Optional[int] = None,
                 tag: str = "telemetry", extra: Optional[dict] = None,
                 append: bool = True) -> str:
        """Append one flat scalar record (the documented schema) to
        ``path``. ``extra`` scalars merge on top of the snapshot."""
        try:
            # refresh gauge/mfu + per-entry attribution gauges from the
            # latest cost records and step histograms, so every exported
            # record carries a current MFU (lazy import: xla_cost imports
            # this module)
            from . import xla_cost

            xla_cost.publish_mfu(self)
        except Exception:
            pass  # attribution must never block a telemetry export
        profile_payload = None
        try:
            # refresh the device-profile decomposition gauges and the
            # bottleneck verdicts the same way, and pick up the last
            # capture's structured top-K table for the record
            from . import bottleneck, device_profile

            device_profile.publish(self)
            bottleneck.publish(self)
            profile_payload = device_profile.jsonl_payload()
        except Exception:
            pass
        goodput_payload = None
        try:
            # refresh the wall-clock ledger gauges (gauge/goodput/*) and
            # pick up the structured attribution table — every exported
            # record then carries a current, conserving goodput snapshot
            from . import goodput

            goodput.publish(self)
            goodput_payload = goodput.jsonl_payload()
        except Exception:
            pass
        scalars = self.scalars()
        for k, v in (extra or {}).items():
            f = _coerce_scalar(v)
            if f is not None:
                scalars[str(k)] = f
        rec = {"ts": time.time(),
               "step": int(step) if step is not None else None,
               "tag": str(tag), "scalars": scalars}
        if profile_payload:
            # the per-op/per-line top-K tables ride as a STRUCTURED
            # top-level key (they are tables, not scalars); the schema
            # gate validates their shape when present
            rec["profile"] = profile_payload
        if goodput_payload:
            # per-attempt wall-clock attribution rides the same way; the
            # aggregator stitches these tables across restarts (last
            # table per attempt wins, attempts sum)
            rec["goodput"] = goodput_payload
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "a" if append else "w") as f:
            f.write(json.dumps(rec) + "\n")
        return path

    def reset(self) -> None:
        """Drop gauges/histograms and zero the counters this object
        created (other StatRegistry stats are left alone). Also resets
        the sibling per-function compile state: the ``tracked_jit``
        retrace trackers and the XLA cost registry — without that,
        back-to-back tests/benches inherit retrace counts and stale
        attribution (lazy imports: both modules import this one)."""
        with self._lock:
            self._gauges.clear()
            self._hists.clear()
            names = list(self._counter_names)
        for n in names:
            monitor.stat_reset(n)
        try:
            from .retrace import reset_trackers

            reset_trackers()
        except Exception:
            pass
        try:
            from .xla_cost import reset as _xla_reset

            _xla_reset()
        except Exception:
            pass
        try:
            # forget the last device-profile report (and abandon any
            # in-flight capture): a record written after reset must not
            # inherit the previous config's decomposition table
            from .device_profile import reset as _devprof_reset

            _devprof_reset()
        except Exception:
            pass
        try:
            # restart the goodput wall clock: per-config bench records
            # (and back-to-back tests) each get their own denominator
            from .goodput import reset as _goodput_reset

            _goodput_reset()
        except Exception:
            pass


_telemetry: Optional[Telemetry] = None
_telemetry_lock = threading.Lock()


def _flush_on_exit() -> None:
    """Final telemetry record to the env-configured sink at interpreter
    exit. This is how ``distributed.launch`` workers leave their
    per-rank JSONL (the launcher exports PADDLE_TPU_TELEMETRY_JSONL as
    ``<log_dir>/telemetry.rank<i>.jsonl`` per rank) without every
    training script remembering a to_jsonl call; ``tools/telemetry_agg``
    merges the files afterwards. ``os._exit`` paths (watchdog) skip
    atexit — the watchdog writes its record explicitly first."""
    sink = os.environ.get("PADDLE_TPU_TELEMETRY_JSONL")
    tel = _telemetry
    if not sink or tel is None or not tel.enabled:
        return
    try:
        tel.to_jsonl(sink, tag="exit")
    except Exception:
        pass  # interpreter teardown: never raise


def get_telemetry() -> Telemetry:
    global _telemetry
    if _telemetry is None:
        with _telemetry_lock:
            if _telemetry is None:
                import atexit

                _telemetry = Telemetry()
                atexit.register(_flush_on_exit)
                _autostart_background(_telemetry)
    return _telemetry


def _autostart_background(tel: Telemetry) -> None:
    """Arm the env-gated background observability services exactly once,
    when the process-wide Telemetry comes up: the periodic JSONL flush
    (PADDLE_TPU_TELEMETRY_FLUSH_EVERY_S), the device-memory sampler
    (PADDLE_TPU_DEVICE_MEM_SAMPLE_EVERY_S), and the per-rank ops HTTP
    server (PADDLE_TPU_OPS_PORT). All no-ops when their env is unset;
    none may ever take the process down."""
    if not tel.enabled:
        return
    try:
        start_periodic_flush(telemetry=tel)
    except Exception:
        pass
    try:
        start_device_memory_sampler(telemetry=tel)
    except Exception:
        pass
    try:
        # armed here, NOT inside the ops server: objectives evaluate and
        # alert into the JSONL/agg funnel even on processes that never
        # export an HTTP port
        from . import slo

        slo.maybe_start_from_env(telemetry=tel)
    except Exception:
        pass
    try:
        from . import ops_server

        ops_server.maybe_start_from_env(telemetry=tel)
    except Exception:
        pass


# -- periodic JSONL flush -----------------------------------------------------
# The atexit flush (_flush_on_exit) only covers orderly interpreter
# teardown: a SIGKILLed / OOMed rank loses its ENTIRE telemetry record,
# silently shrinking telemetry_agg's cluster medians (the dead-rank
# detector then reports it, but the signal it did emit while alive is
# gone). The periodic flusher appends an interval record so the JSONL
# always holds a recent snapshot no matter how the process dies.

def env_float(name: str, default: float = 0.0) -> float:
    """Env var as float, ``default`` on unset/malformed — the shared
    knob parser of the ops plane (slo.py / ops_server.py import it):
    observability config must never crash the workload it watches."""
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


class _IntervalService:
    """Lifecycle of one background daemon loop (flusher, mem sampler).

    Each started thread owns its OWN stop event: a stop whose join times
    out (e.g. the body blocked on a stalled filesystem) can never be
    "revived" by a later start clearing a shared event — the old thread
    still sees its permanently-set event and exits at its next wait,
    while the new thread runs off a fresh one. Start/stop are serialized
    by a lock, so two racing starts cannot both spawn writers."""

    def __init__(self, name: str):
        self.name = name
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._stop: Optional[threading.Event] = None

    def start(self, interval_s: float, body) -> threading.Thread:
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return self._thread
            stop = threading.Event()

            def _loop():
                while not stop.wait(interval_s):
                    try:
                        body()
                    except Exception:
                        pass  # one failed tick must never kill the loop

            self._stop = stop
            self._thread = threading.Thread(target=_loop, name=self.name,
                                            daemon=True)
            self._thread.start()
            return self._thread

    def stop(self, timeout: float = 2.0) -> None:
        with self._lock:
            stop, thread = self._stop, self._thread
            self._stop = self._thread = None
        if stop is not None:
            stop.set()
        if thread is not None and thread.is_alive():
            thread.join(timeout)


_flusher = _IntervalService("TelemetryFlush")
_memsampler = _IntervalService("DeviceMemSampler")


def start_periodic_flush(interval_s: Optional[float] = None,
                         path: Optional[str] = None,
                         telemetry: Optional[Telemetry] = None,
                         tag: str = "periodic") -> Optional[threading.Thread]:
    """Append a telemetry record to ``path`` every ``interval_s`` on a
    daemon thread. Defaults come from PADDLE_TPU_TELEMETRY_FLUSH_EVERY_S
    and PADDLE_TPU_TELEMETRY_JSONL; returns None (no thread) when either
    resolves unset/<= 0. Idempotent: a live flusher is returned as-is."""
    if interval_s is None:
        interval_s = env_float("PADDLE_TPU_TELEMETRY_FLUSH_EVERY_S")
    path = path or os.environ.get("PADDLE_TPU_TELEMETRY_JSONL")
    if interval_s <= 0 or not path:
        return None
    tel = telemetry or get_telemetry()
    return _flusher.start(interval_s,
                          lambda: tel.to_jsonl(path, tag=tag))


def stop_periodic_flush(timeout: float = 2.0) -> None:
    _flusher.stop(timeout)


# The device-memory sampler: /metrics can only show live HBM
# in-use/peak if SOMETHING samples the allocator — callers historically
# had to call sample_device_memory by hand at step boundaries. The
# env-gated sampler keeps the device/* gauges fresh for scrapes with
# zero call-site changes.


def start_device_memory_sampler(interval_s: Optional[float] = None,
                                telemetry: Optional[Telemetry] = None,
                                ) -> Optional[threading.Thread]:
    """Run ``sample_device_memory`` every ``interval_s`` on a daemon
    thread (default: PADDLE_TPU_DEVICE_MEM_SAMPLE_EVERY_S; unset/<= 0 →
    no thread). Idempotent while a sampler is alive."""
    if interval_s is None:
        interval_s = env_float("PADDLE_TPU_DEVICE_MEM_SAMPLE_EVERY_S")
    if interval_s <= 0:
        return None
    tel = telemetry or get_telemetry()
    return _memsampler.start(interval_s,
                             lambda: sample_device_memory(tel))


def stop_device_memory_sampler(timeout: float = 2.0) -> None:
    _memsampler.stop(timeout)


if os.environ.get("PADDLE_TPU_TELEMETRY_JSONL"):
    # a sink is configured (e.g. this is a distributed.launch rank):
    # instantiate now so the atexit flush is registered even if the
    # process never touches telemetry before exiting — otherwise a rank
    # that crashes during setup leaves no JSONL and silently drops out
    # of the telemetry_agg cluster view
    get_telemetry()


def sample_device_memory(telemetry: Optional[Telemetry] = None) -> dict:
    """Device-memory gauges (the reference's STAT_gpu0_mem_size twin):
    ``device/live_bytes`` sums ``jax.live_arrays()``; when the backend
    reports allocator stats (TPU does), per-device gauges
    ``device/bytes_in_use.d<i>``/``device/peak_bytes_in_use.d<i>`` are
    emitted for EVERY addressable device and the legacy unsuffixed names
    carry the summed total — reading only device 0 under-reported every
    multi-chip process by a factor of the local device count."""
    import jax
    from jax._src import xla_bridge

    tel = telemetry or get_telemetry()
    out = {}
    if not xla_bridge.backends_are_initialized():
        # a sampler must never be what opens the device: in a launcher
        # parent that would take the chip from the children that need it
        return out
    try:
        out["device/live_bytes"] = float(
            sum(getattr(a, "nbytes", 0) for a in jax.live_arrays()))
    except Exception:
        pass
    totals = {"bytes_in_use": 0.0, "peak_bytes_in_use": 0.0}
    seen = {k: False for k in totals}
    try:
        for i, dev in enumerate(jax.local_devices()):
            try:
                stats = dev.memory_stats() or {}
            except Exception:
                continue  # CPU backends may not implement memory_stats
            for src in totals:
                if src in stats:
                    v = float(stats[src])
                    out[f"device/{src}.d{i}"] = v
                    totals[src] += v
                    seen[src] = True
    except Exception:
        pass
    for src, any_seen in seen.items():
        if any_seen:
            out[f"device/{src}"] = totals[src]
    for k, v in out.items():
        tel.gauge(k, v)
    return out
