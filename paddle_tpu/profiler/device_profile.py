"""On-demand windowed device profiling with automatic attribution.

"Where did this step's time go" as a runtime service instead of a
by-hand ritual: arm a capture (env knob, ops-server ``POST
/debug/profile``, or :func:`request_capture`), and the next N step
boundaries of whatever engine is running are traced with
``jax.profiler.start_trace``, read from the ``.xplane.pb`` the profiler
writes, joined against the compiled HLO already held by
``xla_cost``/``hlo_attrib``, and published as

- ``gauge/profile/{compute,collective,transfer,host_gap}_frac.<entry>``
  — the per-entry step-time decomposition (fractions of window wall,
  summing ≤ 1 per entry by construction),
- ``gauge/profile/device_total_ms`` / ``gauge/profile/wall_ms`` and
  ``counter/profile/captures``,
- a structured report (:func:`last_report`) carrying the per-op /
  per-source-line top-K tables and the per-scope table (device time
  under each ``jax.named_scope`` of the compiled step,
  ``hlo_attrib.SCOPES``) — merged into every ``to_jsonl`` record
  as a top-level ``"profile"`` object and into the chrome export as
  device-op slices on the host spans' clock (placed by a ``pt.*``
  span that both the trace and the span records hold); the report's
  ``idle_by_span_ms`` books the window's idle device time to the
  innermost ``pt.*`` span open on the host at each moment (``in_step``
  inside a compiled run, ``outside_program_spans`` under none), also in
  ``/debug/profile``,
- ``gauge/bottleneck/<entry>`` verdicts (via ``profiler.bottleneck``).

Step boundaries are hooked where the engines already heartbeat:
``jit.TrainStep``, ``fleet.ParallelTrainStep`` (``__call__`` and
``run_steps`` windows), ``static.Executor.run``/``run_steps``, and the
serving/decode scheduler loops. The hook is two module-global reads when
nothing is armed — zero per-step cost by construction, and capture
start/stop live entirely on the host side of the boundary, so arming a
capture can never change a program signature (zero retraces).

Env contract:

- ``PADDLE_TPU_DEVICE_PROFILE_EVERY=K`` — arm a capture automatically at
  every K-th step boundary (0/unset = off);
- ``PADDLE_TPU_DEVICE_PROFILE_STEPS=N`` — window length in trigger-entry
  steps (default 3);
- ``PADDLE_TPU_DEVICE_PROFILE_DIR`` — where raw traces land (default: a
  temp dir, deleted after parsing; set it to keep the TensorBoard
  artifact).

Exactly ONE device trace can be live per process (an XLA constraint):
overlapping capture requests — or a capture racing a
``utils.profiler.start_profiler(device_trace=True)`` window — are
refused with a warning and a counted ``profile/capture_skipped``, never
an exception mid-training.
"""
from __future__ import annotations

import logging
import os
import shutil
import tempfile
import threading
import time
from typing import Dict, Optional

from . import hlo_attrib
from .telemetry import get_telemetry

__all__ = [
    "request_capture", "step_boundary", "capture_state", "last_report",
    "configure", "reset", "publish", "jsonl_payload", "chrome_events",
    "acquire_device_trace", "release_device_trace", "device_trace_owner",
    "clock_offset_us",
]

logger = logging.getLogger("paddle_tpu.profiler")

_DEFAULT_STEPS = 3


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


# -- device-trace ownership ---------------------------------------------------
# jax.profiler supports one live trace per process. Both producers (this
# module's windowed captures and utils.profiler's profiling windows)
# acquire through here, so a second start anywhere warns-and-noops
# instead of raising out of XLA mid-training.

_owner_lock = threading.Lock()
_trace_owner: Optional[str] = None


def acquire_device_trace(owner: str) -> bool:
    global _trace_owner
    with _owner_lock:
        if _trace_owner is not None:
            return False
        _trace_owner = str(owner)
        return True


def release_device_trace(owner: str) -> bool:
    global _trace_owner
    with _owner_lock:
        if _trace_owner != owner:
            return False
        _trace_owner = None
        return True


def device_trace_owner() -> Optional[str]:
    return _trace_owner


# -- capture state machine ----------------------------------------------------

class _Capture:
    __slots__ = ("steps_total", "logdir", "cleanup", "t_begin", "t_start",
                 "trigger_entry", "trigger_seen", "entry_steps", "started")

    def __init__(self, steps_total: int, logdir: str, cleanup: bool):
        self.steps_total = max(int(steps_total), 1)
        self.logdir = logdir
        self.cleanup = cleanup
        self.t_begin = 0.0  # just before the trace was asked to start
        self.t_start = 0.0  # once it had started: the wall's origin
        self.trigger_entry: Optional[str] = None
        self.trigger_seen = 0
        self.entry_steps: Dict[str, int] = {}
        self.started = False


_lock = threading.Lock()
_armed: Optional[_Capture] = None       # waiting for the next boundary
_active: Optional[_Capture] = None      # trace live
_hot = False                            # armed or active (hot-path gate)
_last_report: Optional[dict] = None
_last_chrome: list = []
_boundary_count = 0
_every = _env_int("PADDLE_TPU_DEVICE_PROFILE_EVERY", 0)
_window_steps = _env_int("PADDLE_TPU_DEVICE_PROFILE_STEPS", _DEFAULT_STEPS)
_top_k = 10


def configure(every: Optional[int] = None,
              steps: Optional[int] = None) -> None:
    """Override the env-derived trigger cadence / window length
    (tests, notebooks). ``reset()`` re-reads the env."""
    global _every, _window_steps, _hot
    with _lock:
        if every is not None:
            _every = max(int(every), 0)
        if steps is not None:
            _window_steps = max(int(steps), 1)
        _hot = _armed is not None or _active is not None or _every > 0


def _discard(cap: Optional[_Capture]) -> None:
    """Drop a capture's disposable logdir (the mkdtemp ones — a user- or
    env-specified dir is never touched). Every path that abandons a
    capture without finishing it must route here, or armed-then-reset
    cycles leak one temp dir each."""
    if cap is not None and cap.cleanup:
        shutil.rmtree(cap.logdir, ignore_errors=True)


def capture_state() -> str:
    """"idle" | "armed" | "capturing"."""
    with _lock:
        if _active is not None:
            return "capturing"
        if _armed is not None:
            return "armed"
        return "idle"


def last_report() -> Optional[dict]:
    return _last_report


def request_capture(steps: Optional[int] = None,
                    logdir: Optional[str] = None) -> bool:
    """Arm a windowed capture starting at the next step boundary. False
    (warning + ``counter/profile/capture_skipped``) when a capture is
    already armed/active or another component owns the device trace."""
    global _armed, _hot
    n = max(int(steps or _window_steps), 1)
    tel = get_telemetry()
    with _lock:
        if _armed is not None or _active is not None:
            tel.counter("profile/capture_skipped")
            logger.warning(
                "device_profile: capture request (steps=%d) refused — a "
                "capture is already %s; one windowed trace at a time",
                n, "running" if _active is not None else "armed")
            return False
        if device_trace_owner() is not None:
            tel.counter("profile/capture_skipped")
            logger.warning(
                "device_profile: capture request refused — %r holds the "
                "device trace (a profiler window is open)",
                device_trace_owner())
            return False
        if logdir:
            cap = _Capture(n, logdir, cleanup=False)
        else:
            env_dir = os.environ.get("PADDLE_TPU_DEVICE_PROFILE_DIR")
            if env_dir:
                cap = _Capture(n, env_dir, cleanup=False)
            else:
                cap = _Capture(n, tempfile.mkdtemp(
                    prefix="paddle_tpu_devprof_"), cleanup=True)
        _armed = cap
        _hot = True
    return True


def step_boundary(entry: str) -> None:
    """Called by every engine at its step boundary (host side, before
    dispatch). Cheap when cold: one global read."""
    global _boundary_count
    if not _hot:
        return
    with _lock:
        _boundary_count += 1
        if (_active is None and _armed is None and _every > 0
                and _boundary_count % _every == 0):
            # env-cadence trigger: arm in place (inline, lock held)
            _arm_from_env_locked()
        if _armed is not None and _active is None:
            _start_locked(entry)
            return
        cap = _active
        if cap is None:
            return
        cap.entry_steps[entry] = cap.entry_steps.get(entry, 0) + 1
        if entry == cap.trigger_entry:
            cap.trigger_seen += 1
            if cap.trigger_seen >= cap.steps_total:
                _stop_locked(cap)


def _arm_from_env_locked() -> None:
    global _armed, _hot
    if device_trace_owner() is not None:
        get_telemetry().counter("profile/capture_skipped")
        return
    env_dir = os.environ.get("PADDLE_TPU_DEVICE_PROFILE_DIR")
    if env_dir:
        _armed = _Capture(_window_steps, env_dir, cleanup=False)
    else:
        _armed = _Capture(_window_steps, tempfile.mkdtemp(
            prefix="paddle_tpu_devprof_"), cleanup=True)
    _hot = True


def _drain_devices() -> None:
    """Wait for everything dispatched so far. Steps are dispatched ahead
    of the device and an operation is written to the trace when it ends:
    without the wait at its start a window holds the tail of the step
    before it, and without the wait at its end the trace is cut while the
    window's last steps still run (on XLA:CPU a tiny step's whole window:
    the capture then holds no operation at all)."""
    import jax

    try:
        jax.block_until_ready(jax.live_arrays())
    except Exception:  # noqa: BLE001 — a failed step is the training
        pass           # loop's to raise, not the profiler's


def _start_locked(entry: str) -> None:
    """Begin the armed capture at this boundary (lock held)."""
    global _armed, _active
    cap = _armed
    if cap is None:
        return
    if not acquire_device_trace("device_profile"):
        get_telemetry().counter("profile/capture_skipped")
        logger.warning("device_profile: cannot start capture — device "
                       "trace held by %r", device_trace_owner())
        _discard(cap)
        _armed = None
        _refresh_hot_locked()
        return
    try:
        import jax

        os.makedirs(cap.logdir, exist_ok=True)
        _drain_devices()
        # no Python tracer: it records every Python call and so slows the
        # host's own code about fourfold, and the idle time the report
        # books to the program's spans would be the tracer's; nothing here
        # reads those events
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        cap.t_begin = time.perf_counter()
        jax.profiler.start_trace(cap.logdir, profiler_options=options)
    except Exception as e:  # noqa: BLE001 — profiling never kills a run
        release_device_trace("device_profile")
        get_telemetry().counter("profile/capture_failed")
        logger.warning("device_profile: jax.profiler.start_trace failed "
                       "(%s) — capture dropped", e)
        _discard(cap)
        _armed = None
        _refresh_hot_locked()
        return
    cap.started = True
    cap.t_start = time.perf_counter()
    # the starting boundary is the step's BEGINNING: zero steps have
    # completed inside the window yet — each LATER boundary of the
    # trigger entry marks one completed step
    cap.trigger_entry = entry
    cap.trigger_seen = 0
    _armed = None
    _active = cap


def _stop_locked(cap: _Capture) -> None:
    """End the window at this boundary: stop the trace, attribute,
    publish (lock held — boundary calls are engine-loop serialized, and
    parsing one small windowed trace is an explicitly requested cost)."""
    global _active
    tel = get_telemetry()
    try:
        import jax

        _drain_devices()
        wall_ms = (time.perf_counter() - cap.t_start) * 1e3
        jax.profiler.stop_trace()
    except Exception as e:  # noqa: BLE001
        tel.counter("profile/capture_failed")
        logger.warning("device_profile: jax.profiler.stop_trace failed "
                       "(%s)", e)
        _active = None
        release_device_trace("device_profile")
        _refresh_hot_locked()
        return
    _active = None
    release_device_trace("device_profile")
    _refresh_hot_locked()
    try:
        _finish_capture(cap, wall_ms, tel)
    except Exception as e:  # noqa: BLE001 — attribution is best-effort
        tel.counter("profile/capture_failed")
        logger.warning("device_profile: attribution failed (%s) — raw "
                       "trace %s", e,
                       cap.logdir if not cap.cleanup else "discarded")
    finally:
        if cap.cleanup:
            shutil.rmtree(cap.logdir, ignore_errors=True)


def _refresh_hot_locked() -> None:
    global _hot
    _hot = _armed is not None or _active is not None or _every > 0


def _finish_capture(cap: _Capture, wall_ms: float, tel) -> None:
    global _last_report, _last_chrome
    trace = hlo_attrib.load_trace(cap.logdir)
    if trace is None:
        tel.counter("profile/capture_failed")
        return
    # steps for windowed entries: one boundary may cover N compiled
    # steps (executor.run_steps / fleet.train_step_multi) — scale by the
    # registered steps-per-call so per-step numbers stay per-STEP
    from . import xla_cost

    steps = {e: n * xla_cost.cost_registry().steps_per_call(e)
             for e, n in cap.entry_steps.items()}
    texts = xla_cost.hlo_texts()
    # the trace's clock against the spans' (perf_counter): the capture's
    # wall placed on the trace, where a span both records hold says how
    offset = clock_offset_us(trace, cap.t_begin * 1e6,
                             (cap.t_start + wall_ms / 1e3) * 1e6)
    window = None
    if offset is not None:
        lo = cap.t_start * 1e6 - offset
        window = (lo, lo + wall_ms * 1e3)
    report = hlo_attrib.attribute_trace(
        trace, texts, steps=steps, wall_ms=wall_ms,
        trigger_entry=cap.trigger_entry,
        default_steps=max(steps.get(cap.trigger_entry or "", 1), 1),
        window_us=window)
    if report is None:
        tel.counter("profile/capture_failed")
        return
    tel.counter("profile/captures")
    _last_report = report.to_dict(top_k=_top_k)
    _last_chrome = _chrome_from_trace(trace, report, offset)
    publish(tel)
    try:
        # join the per-op device ms against the compiled-HLO collective
        # inventory: gauge/collective/<axis>/ms.<entry> — the measured
        # half of the per-axis attribution (static bytes/count ride
        # along), and the evidence the comm_bound:<axis> verdict
        # refinement reads
        from . import collective_attrib

        collective_attrib.on_capture(report, tel)
    except Exception:  # noqa: BLE001 — attribution is best-effort
        pass
    try:
        # fold the fresh decomposition with the roofline/MFU gauges into
        # bottleneck verdicts NOW — a /metrics scrape right after the
        # window closes must already carry gauge/bottleneck/<entry>
        from . import bottleneck

        xla_cost.publish_mfu(tel)
        bottleneck.publish(tel)
    except Exception:  # noqa: BLE001
        pass
    logger.info(
        "device_profile: captured %d step(s) of %s — wall %.2f ms, "
        "device %.2f ms, host gap %.2f ms",
        cap.steps_total, cap.trigger_entry, report.wall_ms,
        report.device_total_ms, report.host_gap_ms)


def clock_offset_us(trace: dict, since_us: float,
                    until_us: float) -> Optional[float]:
    """The spans' clock (``time.perf_counter`` in µs) less the trace's,
    from one ``pt.*`` span that both the trace and this process's own
    records (the window store, the flight recorder) hold, matched by name
    and step; only records opened in [``since_us``, ``until_us``] are
    taken. A span the trace and the records each hold once with its own
    step (``serve.iter``'s batch index) names itself; else the trace's
    first span is the first of its name the process opened after the
    trace was asked to start. None where no span matches."""
    from . import spans as _spans

    traced = sorted(hlo_attrib.program_spans(trace), key=lambda s: s["ts"])
    if not traced:
        return None
    opened: Dict[int, tuple] = {}  # span id -> (name, step, ts_us)
    for name, _cat, ts, _dur, _tid, sid, _par, step in \
            _spans.window_store().snapshot():
        opened[sid] = ("pt." + name, step, ts)
    for ev in _spans.flight_recorder().tail():
        if ev[0] == "B":
            opened[ev[6]] = ("pt." + ev[1], ev[8], ev[3])
    host: Dict[str, list] = {}
    for name, step, ts in opened.values():
        if since_us <= ts <= until_us:
            host.setdefault(name, []).append((ts, step))
    for recs in host.values():
        recs.sort()

    def key(s):
        return s["name"], s["step"]

    count: Dict[tuple, int] = {}
    for s in traced:
        count[key(s)] = count.get(key(s), 0) + 1
    for s in traced:
        if s["step"] is None or count[key(s)] != 1:
            continue
        match = [ts for ts, step in host.get(s["name"], ())
                 if step == s["step"]]
        if len(match) == 1:
            return match[0] - s["ts"]
    first = traced[0]
    match = [ts for ts, step in host.get(first["name"], ())
             if first["step"] is None or step == first["step"]]
    return match[0] - first["ts"] if match else None


def _chrome_from_trace(trace: dict, report, offset_us: Optional[float],
                       max_events: int = 512) -> list:
    """Device-op slices for the chrome export, on the perf_counter clock
    the host spans use: the trace's own times moved by ``offset_us``
    (``clock_offset_us``). Top-N by duration, bounded. None where no span
    placed the two clocks: a slice put by a guess would sit beside the
    wrong host span."""
    if offset_us is None:
        logger.info("device_profile: no pt.* span in both the trace and "
                    "the span records — device slices left out of the "
                    "chrome export")
        return []
    events = hlo_attrib.device_events(
        trace, known_names=set().union(
            *(set(a.by_op) for a in report.entries.values())) or None)
    events = sorted(events, key=lambda e: -e.get("dur", 0))[:max_events]
    from .spans import rank_pid

    pid = rank_pid()  # rank-scoped like every chrome export (merge-safe)
    return [{"name": e.get("name", "?"), "ph": "X",
             "ts": e.get("ts", 0) + offset_us,
             "dur": e.get("dur", 0), "pid": pid,
             "tid": "device ops", "cat": "device",
             "args": {"entry": report.dominant_entry}}
            for e in events]


def publish(telemetry=None) -> Dict[str, dict]:
    """Refresh the profile gauges from the last report (hooked from
    ``Telemetry.to_jsonl`` like ``publish_mfu``). Returns
    ``{entry: fractions}`` for programmatic callers."""
    rep = _last_report
    if not rep:
        return {}
    tel = telemetry or get_telemetry()
    tel.gauge("profile/wall_ms", rep["wall_ms"])
    tel.gauge("profile/device_total_ms", rep["device_total_ms"])
    out: Dict[str, dict] = {}
    for entry, att in rep.get("entries", {}).items():
        fr = att.get("fractions", {})
        for key, v in fr.items():
            tel.gauge(f"profile/{key}.{entry}", v)
        out[entry] = fr
    return out


def jsonl_payload() -> Optional[dict]:
    """The structured top-K report for the JSONL record (merged as a
    top-level ``"profile"`` key by ``Telemetry.to_jsonl``)."""
    return dict(_last_report) if _last_report else None


def chrome_events(drain: bool = True) -> list:
    """Realigned device-op slices of the last capture for the chrome
    export (drained by default — each export owns its window)."""
    global _last_chrome
    out = list(_last_chrome)
    if drain:
        _last_chrome = []
    return out


def reset() -> None:
    """Forget reports and re-read the env knobs (test isolation; hooked
    from ``Telemetry.reset``). An in-flight capture is abandoned: its
    trace is stopped and discarded."""
    global _armed, _active, _last_report, _last_chrome, _boundary_count
    global _every, _window_steps
    with _lock:
        if _active is not None:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception:  # noqa: BLE001
                pass
            release_device_trace("device_profile")
            _discard(_active)
        _discard(_armed)  # an armed-but-unstarted capture owns a dir too
        _armed = None
        _active = None
        _last_report = None
        _last_chrome = []
        _boundary_count = 0
        _every = _env_int("PADDLE_TPU_DEVICE_PROFILE_EVERY", 0)
        _window_steps = _env_int("PADDLE_TPU_DEVICE_PROFILE_STEPS",
                                 _DEFAULT_STEPS)
        _refresh_hot_locked()
