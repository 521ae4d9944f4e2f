"""ServingEngine — the overload-safe request-serving runtime.

Composition (one engine per served model):

    client threads ──submit()──▶ AdmissionQueue ──take()──▶ BatchScheduler
                        │ explicit shed                        │ bucketed
                        ▼                                      ▼ AOT step
                  REJECTED status                   OK / DEADLINE_EXCEEDED

Headline property: graceful degradation. Past capacity the server says
no (``REJECTED`` at submit — bounded queue, bounded p99 for what it
accepts) instead of buffering into collapse; expired work is shed at
every stage rather than burning TPU slots; SIGTERM triggers a drain
(admission stops, queued work finishes or deadlines out, the rest is
``DRAINED``) and then the PR 4 preemption exit (77) so the launch
supervisor relaunches the replica. Every submitted request reaches
exactly one terminal status — ``accounting()`` proves it.

Telemetry (``serve/*``, schema-gated by tools/check_telemetry_schema):
counters ``requests accepted completed admission_rejects
deadline_exceeded drained errors batches double_terminal``; gauges
``queue_depth queue_capacity draining dtype_bits``; histograms
``latency_ms batch_ms[.b<N>] batch_occupancy``. Each batch bucket is a
``tracked_jit`` entry (``serve.step.b<N>``) so the PR 5 attribution
layer publishes per-bucket FLOPs/HBM and MFU.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from ...profiler import spans as _spans
from ...profiler.telemetry import get_telemetry
from ...resilience.inject import active_injector
from .admission import (ADMIT, REJECT_CAPACITY, REJECT_DRAINING,
                        REJECT_EXPIRED, AdmissionQueue)
from .request import Request, RequestStatus
from .scheduler import BatchScheduler

__all__ = ["ServeConfig", "ServingEngine"]


class ServeConfig:
    """Serving knobs. ``buckets`` are BATCH-SIZE buckets (the batch axis
    twin of ``io.ShapeBuckets``): compiles are bounded by len(buckets).

    Args:
        capacity: admission queue bound — the backlog past which submits
            are REJECTED (load shedding, never silent buffering).
        buckets: ascending batch sizes; each compiles one executable.
        max_batch: most requests packed per dispatch (default: largest
            bucket).
        default_deadline_s: deadline for requests that don't carry one
            (None = no deadline).
        drain_grace_s: on drain, how long queued work may keep running
            before the remainder is terminally DRAINED.
        idle_poll_s: scheduler wait per empty take() — also the drain /
            preemption-flag check cadence.
    """

    def __init__(self, capacity: int = 64,
                 buckets: Sequence[int] = (1, 2, 4, 8),
                 max_batch: Optional[int] = None,
                 default_deadline_s: Optional[float] = None,
                 drain_grace_s: float = 5.0,
                 idle_poll_s: float = 0.01):
        self.capacity = int(capacity)
        self.buckets = tuple(sorted(int(b) for b in buckets))
        if not self.buckets or self.buckets[0] < 1:
            raise ValueError(f"buckets must be positive: {buckets}")
        self.max_batch = (self.buckets[-1] if max_batch is None
                          else int(max_batch))
        if self.max_batch > self.buckets[-1]:
            raise ValueError(
                f"max_batch {self.max_batch} exceeds the largest bucket "
                f"{self.buckets[-1]} — a batch that fits no bucket cannot "
                "be dispatched")
        self.default_deadline_s = default_deadline_s
        self.drain_grace_s = float(drain_grace_s)
        self.idle_poll_s = float(idle_poll_s)

    def bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        raise ValueError(f"batch of {n} exceeds largest bucket "
                         f"{self.buckets[-1]}")


class ServingEngine:
    """Continuous-batching server over one ``inference.Predictor``."""

    def __init__(self, predictor, config: Optional[ServeConfig] = None):
        self.config = config or ServeConfig()
        self._predictor = predictor
        self._serving_fn = predictor.serving_fn()
        self._sample_specs = predictor.sample_specs()
        self._init_runtime()

    def _make_scheduler(self):
        """The device-loop this engine runs (the token-level decode
        engine substitutes its own scheduler; everything else — ledger,
        admission, drain, preemption — is shared verbatim)."""
        return BatchScheduler(self)

    def _init_runtime(self) -> None:
        """Queue + scheduler + the terminal-accounting ledger + drain
        state — the request-lifecycle core both engine variants share.
        Requires ``self.config`` to carry at least ``capacity``,
        ``drain_grace_s`` and ``idle_poll_s``."""
        self._queue = AdmissionQueue(self.config.capacity)
        self._scheduler = self._make_scheduler()
        self._tel = get_telemetry()
        self._id_lock = threading.Lock()
        self._next_id = 0
        # memory-bounded accounting: the engine holds a request object
        # only while it is PENDING (dropped at its terminal transition —
        # callers keep their own refs); the ledger keeps COUNTS, so a
        # long-running server's footprint is O(in-flight), not O(ever
        # submitted)
        self._pending: Dict[int, Request] = {}
        self._status_counts: Dict[str, int] = {}
        self._submitted_total = 0
        self._double_terminal = 0
        self._started = False
        self._drain_reason: Optional[str] = None
        self._drained = threading.Event()
        self._drain_latch_lock = threading.Lock()
        self._on_drain: Optional[Callable[[], None]] = None
        self._grace_timer: Optional[threading.Timer] = None

    # -- lifecycle ---------------------------------------------------------
    def start(self, warmup: bool = True) -> "ServingEngine":
        """Arm the scheduler; with ``warmup`` (default) every bucket's
        executable is compiled before the first request is accepted —
        on a relaunch these come out of the persistent XLA cache
        (``device.configure_compilation_cache``), so a relaunched replica
        is serving-warm without a compile storm under live traffic."""
        if self._started:
            return self
        if self._tel.enabled:
            self._tel.gauge("serve/queue_capacity", self.config.capacity)
            self._tel.gauge("serve/draining", 0)
            self._publish_start_gauges()
        self.warmup_ms = self._scheduler.warmup() if warmup else {}
        self._started = True
        self._scheduler.start()
        # ops plane: register this engine as the rank's live serving
        # state (drain latch, queue saturation, in-flight ledger) and
        # arm the env-gated per-rank HTTP server — both no-ops without
        # PADDLE_TPU_OPS_PORT, and neither may block serving startup
        try:
            from ...profiler import ops_server

            ops_server.set_serving_engine(self)
            ops_server.maybe_start_from_env(telemetry=self._tel)
        except Exception:
            pass
        return self

    def _publish_start_gauges(self) -> None:
        """Engine-variant start-time gauges (the decode engine has no
        predictor and overrides this to a no-op)."""
        self._tel.gauge("serve/dtype_bits",
                        getattr(self._predictor, "serving_dtype_bits", 32))

    # -- client side -------------------------------------------------------
    def submit(self, inputs: Sequence[np.ndarray],
               deadline_s: Optional[float] = None,
               ) -> Request:
        """Admit or shed one request. ALWAYS returns a ``Request``; a
        shed one is already terminal (REJECTED / DEADLINE_EXCEEDED) —
        callers branch on status, they never wait on a rejected slot."""
        if not self._started:
            raise RuntimeError("ServingEngine.start() first")
        # validate BEFORE consuming an id / the submitted total: a
        # ValueError here must leave the ledger untouched, or submitted
        # would forever exceed terminal+pending by the rejected calls
        if len(inputs) != len(self._sample_specs):
            raise ValueError(
                f"request has {len(inputs)} inputs, model takes "
                f"{len(self._sample_specs)}")
        arrays = []
        for a, (shape, dtype) in zip(inputs, self._sample_specs):
            a = np.asarray(a, dtype=dtype)
            if tuple(a.shape) != tuple(shape):
                raise ValueError(
                    f"request input shape {tuple(a.shape)} != per-sample "
                    f"spec {tuple(shape)} (submit WITHOUT the batch axis)")
            arrays.append(a)
        req_id = self._allocate_request_id()
        req = Request(req_id, arrays,
                      self._resolve_deadline(req_id, deadline_s))
        return self._admit(req)

    # -- admission funnel (shared by both engine variants) ------------------
    def _allocate_request_id(self) -> int:
        with self._id_lock:
            req_id = self._next_id
            self._next_id += 1
            self._submitted_total += 1
        return req_id

    def _resolve_deadline(self, req_id: int,
                          deadline_s: Optional[float]) -> Optional[float]:
        inj = active_injector()
        if inj is not None:
            storm = inj.storm_deadline(req_id)
            if storm is not None:  # injected deadline storm
                return storm
        return (self.config.default_deadline_s if deadline_s is None
                else deadline_s)

    def _admit(self, req: Request) -> Request:
        """Register + enqueue-or-shed one constructed request — the ONE
        verdict dispatch both engine variants share, so the
        exactly-one-terminal ledger semantics cannot drift between
        them. Also the ONE place request-scoped traces are minted: a
        sampled request (PADDLE_TPU_TRACE_SAMPLE, deterministic on id)
        carries its timeline from here to its terminal transition."""
        if _spans.should_trace(req.id):
            req.trace = _spans.ReqTrace(req.id)
            req.trace_event("submit")
        with self._id_lock:
            self._pending[req.id] = req
        if self._tel.enabled:
            self._tel.counter("serve/requests")
        verdict = self._queue.submit(req)  # stamps 'admit' on admission
        if verdict == ADMIT:
            if self._tel.enabled:
                self._tel.counter("serve/accepted")
                self._tel.gauge("serve/queue_depth", len(self._queue))
        elif verdict == REJECT_EXPIRED:
            self._finish(req, RequestStatus.DEADLINE_EXCEEDED,
                         detail="deadline expired before enqueue")
        else:  # capacity or draining: explicit shed
            self._finish(req, RequestStatus.REJECTED,
                         detail=f"admission rejected: {verdict}")
        return req

    # -- terminal accounting (single funnel) --------------------------------
    def _finish(self, req: Request, status: str, outputs=None,
                detail: str = "", error=None) -> None:
        if not req.finish(status, outputs=outputs, detail=detail,
                          error=error):
            # two paths claimed one request — the invariant the drain
            # test asserts stays zero ("never both executed and
            # rejected")
            with self._id_lock:
                self._double_terminal += 1
            if self._tel.enabled:
                self._tel.counter("serve/double_terminal")
            return
        if req.trace is not None:
            # terminal stamp closes the sampled timeline; publishing to
            # the trace store is what /debug/requests and the chrome
            # export read — only the WINNING transition publishes, so a
            # trace appears exactly once
            req.trace_event(f"terminal:{status}")
            _spans.trace_store().add(req.trace)
        with self._id_lock:
            self._pending.pop(req.id, None)
            self._status_counts[status] = \
                self._status_counts.get(status, 0) + 1
        if not self._tel.enabled:
            return
        if status == RequestStatus.OK:
            self._tel.counter("serve/completed")
            self._tel.observe("serve/latency_ms", req.latency_ms())
        elif status == RequestStatus.REJECTED:
            self._tel.counter("serve/admission_rejects")
        elif status == RequestStatus.DEADLINE_EXCEEDED:
            self._tel.counter("serve/deadline_exceeded")
        elif status == RequestStatus.DRAINED:
            self._tel.counter("serve/drained")
        elif status == RequestStatus.ERROR:
            self._tel.counter("serve/errors")

    def accounting(self) -> dict:
        """The overload-safety ledger: status counts over every request
        this engine ever returned from ``submit``, the ids (if any) that
        lack a terminal status, and the double-terminal count. A healthy
        drain shows ``unaccounted == []`` and ``double_terminal == 0``."""
        with self._id_lock:
            # _pending may briefly hold a just-terminal request (finish
            # wins its race before the pop) — filter by status, which is
            # the authoritative transition
            unaccounted = sorted(
                r.id for r in self._pending.values()
                if r.status not in RequestStatus.TERMINAL)
            return {"submitted": self._submitted_total,
                    "by_status": dict(self._status_counts),
                    "unaccounted": unaccounted,
                    "double_terminal": self._double_terminal}

    def debug_requests(self, limit: int = 256) -> list:
        """The in-flight ledger for the ops plane's ``/debug/requests``:
        one row per PENDING request (age, phase, deadline remaining,
        generation progress), oldest first, capped at ``limit`` — an
        overloaded replica must not build an unbounded JSON body."""
        with self._id_lock:
            reqs = sorted(self._pending.values(),
                          key=lambda r: r.submitted_at)
        now = time.monotonic()
        return [r.debug_state(now) for r in reqs
                if r.status == RequestStatus.PENDING][:int(limit)]

    # -- batch-formation helpers (scheduler-facing) -------------------------
    def _stack_batch(self, reqs: List[Request], bucket: int
                     ) -> List[np.ndarray]:
        arrays = []
        n = len(reqs)
        for i in range(len(self._sample_specs)):
            arr = np.stack([r.inputs[i] for r in reqs])
            if bucket > n:  # zero padding rows, sliced off after the run
                pad = np.zeros((bucket - n,) + arr.shape[1:], arr.dtype)
                arr = np.concatenate([arr, pad])
            arrays.append(arr)
        return arrays

    def _zero_batch(self, bucket: int) -> List[np.ndarray]:
        return [np.zeros((bucket,) + tuple(shape), dtype)
                for shape, dtype in self._sample_specs]

    # -- drain / shutdown ---------------------------------------------------
    @property
    def draining(self) -> bool:
        return self._queue.draining

    @property
    def drain_reason(self) -> Optional[str]:
        return self._drain_reason

    def _begin_drain(self, reason: str) -> None:
        # atomic check-and-latch: the scheduler (preemption flag) and a
        # user drain() can race here — only ONE may arm the grace timer
        # and the on_drain hook
        with self._drain_latch_lock:
            if self._queue.draining:
                return
            self._drain_reason = reason
            self._queue.start_drain()
        # goodput: from the drain latch until exit, unclaimed wall time
        # is drain_shutdown, not unattributed (thread-agnostic flip —
        # the latch may trip from the scheduler thread)
        from paddle_tpu.profiler import goodput as _goodput

        _goodput.shutdown_begin()
        if self._tel.enabled:
            self._tel.gauge("serve/draining", 1)
            self._tel.counter("serve/drains")
        # grace: queued work may keep running this long; the remainder
        # is terminally DRAINED so the preemption exit never strands an
        # accepted request without a status
        self._grace_timer = threading.Timer(self.config.drain_grace_s,
                                            self._grace_expired)
        self._grace_timer.daemon = True
        self._grace_timer.start()
        # watcher publishes drain completion + runs the on_drain hook
        # (daemon: must not hold the interpreter open if the main thread
        # dies mid-drain)
        threading.Thread(target=self._watch_drain, name="ServingDrain",
                         daemon=True).start()

    def _grace_expired(self) -> None:
        for r in self._queue.pop_all():
            self._finish(r, RequestStatus.DRAINED,
                         detail="unfinished at drain-grace expiry")

    def _watch_drain(self) -> None:
        self._scheduler.join(timeout=self.config.drain_grace_s + 30.0)
        if self._grace_timer is not None:
            self._grace_timer.cancel()
        for r in self._queue.pop_all():  # scheduler died mid-drain
            self._finish(r, RequestStatus.DRAINED,
                         detail="unfinished at drain completion")
        if self._tel.enabled:
            self._tel.gauge("serve/draining", 0)
            self._tel.gauge("serve/queue_depth", 0)
        if self._on_drain is not None:
            try:
                self._on_drain()
            except Exception:
                pass  # the drain outcome outranks its hook
        self._drained.set()

    def drain(self, wait: bool = True, reason: str = "drain",
              timeout: Optional[float] = None) -> dict:
        """Stop admission, let queued work finish or deadline-out within
        the grace window, terminate the rest as DRAINED. Returns the
        accounting ledger (after completion when ``wait``)."""
        if not self._started:
            self._drained.set()
            return self.accounting()
        self._begin_drain(reason)
        if wait:
            self.wait_drained(timeout)
        return self.accounting()

    def wait_drained(self, timeout: Optional[float] = None) -> bool:
        return self._drained.wait(
            self.config.drain_grace_s + 30.0 if timeout is None else timeout)

    def shutdown(self) -> dict:
        """Clean teardown — same path as drain (queued work is never
        silently dropped), then joins the scheduler. Safe to call from a
        ``finally`` even when ``start()`` never ran."""
        acct = self.drain(wait=True, reason="shutdown")
        if self._started:  # joining a never-started thread raises
            self._scheduler.join(timeout=5.0)
        return acct

    # -- preemption wiring (PR 4) -------------------------------------------
    def install_preemption(self, on_drain: Optional[Callable[[], None]] = None
                           ) -> "ServingEngine":
        """Arm SIGTERM/SIGINT handling: the scheduler's batch loop
        checks the preemption flag and flips into drain. ``on_drain``
        runs after every accepted request is terminal (write your
        accounting/telemetry there); then call ``exit_if_preempted()``
        from the main thread to take the exit-77 relaunch path."""
        from ...resilience.preemption import install_preemption_handler

        install_preemption_handler()
        self._on_drain = on_drain
        return self

    def exit_if_preempted(self, save_fn: Optional[Callable[[], None]] = None,
                          timeout: Optional[float] = None) -> bool:
        """When a preemption triggered the drain: wait for it to finish
        and exit via ``resilience.preemption.exit_for_relaunch`` (raises
        ``SystemExit(77)`` — the launch supervisor relaunches). Returns
        False when no preemption drain happened (normal shutdowns fall
        through). Also consults the preemption flag directly: a SIGTERM
        that raced an already-latched drain (or landed after the
        scheduler exited) never got to set the drain REASON, but must
        still take the relaunch exit."""
        from ...resilience.preemption import (exit_for_relaunch,
                                              preemption_requested)

        if self._drain_reason != "preempted" and not preemption_requested():
            return False

        self.wait_drained(timeout)
        exit_for_relaunch(save_fn)
        return True  # unreachable (exit raises); documents intent
