"""Continuous-batching scheduler — the TPU-facing loop of the server.

One thread owns the device: it pulls whatever is queued (up to
``max_batch``), packs it into the smallest batch-size bucket that fits,
and dispatches ONE compiled executable per bucket shape. Buckets bound
the compile count exactly like ``io.ShapeBuckets`` bounds training-feed
retraces: a serving process compiles ``len(buckets)`` executables total
(amortized further by the persistent XLA compile cache — PR 2 — so a
RESTARTED server skips even those), then never retraces again no matter
how request sizes mix. Padding rows are zeros; results for them are
sliced off before delivery.

Robustness wiring, per batch iteration:
- ``resilience.watchdog.heartbeat()`` — a hung device step trips the
  watchdog into a stack dump + exit 113, which the launch supervisor
  relaunches (PR 6);
- preemption flag check — SIGTERM (via ``resilience.preemption``) flips
  the engine into drain: admission stops, queued work finishes or
  deadlines out, leftovers get DRAINED;
- deadline enforcement at completion — a batch that finished past a
  request's deadline discards THAT request's output (stale results are
  never delivered) and counts ``serve/deadline_exceeded``;
- fault injection (``resilience.inject``): ``slow_req@id:secs`` stalls
  the batch containing that request (straggler simulation),
  ``drop_req@id`` loses its result post-execution (the accounting layer
  must still terminate it), ``sigterm@n`` delivers a real SIGTERM at
  batch-boundary ``n`` (mid-load preemption, deterministic).
"""
from __future__ import annotations

import threading
import time
import traceback
from typing import Dict, List

import numpy as np

from ...profiler import device_profile as _device_profile
from ...profiler import goodput as _goodput
from ...profiler.retrace import tracked_jit
from ...profiler.telemetry import get_telemetry
from ...resilience.inject import active_injector
from ...resilience.preemption import preemption_requested
from ...resilience.watchdog import heartbeat
from .request import Request, RequestStatus

__all__ = ["BatchScheduler"]


class BatchScheduler:
    """The engine's batch loop; one instance, one daemon thread."""

    def __init__(self, engine):
        self._engine = engine
        self._thread = threading.Thread(
            target=self._run, name="ServingScheduler", daemon=True)
        self._stopped = threading.Event()
        self.batch_index = 0
        # bucket size -> tracked_jit entry. Per-BUCKET entries (not one
        # shared entry) so each bucket owns its MFU denominator: xla_cost
        # maps "serve.step.b<B>" to the "serve/batch_ms.b<B>" histogram
        # this loop records, and publishes gauge/mfu/serve.step.b<B>.
        self._bucket_fns: Dict[int, object] = {}

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        self._thread.start()
        return self

    def join(self, timeout=None):
        self._thread.join(timeout)

    @property
    def alive(self) -> bool:
        return self._thread.is_alive()

    # -- compiled executables ----------------------------------------------
    def _fn_for_bucket(self, bucket: int):
        fn = self._bucket_fns.get(bucket)
        if fn is None:
            raw = self._engine._serving_fn
            fn = tracked_jit(raw, name=f"serve.step.b{bucket}")
            self._bucket_fns[bucket] = fn
        return fn

    def warmup(self) -> Dict[int, float]:
        """Compile every bucket's executable up front with zero batches
        (cold-start cost paid before the first real request; a restarted
        server replays these from the persistent compile cache). Returns
        ``{bucket: wall_ms}`` of the compiling call — the engine's load
        calibration reads the LAST (largest, fully warm) entry."""
        out: Dict[int, float] = {}
        for b in self._engine.config.buckets:
            arrays = self._engine._zero_batch(b)
            fn = self._fn_for_bucket(b)
            t0 = time.perf_counter()
            res = fn(*arrays)
            for leaf in (res if isinstance(res, (list, tuple)) else (res,)):
                np.asarray(leaf)  # block: measure compile+run, not dispatch
            out[b] = (time.perf_counter() - t0) * 1e3
        return out

    # -- the loop ----------------------------------------------------------
    def _run(self):
        eng = self._engine
        tel = get_telemetry()
        ready: List[Request] = []
        try:
            while True:
                ready = []
                heartbeat()  # a hung dispatch below -> watchdog 113
                if preemption_requested() and not eng.draining:
                    eng._begin_drain(reason="preempted")
                ready, expired = eng._queue.take(
                    eng.config.max_batch, timeout=eng.config.idle_poll_s)
                now = time.monotonic()
                for r in ready:  # sampled traces: queue wait ends here
                    r.trace_event("queue", dur_s=now - r.submitted_at)
                for r in expired:
                    eng._finish(r, RequestStatus.DEADLINE_EXCEEDED,
                                detail="deadline expired in queue")
                if tel.enabled:
                    tel.gauge("serve/queue_depth", len(eng._queue))
                if not ready:
                    if eng.draining and len(eng._queue) == 0:
                        return  # drained dry — engine finalizes
                    continue
                # device-profile capture boundary: one serving batch is
                # one "step" of this loop (no-op unless a capture armed)
                _device_profile.step_boundary("serve.step")
                # goodput: one served batch is one productive step of
                # this host loop (in a serving-only process the
                # scheduler thread is the ledger's driver; inside a
                # trainer it is a background thread and this is a no-op)
                with _goodput.activity("productive_step"):
                    self._run_batch(ready)
                self.batch_index += 1
                inj = active_injector()
                if inj is not None:
                    inj.maybe_sigterm(self.batch_index)
        except BaseException:
            # a scheduler crash must not strand accepted requests without
            # terminal statuses: latch drain FIRST so submits racing the
            # crash (and every one after it) are shed as REJECTED rather
            # than admitted into a queue no thread serves, then fail the
            # batch in hand (taken from the queue but possibly not yet
            # terminal — only the still-pending ones, so double_terminal
            # stays a truthful invariant) plus everything still queued
            tb = traceback.format_exc()
            eng._begin_drain(reason="scheduler crashed")
            for r in ready + eng._queue.pop_all():
                if not r.done():
                    eng._finish(r, RequestStatus.ERROR,
                                detail=f"scheduler crashed:\n{tb}")
            raise
        finally:
            self._stopped.set()

    def _run_batch(self, reqs: List[Request]):
        eng = self._engine
        tel = get_telemetry()
        inj = active_injector()
        if inj is not None:
            for r in reqs:  # injected straggler: stall the whole batch
                inj.slow_req(r.id)
        n = len(reqs)
        bucket = eng.config.bucket_for(n)
        t0 = time.perf_counter()
        try:
            arrays = eng._stack_batch(reqs, bucket)
            outs = self._fn_for_bucket(bucket)(*arrays)
            outs = outs if isinstance(outs, (list, tuple)) else (outs,)
            outs_np = [np.asarray(o) for o in outs]  # drains the device
        except BaseException as e:
            detail = f"batch execution failed: {e!r}"
            for r in reqs:
                eng._finish(r, RequestStatus.ERROR, detail=detail, error=e)
            return
        batch_ms = (time.perf_counter() - t0) * 1e3
        for r in reqs:  # sampled traces: the compiled step this rode in
            r.trace_event(f"batch.b{bucket}", dur_s=batch_ms / 1e3)
        if tel.enabled:
            tel.counter("serve/batches")
            tel.observe("serve/batch_ms", batch_ms)
            tel.observe(f"serve/batch_ms.b{bucket}", batch_ms)
            tel.observe("serve/batch_occupancy", n / bucket)
        now = time.monotonic()
        for k, r in enumerate(reqs):
            if inj is not None and inj.drop_req_due(r.id):
                eng._finish(r, RequestStatus.ERROR,
                            detail="result dropped (injected)")
                continue
            if r.deadline is not None and now >= r.deadline:
                # the slot is already burned, but a stale result is
                # never delivered as success
                eng._finish(r, RequestStatus.DEADLINE_EXCEEDED,
                            detail="completed past deadline")
                continue
            eng._finish(r, RequestStatus.OK,
                        outputs=[o[k] for o in outs_np])
