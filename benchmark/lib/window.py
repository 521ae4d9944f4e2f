"""The measured loop of a training cell: one step in flight.

The loop dispatches step i, then fetches the loss of step i - 1, so the
device never waits for the fetch and every step's completion has a host
timestamp. A window opens at a fetched loss and closes at the first
fetched loss at or past its length; steps and time are counted between
those two points and nowhere else. ``jax.profiler.TraceAnnotation`` wraps
the harness's three calls, so a traced run shows them on the device
trace's clock.
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation


class StepLoop:
    """``next_batch(i)`` gives step i's batch, ``dispatch(batch)`` starts
    the step and returns its loss handle, ``fetch(handle)`` waits for it."""

    def __init__(self, next_batch, dispatch, fetch):
        self._next_batch, self._dispatch, self._fetch = (next_batch, dispatch,
                                                         fetch)
        self._clock = time.perf_counter
        self._i = 0
        self._in_flight = None
        self.losses = []        # every fetched loss, in order
        self.dispatch_ms = []   # host time inside each dispatch call
        self.done_at = []       # host time at which each loss was fetched

    def _start_one(self):
        with TraceAnnotation("bench.next_batch"):
            batch = self._next_batch(self._i)
        t0 = self._clock()
        with TraceAnnotation("bench.dispatch"):
            handle = self._dispatch(batch)
        self.dispatch_ms.append((self._clock() - t0) * 1e3)
        self._i += 1
        return handle

    def _finish(self, handle):
        with TraceAnnotation("bench.fetch_loss"):
            self.losses.append(self._fetch(handle))
        self.done_at.append(self._clock())

    def step(self):
        """Dispatch one step and fetch the one before it."""
        handle = self._start_one()
        if self._in_flight is not None:
            self._finish(self._in_flight)
        self._in_flight = handle

    def drain(self):
        if self._in_flight is not None:
            self._finish(self._in_flight)
            self._in_flight = None

    def run_steps(self, n: int):
        for _ in range(n):
            self.step()

    def run_window(self, seconds: float) -> dict:
        """Measure for ``seconds`` from the newest fetched loss. Needs a
        step in flight and a loss fetched (``run_steps(2)`` before)."""
        first = len(self.done_at)
        opened = self.done_at[-1]
        while self.done_at[-1] - opened < seconds:
            self.step()
        return {"steps": len(self.done_at) - first,
                "seconds": self.done_at[-1] - opened,
                "first": first, "last": len(self.done_at)}
