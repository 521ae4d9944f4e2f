"""The benchmark's own load generator for a served model: what is offered,
and when, is fixed here and in a cell's data file, and by nothing in the
program.

A cell's ``traffic`` group gives the parameters::

    {"loop": "open", "rate_per_s": 8.0, "ramp_s": 5.0,
     "prompt_tokens": {"dist": "lognormal", "median": 192, "sigma": 0.6,
                       "min": 64, "max": 768},
     "new_tokens": {"dist": "uniform", "min": 32, "max": 256}}

    {"loop": "closed", "clients": 64, "ramp_spread_s": 4.0,
     "ramp_hold_s": 2.0, "pool": 1024, ...the two length groups...}

``schedule`` is a pure function of those and the seed. Every seed gets the
same set of lengths (the distribution's quantiles at ``(i + 0.5) / n``) and,
in an open loop, the same set of gaps between arrivals (an exponential's
quantiles: Poisson arrivals), each in an order of its own drawn from the
seed, and prompt ids uniform over the published vocabulary: the seed moves
which request comes when, not how much work a run holds. In a closed loop
the answers' lengths *are* the arrivals (a client sends when its last
answer ended), so they come in one order for every seed, by client and
generation (client ``c``'s ``g``-th request is entry ``c + g * clients``),
and so do the first requests' shares: every seed's window then holds the
same completions, and the seed orders the prompts' lengths and draws their
ids.

``LoadRun`` drives an engine through one run on one thread. Open loop: a
request is submitted at its due time whether or not earlier ones finished,
and is timed from that due time, so a generator that runs late shows as
latency and in ``lag_ms``. Closed loop: each client submits its next
request when its last reached a terminal status; a request's due time is
the moment its client was seen free. A client's first request is cut to a
share of its new tokens (the shares are (c + 0.5) / clients, in an order
drawn from the seed), so that the clients' generations are out of step from
the start as they are in a loop that has run for long. The thread stamps
each request where it sees it ended (``Sent.ended``, the generator's own
clock), samples what the engine shows of itself (queue depth, pool
occupancy) and reads its counters when the window opens and when it closes;
there the load stops. The window's requests are those that ended inside it.
"""
from __future__ import annotations

import math
import statistics
import threading
import time

import numpy as np

TICK_S = 0.01       # the longest the generator sleeps between looks
SAMPLE_S = 0.02     # how often it samples the engine's gauges
EDGE_WAIT_S = 5.0   # the longest an end of the window waits for a step's end


def quantile_set(spec: dict, n: int) -> np.ndarray:
    """``n`` whole numbers that follow ``spec``: the distribution's
    quantiles at (i + 0.5) / n, clipped to [min, max]."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"])
    elif spec["dist"] == "lognormal":
        normal = statistics.NormalDist()
        z = np.array([normal.inv_cdf(v) for v in u])
        x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    return np.clip(np.rint(x), spec.get("min", 1),
                   spec.get("max", np.inf)).astype(np.int64)


def schedule(traffic: dict, seed: int, vocab: int, seconds: float) -> dict:
    """Every request of a run: ``prompts`` (int32 arrays), ``new_tokens``,
    for an open loop ``due_s`` (seconds after the load starts), for a closed
    one ``first_share`` (the share of its new tokens that each client's
    first request keeps)."""
    rng = np.random.default_rng([int(seed), 0x5E12FE])
    lengths_rng, due, share = rng, None, None
    if traffic["loop"] == "open":
        span = traffic["ramp_s"] + seconds
        n = max(1, int(round(traffic["rate_per_s"] * span)))
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / traffic["rate_per_s"]
        due = np.cumsum(rng.permutation(gaps))
        due = due - due[0] * rng.random()  # the first is not always late
    else:
        n, clients = int(traffic["pool"]), int(traffic["clients"])
        lengths_rng = np.random.default_rng(0x5E12FE)  # the same for all seeds
        share = lengths_rng.permutation((np.arange(clients) + 0.5) / clients)
    prompt_len = rng.permutation(quantile_set(traffic["prompt_tokens"], n))
    new_tokens = lengths_rng.permutation(
        quantile_set(traffic["new_tokens"], n))
    ids = rng.integers(0, vocab, int(prompt_len.sum()), dtype=np.int32)
    prompts = np.split(ids, np.cumsum(prompt_len)[:-1])
    return {"prompts": prompts, "new_tokens": [int(x) for x in new_tokens],
            "due_s": None if due is None else [float(x) for x in due],
            "first_share": None if share is None else [float(x)
                                                       for x in share]}


class Sent:
    """One submitted request: which one of the run it was (``index``, in
    the order sent), its entry in the schedule (``slot``), when it was due,
    when the generator got to it, when the generator saw it ended (None
    while it runs), and the program's request object."""
    __slots__ = ("index", "slot", "due", "submitted", "ended", "req")

    def __init__(self, index, slot, due, submitted, req):
        self.index, self.slot, self.due = index, slot, due
        self.submitted, self.req, self.ended = submitted, req, None


class LoadRun:
    """One run's load on one thread. ``submit(prompt, new_tokens)`` gives
    the program's request (``done()``, ``status``); ``observe()`` gives a
    dict of what the engine shows now (sampled), ``counters()`` a dict of
    its counters, read at the window's two ends: at the first look past
    each end at which ``edge()`` (a number that moves when the engine
    finishes a step) has moved. ``before_submit(i)`` is the tests' hook to
    stall the generator."""

    def __init__(self, traffic, plan, seconds, submit, observe=None,
                 counters=None, edge=None, before_submit=None,
                 clock=time.monotonic):
        self.traffic, self.plan, self.seconds = traffic, plan, seconds
        self._submit, self._observe = submit, observe
        self._counters, self._edge = counters, edge
        self._before = before_submit
        self.clock = clock
        self.sent: list = []
        self.samples: list = []       # (time, observed dict)
        self.at_open = self.at_close = None   # (time, counters dict)
        self.ramp_s = (traffic["ramp_s"] if traffic["loop"] == "open" else
                       traffic["ramp_spread_s"] + traffic["ramp_hold_s"])
        self._thread = threading.Thread(target=self._run, name="bench.load",
                                        daemon=True)
        self.error = None

    def start(self):
        self.t_start = self.clock()
        self.t_open = self.t_start + self.ramp_s
        self.t_close = self.t_open + self.seconds
        self._thread.start()
        return self

    def join(self):
        self._thread.join()
        if self.error is not None:
            raise self.error

    def _send(self, slot, due, share=1.0):
        index = len(self.sent)
        if self._before is not None:
            self._before(index)
        slot %= len(self.plan["prompts"])
        new = max(1, math.ceil(share * self.plan["new_tokens"][slot]))
        req = self._submit(self.plan["prompts"][slot], new)
        self.sent.append(Sent(index, slot, due, self.clock(), req))
        return self.sent[-1]

    def _run(self):
        try:
            self._loop()
        except BaseException as e:  # the main thread raises it from join
            self.error = e

    def _loop(self):
        clock, open_loop = self.clock, self.traffic["loop"] == "open"
        next_index, next_sample, running = 0, 0.0, []
        seen = self._edge() if self._edge is not None else None
        if open_loop:
            due = [self.t_start + d for d in self.plan["due_s"]]
        else:
            n_clients = self.traffic["clients"]
            first = [self.t_start + self.traffic["ramp_spread_s"] * c
                     / n_clients for c in range(n_clients)]
            last, sends = [None] * n_clients, [0] * n_clients
        while True:
            now = clock()
            for s in running:
                if s.req.done():
                    s.ended = now
            running = [s for s in running if s.ended is None]
            # the counters are read where the engine has just finished a
            # step (``edge`` has moved since the last look): a window that
            # began or ended inside a step would count it whole or not at all
            value = self._edge() if self._edge is not None else None
            moved, seen = self._edge is None or value != seen, value
            if self.at_open is None and (
                    now >= self.t_open + EDGE_WAIT_S
                    or now >= self.t_open and moved):
                self.at_open = (clock(), self._counters() if self._counters
                                else {})
            if self.at_close is None and (
                    now >= self.t_close + EDGE_WAIT_S
                    or now >= self.t_close and moved):
                self.at_close = (clock(), self._counters() if self._counters
                                 else {})
                return
            before = len(self.sent)
            wake = now + TICK_S
            if open_loop:
                while next_index < len(due) and due[next_index] <= now:
                    self._send(next_index, due[next_index])
                    next_index += 1
                if next_index < len(due):
                    wake = min(wake, due[next_index])
            else:
                for c in range(n_clients):
                    slot = c + sends[c] * n_clients
                    if last[c] is None:
                        if now < first[c]:
                            continue
                        last[c] = self._send(slot, first[c],
                                             self.plan["first_share"][c])
                    elif last[c].ended is not None:
                        last[c] = self._send(slot, now)
                    else:
                        continue
                    sends[c] += 1
            running += self.sent[before:]
            if self._observe is not None and now >= next_sample:
                self.samples.append((now, self._observe()))
                next_sample = now + SAMPLE_S
            pause = wake - clock()
            if pause > 0:
                time.sleep(pause)

    # -- what the window held ------------------------------------------------
    def attempted(self) -> list:
        """The requests that ended inside the window, in whatever status:
        seen ended between the two readings of the counters."""
        lo, hi = self.at_open[0], self.at_close[0]
        return [s for s in self.sent
                if s.ended is not None and lo <= s.ended < hi]

    def in_flight(self) -> list:
        """What the close of the window left unfinished."""
        return [s for s in self.sent if s.ended is None]

    def lag_ms(self) -> np.ndarray:
        """How late the generator got to each request that fell due inside
        the window."""
        return np.array([(s.submitted - s.due) * 1e3 for s in self.sent
                         if self.t_open <= s.due < self.t_close])

    def window_samples(self, key: str) -> np.ndarray:
        return np.array([o[key] for t, o in self.samples
                         if self.t_open <= t < self.t_close and key in o],
                        float)
