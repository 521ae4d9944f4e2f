"""Retrace/compile tracking for jitted entry points.

Silent XLA retraces are the classic JAX production regression: a feed
whose shape drifts batch-to-batch recompiles the step program every
iteration and throughput falls off a cliff with no error anywhere.
``tracked_jit`` wraps ``jax.jit`` so every compilation is *counted*
(``counter compile/<name>``), *timed* (``hist compile_ms/<name>`` — the
wall time of the triggering call, which is dominated by trace+compile),
and *warned about* through a rate-limited logger once a function has
compiled more than ``PADDLE_TPU_RETRACE_WARN`` times (default 3; ``0``
disables the warning).

Compilations are detected by the abstract signature of the call — the
(shape, dtype, weak_type) of every array leaf, the type of Python-scalar
leaves, and the pytree structure — the dominant drivers of jax.jit's
tracing cache. This is deliberately independent of private jax cache
APIs so counts are deterministic and testable; exotic cache keys the
signature cannot see (e.g. sharding-driven recompiles under some
configs) may undercount, never overcount.
"""
from __future__ import annotations

import logging
import os
import time
import weakref
from typing import Optional

import jax

from .telemetry import get_telemetry

__all__ = ["tracked_jit", "RetraceTracker", "retrace_warn_threshold",
           "reset_trackers"]

logger = logging.getLogger("paddle_tpu.profiler")

_WARN_EVERY_S = 30.0  # at most one retrace warning per function per 30 s

# every live tracker, so Telemetry.reset() can clear per-function compile
# state: without this, back-to-back tests/benches in one process inherit
# retrace counts (compile/<name> counters reset but tracker.compiles did
# not, so the next retrace-warning threshold fired early and gates read
# stale per-function totals)
_trackers: "weakref.WeakSet[RetraceTracker]" = weakref.WeakSet()


def reset_trackers() -> None:
    """Zero every tracker's compile count and forget seen signatures.
    Hooked from ``Telemetry.reset()``. A signature seen before the reset
    counts as a fresh compile after it — jax's own cache may satisfy it
    instantly, but the accounting starts from zero, which is what test
    isolation needs."""
    for t in list(_trackers):
        t.reset()


def retrace_warn_threshold() -> int:
    try:
        return int(os.environ.get("PADDLE_TPU_RETRACE_WARN", "3"))
    except ValueError:
        return 3


def _leaf_signature(x):
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        # weak_type participates in jit's cache key: a weak f32 scalar and
        # a strong one of the same shape/dtype trace separately
        return (tuple(x.shape), str(x.dtype),
                bool(getattr(x, "weak_type", False)))
    if isinstance(x, (bool, int, float, complex)):
        # jax traces Python scalars as weak-typed 0-d DYNAMIC values: a
        # new VALUE does not retrace, only a new type does — keying on
        # the value would report a false compile every step for e.g. a
        # host-side lr float
        return ("pyscalar", type(x).__name__)
    return (type(x).__name__, repr(x))


class RetraceTracker:
    """Per-function compile bookkeeping shared by every tracked_jit
    wrapper with the same ``name`` (cross-instance counts aggregate in
    telemetry; signatures are tracked per tracker)."""

    def __init__(self, name: str):
        self.name = name
        self._signatures = set()
        self.compiles = 0
        self._last_warn = 0.0
        _trackers.add(self)

    def reset(self) -> None:
        self._signatures.clear()
        self.compiles = 0
        self._last_warn = 0.0

    def signature_of(self, args, kwargs):
        """Hash digest of the call's abstract signature. Only the digest
        is kept: storing the full per-call signature tuple (thousands of
        leaves for a large model's params/opt-state) would leak one big
        tuple per retrace — exactly in the drifting-shape pathology this
        tracker exists to catch."""
        leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
        return hash((treedef, tuple(_leaf_signature(l) for l in leaves)))

    def seen(self, sig) -> bool:
        return sig in self._signatures

    def commit(self, sig) -> None:
        """Register a signature whose compile COMPLETED. Called after the
        jitted call returns — a call that raises mid-compile (OOM, TPU
        compile-service rejection) must not mark its signature compiled,
        or the retry would count as a cache hit and its compile time
        would pollute the dispatch histograms."""
        self._signatures.add(sig)
        self.compiles += 1
        tel = get_telemetry()
        tel.counter(f"compile/{self.name}")
        threshold = retrace_warn_threshold()
        if threshold and self.compiles > threshold:
            now = time.monotonic()
            if now - self._last_warn >= _WARN_EVERY_S:
                self._last_warn = now
                logger.warning(
                    "jitted function %r compiled %d times (threshold %d) — "
                    "an input shape/dtype is drifting call-to-call and every "
                    "drift pays a full XLA retrace+compile; pad or bucket "
                    "the offending input [tpu-lint R3: tools/tpu_lint.py "
                    "flags this hazard statically] (warning rate-limited "
                    "to one per %.0f s)", self.name, self.compiles, threshold,
                    _WARN_EVERY_S)


def tracked_jit(fn=None, *, name: Optional[str] = None,
                sig_argnums: Optional[tuple] = None, **jit_kwargs):
    """``jax.jit`` with compile telemetry. Drop-in: accepts every jit
    kwarg (donate_argnums, out_shardings, static_argnums, ...) and works
    bare or as a decorator factory::

        step = tracked_jit(step_fn, name="fleet.train_step",
                           donate_argnums=(0, 2))

    ``sig_argnums`` limits signature hashing to those positional args
    (an index tuple, or a ``slice`` for "everything from position k on")
    — the engines pass only the drift-capable inputs (batch, lr), since
    flattening a large model's params/opt-state pytree every call would
    put O(n_leaves) host work on the dispatch hot path. Signatures of
    the excluded args are assumed stable after construction (true for
    engine-owned state); a drift there undercounts, never overcounts.

    The wrapper exposes ``.tracker`` (compile count / signatures) and
    ``.jitted`` (the underlying jax.jit object, for ``.lower`` etc.).
    """
    if fn is None:
        return lambda f: tracked_jit(f, name=name, sig_argnums=sig_argnums,
                                     **jit_kwargs)

    label = name or getattr(fn, "__name__", "jit_fn")
    jitted = jax.jit(fn, **jit_kwargs)
    tracker = RetraceTracker(label)
    tel = get_telemetry()

    def wrapper(*args, **kwargs):
        if not tel.enabled:  # telemetry off ⇒ zero hot-path overhead
            return jitted(*args, **kwargs)
        if sig_argnums is None:
            sig_args = args
        elif isinstance(sig_argnums, slice):
            sig_args = args[sig_argnums]
        else:
            sig_args = tuple(args[i] for i in sig_argnums if i < len(args))
        sig = tracker.signature_of(sig_args, kwargs)
        if tracker.seen(sig):
            return jitted(*args, **kwargs)
        t0 = time.perf_counter()
        # goodput: the same region compile_ms times — an unseen
        # signature's triggering call is (re)trace + XLA compile, badput
        # the wall-clock ledger must own (nested under the step's claim)
        from . import goodput, spans

        # the span puts ``pt.compile`` on a profiler trace: a device gap
        # under a retrace reads as one
        with goodput.activity("compile"), \
                spans.span("compile", cat="compile"):
            out = jitted(*args, **kwargs)  # raises ⇒ signature NOT committed
        tracker.commit(sig)
        # the triggering call's wall time ≈ trace+compile (+1 run):
        # the honest host-visible cost of the retrace
        tel.observe(f"compile_ms/{label}",
                    (time.perf_counter() - t0) * 1e3)
        # attribution: cost-analyze the executable this compile produced
        # (flops/HBM -> MFU). After the call on purpose: lower() reads
        # only avals, so donated (deleted) buffers are safe, and a failed
        # compile never reaches here.
        from . import xla_cost

        xla_cost.capture(label, jitted, args, kwargs)
        return out

    wrapper.__name__ = f"tracked_{label}"
    wrapper.tracker = tracker
    wrapper.jitted = jitted
    return wrapper
