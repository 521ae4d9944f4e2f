"""Benchmarked attention tier selection — measurement over heuristics.

``ops/attention.py`` carries four interchangeable tiers (the materialized
``xla`` path, the repo's ``flash_tpu`` Pallas kernel, the jax-shipped
``pallas`` kernel, the streaming ``blockwise`` recurrence) whose relative
speed depends on shape, dtype and toolchain — a hardcoded threshold is
wrong somewhere for someone. This module makes ``impl='auto'`` consult a
*measured* verdict instead:

- **One micro-bench per (backend, device_kind, heads, L, d, dtype,
  causal)**: the first trace that dispatches an unseen attention shape
  times every feasible tier — forward+backward, AOT-compiled
  (``jit -> lower -> compile``; the executable call path is immune to
  the ambient trace the selection usually runs under) — and the fastest
  wins. ``counter/attn/tier_bench`` counts benches run. On TPU a
  candidate that passed its shape gate and is then refused by the
  compiler is an ERROR carrying the compiler's message
  (``TierCompileError``): feasibility is the gate's decision, and a gate
  that offers what the chip rejects is a bug to fix, not a tier to drop.
- **Persistent verdicts**: results land in a JSON cache file
  (``PADDLE_TPU_ATTN_TIER_CACHE``, defaulting to ``attn_tiers.json``
  inside the persistent XLA compile cache directory in effect —
  ``device.configure_compilation_cache``), committed
  via ``framework.io.atomic_replace``, so a process restart re-selects
  without re-measuring — the same restart-warm contract as the compile
  cache whose key scheme (backend + device_kind + abstract shape) this
  mirrors. A corrupted cache file is NEVER deleted or overwritten: the
  policy re-measures in memory, warns once, and leaves the bytes on disk
  for inspection.
- **Override**: ``PADDLE_TPU_ATTN_POLICY`` forces a tier
  (``xla``/``flash_tpu``/``pallas``/``blockwise``/``ring``), pins the old
  threshold heuristic (``heuristic``), or forces measurement (``bench``).
  Unset, 'auto' measures on TPU and keeps the heuristic off-TPU (CPU
  timings would enshrine host quirks into the cache; CI opts in
  explicitly).

Telemetry (all trace-time facts — one event per compiled program, not
per step): ``gauge/attn/tier.<key>`` (the tier id in effect for a shape,
published by every dispatch in every mode), ``counter/attn/calls``,
``counter/attn/tier_bench`` (micro-benches run),
``counter/attn/tier_fallbacks`` (silent reroutes — gated to zero by
``tools/check_attribution.py``).
"""
from __future__ import annotations

import json
import logging
import os
import threading
import time
from typing import Dict, List, Optional

logger = logging.getLogger("paddle_tpu.ops")

__all__ = [
    "TIER_IDS", "PAGED_TIERS", "policy_mode", "forced_mode", "cache_path",
    "select", "select_paged", "publish_tier", "registry", "TierRegistry",
    "TierCompileError", "reset",
]


class TierCompileError(RuntimeError):
    """A tier that passed its shape gate failed to compile or run on the
    TPU during the micro-bench. Carries the tier's name and the compiler's
    message; the fix is the gate (or the kernel), never a silent drop."""


# stable numeric ids for the gauge/attn/tier.* telemetry (schema: >= 0).
# paged_gather / paged_scan are the DECODE tiers (attention over the
# serving KV-cache pool — ops.attention.paged_attention); they join the
# same id space so one gauge family covers train and serve dispatch.
TIER_IDS = {"xla": 0, "flash_tpu": 1, "pallas": 2, "blockwise": 3, "ring": 4,
            "paged_gather": 5, "paged_scan": 6}

_FORCIBLE = ("xla", "flash_tpu", "pallas", "blockwise", "ring")

# decode-path tiers: both are always feasible (pure-XLA gather/scan), so
# selection is purely a measurement or heuristic question, never a gate
PAGED_TIERS = ("paged_gather", "paged_scan")

# micro-bench shape: batch is pinned to 1 (every tier scales ~linearly in
# batch, so the ranking is batch-invariant and the bench stays cheap);
# heads/L/d/dtype come from the real call — they drive tiling feasibility
# and the compute/bandwidth balance the tiers differ on.
_BENCH_BATCH = 1
_BENCH_REPS = 2

_warned_unknown_policy = None  # one-shot per distinct bad env value


def forced_mode() -> Optional[str]:
    """The EXPLICIT ``PADDLE_TPU_ATTN_POLICY`` value when one is set and
    valid, else None. Distinct from ``policy_mode`` so overrides can
    outrank decisions (ring auto-promotion) that the unset default must
    not suppress."""
    v = os.environ.get("PADDLE_TPU_ATTN_POLICY", "").strip().lower()
    if v in _FORCIBLE or v in ("bench", "heuristic"):
        return v
    return None


def policy_mode() -> str:
    """'bench' | 'heuristic' | a forced tier name.

    ``PADDLE_TPU_ATTN_POLICY`` wins; unset defaults to measured selection
    on TPU and the threshold heuristic elsewhere (read per call so tests
    and bench configs can flip it without reloads)."""
    global _warned_unknown_policy
    forced = forced_mode()
    if forced is not None:
        return forced
    if os.environ.get("PADDLE_TPU_ATTN_POLICY", "").strip():
        if os.environ["PADDLE_TPU_ATTN_POLICY"] != _warned_unknown_policy:
            _warned_unknown_policy = os.environ["PADDLE_TPU_ATTN_POLICY"]
            logger.warning("tier_policy: unknown PADDLE_TPU_ATTN_POLICY=%r "
                           "— falling back to the heuristic (warned once "
                           "per value)",
                           os.environ["PADDLE_TPU_ATTN_POLICY"])
        return "heuristic"
    import jax

    return "bench" if jax.default_backend() == "tpu" else "heuristic"


def cache_path() -> Optional[str]:
    """Verdict cache file, or None (memory-only):
    ``PADDLE_TPU_ATTN_TIER_CACHE`` wins, else ``attn_tiers.json`` inside
    whichever XLA compile cache directory is in effect."""
    p = os.environ.get("PADDLE_TPU_ATTN_TIER_CACHE")
    if p:
        return p
    import jax

    d = jax.config.jax_compilation_cache_dir
    return os.path.join(d, "attn_tiers.json") if d else None


def _backend_key() -> str:
    import jax

    kind = "unknown"
    try:
        kind = str(jax.devices()[0].device_kind).replace(" ", "_")
    except Exception:
        pass
    return f"{jax.default_backend()}:{kind}"


def make_key(h: int, L: int, d: int, dtype, causal: bool) -> str:
    return (f"{_backend_key()}:h{h}:L{L}:d{d}:{dtype}:"
            f"{'causal' if causal else 'full'}")


def gauge_key(L: int, d: int, causal: bool) -> str:
    """Short per-shape suffix for ``gauge/attn/tier.<key>``."""
    return f"L{L}.d{d}.{'c' if causal else 'f'}"


def publish_tier(L: int, d: int, causal: bool, tier: str) -> None:
    """Record the tier in effect for a shape — every dispatch publishes,
    whatever mode chose it, so bench records always carry the verdict
    (``tools/check_attribution.py`` gates on its presence)."""
    from ..profiler.telemetry import get_telemetry

    tel = get_telemetry()
    tel.gauge(f"attn/tier.{gauge_key(L, d, causal)}",
              TIER_IDS.get(tier, -1))


class TierRegistry:
    """In-memory verdicts + the persistent JSON cache behind them."""

    def __init__(self):
        self._lock = threading.RLock()
        self._verdicts: Dict[str, dict] = {}
        self._loaded_path: Optional[str] = None
        self._poisoned = False   # cache file unreadable: never write to it

    # -- persistence -------------------------------------------------------
    def _load(self, path: str) -> None:
        if self._loaded_path == path:
            return
        self._loaded_path = path
        self._poisoned = False
        if not os.path.exists(path):
            return
        try:
            with open(path) as f:
                data = json.load(f)
            if not isinstance(data, dict):
                raise ValueError(f"expected a JSON object, got "
                                 f"{type(data).__name__}")
        except Exception as e:
            # a corrupt cache is left EXACTLY as found (it may be the only
            # evidence of what corrupted it); verdicts re-measure in
            # memory and nothing further is written to this path
            self._poisoned = True
            logger.warning(
                "tier_policy: attention tier cache %s is unreadable (%s) — "
                "re-measuring in memory; the file is left untouched, "
                "remove it to re-enable persistence", path, e)
            return
        for k, v in data.items():
            if isinstance(v, dict) and v.get("tier") in TIER_IDS:
                self._verdicts.setdefault(k, v)

    def _persist(self, path: str) -> None:
        if self._poisoned:
            return
        from ..framework.io import atomic_replace

        persistable = {k: v for k, v in self._verdicts.items()
                       if not v.get("volatile")}
        # merge-on-write: re-read the file so verdicts another process
        # persisted since OUR load survive this atomic_replace (ours win
        # on key collision — we just measured; except volatile keys,
        # where the disk's full-candidate-set verdict is the keeper)
        try:
            with open(path) as f:
                data = json.load(f)
            if isinstance(data, dict):
                for k, v in data.items():
                    if isinstance(v, dict) and v.get("tier") in TIER_IDS:
                        self._verdicts.setdefault(k, v)
                        persistable.setdefault(k, v)
        except Exception:
            pass  # absent, or corrupted since load: poisoning is _load's call
        payload = json.dumps(persistable, indent=1, sort_keys=True)

        def write(tmp):
            with open(tmp, "w") as f:
                f.write(payload)

        try:
            os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
            atomic_replace(path, write)
        except OSError as e:
            logger.warning("tier_policy: could not persist tier cache to "
                           "%s: %s", path, e)

    # -- selection ---------------------------------------------------------
    def verdict(self, key: str) -> Optional[dict]:
        with self._lock:
            path = cache_path()
            if path:
                self._load(path)
            return self._verdicts.get(key)

    def record(self, key: str, verdict: dict, persist: bool = True) -> None:
        """Store a verdict; ``persist=False`` keeps it process-local
        (marked volatile — never written to disk, even as a bystander of
        a later persist) so a measurement taken under an env-restricted
        candidate set cannot clobber the full-set verdict on disk."""
        with self._lock:
            if not persist:
                verdict = dict(verdict, volatile=True)
            self._verdicts[key] = verdict
            path = cache_path()
            if path:
                self._load(path)   # no-op unless the cache path changed
                if persist:
                    self._persist(path)

    def reset(self) -> None:
        with self._lock:
            self._verdicts.clear()
            self._loaded_path = None
            self._poisoned = False


_registry = TierRegistry()


def registry() -> TierRegistry:
    return _registry


def reset() -> None:
    """Forget every in-memory verdict (tests; the disk cache persists)."""
    _registry.reset()


# -- the micro-bench -------------------------------------------------------

def _tier_callable(tier: str, causal: bool):
    """A [b, h, L, d] -> [b, h, L, d] callable for one tier."""
    from . import attention as att

    if tier == "xla":
        return lambda q, k, v: att.xla_attention(q, k, v, causal=causal)
    if tier == "blockwise":
        return lambda q, k, v: att.blockwise_attention(q, k, v, causal=causal)
    if tier == "flash_tpu":
        from .flash_tpu import flash_attention_blhd

        def _ft(q, k, v):
            tr = lambda t: t.transpose(0, 2, 1, 3)
            return tr(flash_attention_blhd(tr(q), tr(k), tr(v), causal))

        return _ft
    if tier == "pallas":
        return lambda q, k, v: att.jax_flash_attention(q, k, v, causal=causal)
    raise ValueError(f"unknown tier {tier!r}")


def _tier_failed(tier: str, what: str, e: Exception) -> None:
    """A candidate failed to compile or run in the micro-bench. On TPU
    that is an error (see ``TierCompileError``); elsewhere the tier is
    dropped from this verdict, as the CPU backend cannot build the
    kernels at all."""
    import jax

    if jax.default_backend() == "tpu":
        raise TierCompileError(
            f"attention tier {tier!r} passed its shape gate for {what} and "
            f"then failed on the TPU — {type(e).__name__}: {e}") from e
    logger.info("tier_policy: tier %r infeasible for %s (%s: %s)",
                tier, what, type(e).__name__, e)


def _time_tier(tier: str, q, k, v, causal: bool) -> Optional[float]:
    """Fastest-rep seconds of one fwd+bwd step; None when the tier fails
    to compile/run off-TPU (on TPU that raises — ``_tier_failed``).

    The step is AOT-compiled (``jit -> lower -> compile``) and the
    EXECUTABLE is what the clock times: a selection usually triggered
    mid-trace of the train step must neither be lifted into the ambient
    trace nor degrade into eager op-by-op dispatch — the compiled
    executable's call path is immune to both."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    fn = _tier_callable(tier, causal)

    def loss(q_, k_, v_):
        return fn(q_, k_, v_).astype(jnp.float32).sum()

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    try:
        compiled = step.lower(q, k, v).compile()
        out = compiled(q, k, v)
        np.asarray(out[0])  # drain the device queue before the clock
        times = []
        for _ in range(_BENCH_REPS):
            t0 = time.perf_counter()
            out = compiled(q, k, v)
            np.asarray(out[0])
            times.append(time.perf_counter() - t0)
        # min, not mean/median: host noise (GC, scheduler) only ever ADDS
        # time, and a verdict poisoned by one blip persists restart-warm
        # where no gate can catch it — the fastest rep is the estimate
        # closest to the kernel's true cost
        return min(times)
    except Exception as e:
        _tier_failed(tier, f"q{tuple(q.shape)} {q.dtype} causal={causal}", e)
        return None


def bench(key: str, h: int, L: int, d: int, dtype, causal: bool,
          candidates: List[str], persist: bool = True) -> Optional[dict]:
    """Time ``candidates`` at [1, h, L, d] and record the winner.

    The first unseen shape is usually dispatched while TRACING the train
    step — ``jax.ensure_compile_time_eval()`` keeps the whole bench
    eagerly evaluated at trace time instead of being lifted into the
    ambient trace (where the timed steps would become tracers and the
    clock would measure nothing)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..profiler.telemetry import get_telemetry

    rng = np.random.RandomState(0)
    timings = {}
    with jax.ensure_compile_time_eval():
        # input CREATION only: jnp ops on host data must evaluate rather
        # than lift into the ambient trace; the timing itself runs AOT
        # executables, which need no escape hatch (and compile-time eval
        # would break scan transposes inside lower())
        mk = lambda: jnp.asarray(
            rng.randn(_BENCH_BATCH, h, L, d).astype(np.float32), dtype)
        q, k, v = mk(), mk(), mk()
    for tier in candidates:
        t = _time_tier(tier, q, k, v, causal)
        if t is not None:
            timings[tier] = t
    if not timings:
        return None
    best = min(timings, key=timings.get)
    verdict = {
        "tier": best,
        "candidates": list(candidates),
        "timings_ms": {t: round(s * 1e3, 3) for t, s in timings.items()},
        "ts": time.time(),
    }
    _registry.record(key, verdict, persist=persist)
    get_telemetry().counter("attn/tier_bench")
    logger.info("tier_policy: %s -> %s (%s)", key, best,
                ", ".join(f"{t}={ms:.2f}ms"
                          for t, ms in verdict["timings_ms"].items()))
    return verdict


def select(h: int, L: int, d: int, dtype, causal: bool,
           candidates: List[str]) -> Optional[str]:
    """The measured tier for this shape, benching once per key if needed.
    Returns None when no candidate is feasible (caller keeps its
    heuristic). Pure cache hits are one dict lookup — selection happens
    at trace time and must never add per-step work (the verdict is baked
    into the compiled program; retrace budget unchanged)."""
    if not candidates:
        return None
    key = make_key(h, L, d, dtype, causal)
    verdict = _registry.verdict(key)
    if verdict is None:
        verdict = bench(key, h, L, d, dtype, causal, candidates)
    elif verdict.get("tier") not in candidates:
        # the cached winner is not among THIS call's candidates (a gate
        # changed since the verdict was written, or a caller restricted
        # the set). Re-measure for this process but never overwrite the
        # full-set verdict on disk.
        verdict = bench(key, h, L, d, dtype, causal, candidates,
                        persist=False)
    if verdict is None:
        return None
    return verdict["tier"]


# -- paged (decode) tier selection -----------------------------------------
# The KV-cache decode path has its own pair of tiers
# (ops.attention.paged_attention): 'paged_gather' materializes the whole
# gathered context per step (one big fused softmax — wins while the
# context fits comfortably), 'paged_scan' streams page-by-page with
# online softmax (O(block) live memory — wins for long contexts and is
# the only safe choice near HBM capacity). Their crossover depends on
# chip and shape exactly like the training tiers, so the same machinery
# applies: measure once per shape key, persist the verdict, zero
# per-step cost (selection happens at trace time of the decode step).

def paged_policy_mode() -> str:
    """'bench' | 'heuristic' | a forced paged tier.

    ``PADDLE_TPU_ATTN_PAGED_POLICY`` wins (``paged_gather`` /
    ``paged_scan`` / ``bench`` / ``heuristic``); unset follows the same
    default as the training tiers — measure on TPU, heuristic off-TPU
    (host timings never poison the shared verdict cache)."""
    v = os.environ.get("PADDLE_TPU_ATTN_PAGED_POLICY", "").strip().lower()
    if v in PAGED_TIERS or v in ("bench", "heuristic"):
        return v
    if v:
        global _warned_unknown_policy
        if v != _warned_unknown_policy:
            _warned_unknown_policy = v
            logger.warning("tier_policy: unknown "
                           "PADDLE_TPU_ATTN_PAGED_POLICY=%r — falling back "
                           "to the heuristic (warned once per value)", v)
        return "heuristic"
    import jax

    return "bench" if jax.default_backend() == "tpu" else "heuristic"


def make_paged_key(t: int, h: int, d: int, m: int, bs: int, dtype,
                   quantized: bool) -> str:
    """Decode-shape verdict key: query chunk length, heads, head_dim,
    table width x block size (the gathered-context geometry), storage
    dtype. Batch is deliberately absent — like the training bench's
    pinned batch, both tiers scale ~linearly in B, so the ranking is
    batch-invariant and one verdict covers every decode bucket."""
    q = "int8" if quantized else str(dtype)
    return f"{_backend_key()}:paged:t{t}:h{h}:d{d}:m{m}x{bs}:{q}"


def _paged_heuristic(m: int, bs: int) -> str:
    # materialized gather is profitable while the gathered context is
    # score-tensor-small; past that the page-streaming scan bounds live
    # memory (same 4096 knee the xla/blockwise training split uses)
    return "paged_gather" if m * bs <= 4096 else "paged_scan"


def bench_paged(key: str, t: int, h: int, d: int, m: int, bs: int, dtype,
                quantized: bool, persist: bool = True) -> Optional[dict]:
    """Time both paged tiers at [1, t, h, d] queries over an [m*bs]-token
    paged context and record the winner — forward only (decode is
    inference; there is no backward to weigh in)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..profiler.telemetry import get_telemetry
    from . import attention as att

    rng = np.random.RandomState(0)
    with jax.ensure_compile_time_eval():
        q = jnp.asarray(rng.randn(1, t, h, d).astype(np.float32), dtype)
        if quantized:
            k_pages = jnp.asarray(
                rng.randint(-127, 127, (m + 1, bs, h, d)), jnp.int8)
            v_pages = jnp.asarray(
                rng.randint(-127, 127, (m + 1, bs, h, d)), jnp.int8)
            k_scale = jnp.asarray(
                rng.rand(m + 1, bs, h).astype(np.float32)) * 0.01
            v_scale = jnp.asarray(
                rng.rand(m + 1, bs, h).astype(np.float32)) * 0.01
        else:
            k_pages = jnp.asarray(
                rng.randn(m + 1, bs, h, d).astype(np.float32), dtype)
            v_pages = jnp.asarray(
                rng.randn(m + 1, bs, h, d).astype(np.float32), dtype)
            k_scale = v_scale = None
        tables = jnp.asarray(np.arange(1, m + 1, dtype=np.int32)[None, :])
        q_pos = jnp.asarray(
            np.arange(m * bs - t, m * bs, dtype=np.int32)[None, :])
        kv_lens = jnp.asarray(np.asarray([m * bs], np.int32))
    timings = {}
    for tier in PAGED_TIERS:
        impl = (att._paged_gather_impl if tier == "paged_gather"
                else att._paged_scan_impl)

        def fn(q_, kp, vp, bt, qp, kl, ks=k_scale, vs=v_scale, impl=impl):
            return impl(q_, kp, vp, bt, qp, kl, ks, vs)

        try:
            compiled = jax.jit(fn).lower(
                q, k_pages, v_pages, tables, q_pos, kv_lens).compile()
            out = compiled(q, k_pages, v_pages, tables, q_pos, kv_lens)
            np.asarray(out)  # drain before the clock
            times = []
            for _ in range(_BENCH_REPS):
                t0 = time.perf_counter()
                out = compiled(q, k_pages, v_pages, tables, q_pos, kv_lens)
                np.asarray(out)
                times.append(time.perf_counter() - t0)
            timings[tier] = min(times)  # min: host noise only adds time
        except Exception as e:
            _tier_failed(tier, key, e)
    if not timings:
        return None
    best = min(timings, key=timings.get)
    verdict = {"tier": best,
               "candidates": list(PAGED_TIERS),
               "timings_ms": {k2: round(s * 1e3, 3)
                              for k2, s in timings.items()},
               "ts": time.time()}
    _registry.record(key, verdict, persist=persist)
    get_telemetry().counter("attn/tier_bench")
    logger.info("tier_policy: %s -> %s (%s)", key, best,
                ", ".join(f"{k2}={ms:.2f}ms"
                          for k2, ms in verdict["timings_ms"].items()))
    return verdict


def select_paged(t: int, h: int, d: int, m: int, bs: int, dtype,
                 quantized: bool) -> str:
    """The paged tier for this decode shape. Forced > cached verdict >
    fresh micro-bench (bench mode) > heuristic. Like ``select``, a pure
    cache hit is one dict lookup at trace time — the verdict bakes into
    the compiled decode step."""
    mode = paged_policy_mode()
    if mode in PAGED_TIERS:
        return mode
    if mode == "bench":
        key = make_paged_key(t, h, d, m, bs, dtype, quantized)
        verdict = _registry.verdict(key)
        if verdict is None or verdict.get("tier") not in PAGED_TIERS:
            verdict = bench_paged(key, t, h, d, m, bs, dtype, quantized)
        if verdict is not None:
            return verdict["tier"]
    return _paged_heuristic(m, bs)
