"""What is left of attention tier selection by measurement, and the tier ids.

A dense call's tier is a rule on the call and the backend
(``ops.attention._tier``); this module gives it its telemetry:
``TIER_IDS``, ``gauge_key`` and ``publish_tier`` write
``gauge/attn/tier.<key>``, the tier id in effect for a shape, published by
every dispatch (``ops.linear_attention`` publishes ``gauge/kda/tier.*``
from the same ids). ``'pallas': 2`` stays for that gauge; no dense tier
has the name since PR 32, when the race that timed dense tiers on
whatever machine ran first was taken out (the jax-shipped Pallas kernel it
sometimes drew read 87.43 ms of GPT-2 345M's step against 24.67 for the
XLA tier; ledger, PR 29's GPT row).

The decode path's pair (``ops.attention.paged_attention``:
``paged_gather`` / ``paged_scan``) is still chosen by measurement, the
last such choice in ``ops/`` (ROADMAP D2b: to be settled by rule with the
first serve cell):

- **One micro-bench per decode shape** (``make_paged_key``): the first
  trace that dispatches an unseen shape times both tiers, forward only,
  AOT-compiled (``jit -> lower -> compile``; the executable call path is
  immune to the ambient trace the selection usually runs under), and the
  fastest wins. ``counter/attn/tier_bench`` counts benches run. On TPU a
  tier the compiler refuses is an ERROR carrying the compiler's message
  (``TierCompileError``), not a tier to drop.
- **Persistent verdicts**: results land in a JSON file
  (``PADDLE_TPU_ATTN_TIER_CACHE``, defaulting to ``attn_tiers.json``
  inside the persistent XLA compile cache directory in effect —
  ``device.configure_compilation_cache``), committed via
  ``framework.io.atomic_replace``, so a process restart re-selects
  without re-measuring. A corrupted file is NEVER deleted or overwritten:
  the policy re-measures in memory, warns once, and leaves the bytes on
  disk for inspection. No dense call reads this file.
- **Override**: ``PADDLE_TPU_ATTN_PAGED_POLICY`` forces a tier
  (``paged_gather``/``paged_scan``), pins the threshold heuristic
  (``heuristic``), or forces measurement (``bench``). Unset, it measures
  on TPU and keeps the heuristic off it (CPU timings would enshrine host
  quirks into the file).
"""
from __future__ import annotations

import json
import logging
import os
import threading
import time
from typing import Dict, Optional

logger = logging.getLogger("paddle_tpu.ops")

__all__ = [
    "TIER_IDS", "PAGED_TIERS", "policy_mode", "cache_path", "select_paged",
    "publish_tier", "registry", "TierRegistry", "TierCompileError", "reset",
]


class TierCompileError(RuntimeError):
    """A paged tier failed to compile or run on the TPU during the
    micro-bench. Carries the tier's name and the compiler's message; the
    fix is the tier, never a silent drop."""


# stable numeric ids for the gauge/attn/tier.* telemetry (schema: >= 0).
# paged_gather / paged_scan are the DECODE tiers (attention over the
# serving KV-cache pool — ops.attention.paged_attention); they join the
# same id space so one gauge family covers train and serve dispatch.
TIER_IDS = {"xla": 0, "flash_tpu": 1, "pallas": 2, "blockwise": 3, "ring": 4,
            "paged_gather": 5, "paged_scan": 6,
            # the two forms of latent attention over a paged latent cache
            # (ops.attention.mla_paged_attention): a rule on the call
            "mla_absorbed": 7, "mla_expanded": 8}

# decode-path tiers: both are always feasible (pure-XLA gather/scan), so
# selection is purely a measurement or heuristic question, never a gate
PAGED_TIERS = ("paged_gather", "paged_scan")

# repetitions of a timed tier; the fastest counts
_BENCH_REPS = 2

_warned_unknown_policy = None  # one-shot per distinct bad env value


def cache_path() -> Optional[str]:
    """The paged verdicts' file, or None (memory-only):
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


def gauge_key(L: int, d: int, causal: bool) -> str:
    """Short per-shape suffix for ``gauge/attn/tier.<key>``."""
    return f"L{L}.d{d}.{'c' if causal else 'f'}"


def publish_tier(L: int, d: int, causal: bool, tier: str) -> None:
    """Record the tier in effect for a shape — every dispatch publishes,
    so bench records always carry it (``tools/check_attribution.py``
    gates on its presence)."""
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

        # merge-on-write: re-read the file so verdicts another process
        # persisted since OUR load survive this atomic_replace (ours win
        # on key collision — we just measured)
        try:
            with open(path) as f:
                data = json.load(f)
            if isinstance(data, dict):
                for k, v in data.items():
                    if isinstance(v, dict) and v.get("tier") in TIER_IDS:
                        self._verdicts.setdefault(k, v)
        except Exception:
            pass  # absent, or corrupted since load: poisoning is _load's call
        payload = json.dumps(self._verdicts, indent=1, sort_keys=True)

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

    def record(self, key: str, verdict: dict) -> None:
        with self._lock:
            self._verdicts[key] = verdict
            path = cache_path()
            if path:
                self._load(path)   # no-op unless the cache path changed
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


def _tier_failed(tier: str, what: str, e: Exception) -> None:
    """A tier failed to compile or run in the micro-bench. On TPU that is
    an error (see ``TierCompileError``); elsewhere the tier is dropped
    from this verdict."""
    import jax

    if jax.default_backend() == "tpu":
        raise TierCompileError(
            f"attention tier {tier!r} passed its shape gate for {what} and "
            f"then failed on the TPU — {type(e).__name__}: {e}") from e
    logger.info("tier_policy: tier %r infeasible for %s (%s: %s)",
                tier, what, type(e).__name__, e)


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

def policy_mode() -> str:
    """'bench' | 'heuristic' | a forced paged tier: the mode that governs
    the file at ``cache_path()``.

    ``PADDLE_TPU_ATTN_PAGED_POLICY`` wins (``paged_gather`` /
    ``paged_scan`` / ``bench`` / ``heuristic``); unset measures on TPU and
    keeps the heuristic off it (host timings never poison the verdict
    file). Read per call so tests can flip it without reloads."""
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
                   quantized: bool, hkv: Optional[int] = None) -> str:
    """Decode-shape verdict key: query chunk length, heads, head_dim,
    table width x block size (the gathered-context geometry), storage
    dtype. Batch is deliberately absent: both tiers scale ~linearly in
    B, so the ranking is batch-invariant and one verdict (timed at batch
    1) covers every decode bucket."""
    q = "int8" if quantized else str(dtype)
    heads = f"h{h}" if hkv in (None, h) else f"h{h}kv{hkv}"  # grouped queries
    return f"{_backend_key()}:paged:t{t}:{heads}:d{d}:m{m}x{bs}:{q}"


def _paged_heuristic(m: int, bs: int) -> str:
    # materialized gather is profitable while the gathered context is
    # score-tensor-small; past that the page-streaming scan bounds live
    # memory (the 4096 knee of the dense rule's non-causal xla/blockwise
    # split, ops.attention._XLA_MAX_SEQ)
    return "paged_gather" if m * bs <= 4096 else "paged_scan"


def bench_paged(key: str, t: int, h: int, d: int, m: int, bs: int, dtype,
                quantized: bool, hkv: Optional[int] = None) -> Optional[dict]:
    """Time both paged tiers at [1, t, h, d] queries over an [m*bs]-token
    paged context of ``hkv`` key heads (``h`` where not given) and record
    the winner — forward only (decode is inference; there is no backward
    to weigh in)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..profiler.telemetry import get_telemetry
    from . import attention as att

    rng = np.random.RandomState(0)
    with jax.ensure_compile_time_eval():
        q = jnp.asarray(rng.randn(1, t, h, d).astype(np.float32), dtype)
        h = hkv or h  # the pages' heads from here on
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
    _registry.record(key, verdict)
    get_telemetry().counter("attn/tier_bench")
    logger.info("tier_policy: %s -> %s (%s)", key, best,
                ", ".join(f"{k2}={ms:.2f}ms"
                          for k2, ms in verdict["timings_ms"].items()))
    return verdict


def select_paged(t: int, h: int, d: int, m: int, bs: int, dtype,
                 quantized: bool, hkv: Optional[int] = None) -> str:
    """The paged tier for this decode shape. Forced > cached verdict >
    fresh micro-bench (bench mode) > heuristic. A pure cache hit is one
    dict lookup at trace time — the verdict bakes into the compiled
    decode step."""
    mode = policy_mode()
    if mode in PAGED_TIERS:
        return mode
    if mode == "bench":
        key = make_paged_key(t, h, d, m, bs, dtype, quantized, hkv)
        verdict = _registry.verdict(key)
        if verdict is None or verdict.get("tier") not in PAGED_TIERS:
            verdict = bench_paged(key, t, h, d, m, bs, dtype, quantized,
                                  hkv)
        if verdict is not None:
            return verdict["tier"]
    return _paged_heuristic(m, bs)
