"""Token-level LLM serving: decode-step continuous batching over a paged
KV cache, chunked-prefill admission, and speculative decoding.

PR 7's runtime batches ONE-SHOT predictor calls — the dominant real
traffic shape (long prompt + streamed decode) would recompute its whole
prefix every token. This module serves generation natively:

- **Continuous batching at token granularity**: every scheduler
  iteration advances ALL running sequences by one decode step (packed
  into the smallest decode bucket — one compiled executable per bucket,
  same bounded-compile scheme as the PR 7 scheduler) and at most ONE
  prefill chunk, so a newly admitted 10k-token prompt costs running
  decodes at most one chunk of latency, never a full prefill stall.
- **One decode step in flight**: an iteration dispatches its prefill chunk
  and its decode step before it fetches the tokens of the iteration
  before, so the host's books, the next feed and its dispatch run while
  the chip works (as the train loop keeps one step in flight). A step
  takes a row's input token from a small device store that every decode
  step and prefill chunk writes (the last token it emitted for a
  sequence, keyed by the sequence's first page), so a token the host has
  not fetched yet makes no round trip. The host stays the record, one
  iteration late: a row whose budget the dispatched steps reach is not
  fed again, a row may run one step past its ``eos_id``, and a row
  evicted or ended while its step is in flight drops that step's token.
  A speculative round needs the acceptance on the host before its next
  input, so it fetches whatever is in flight first.
- **Paged KV cache** (``kv_cache.KVCachePool``): per-sequence block
  tables over a fixed pool; blocks allocate as sequences grow and free
  at EVERY terminal transition (the engine's ``_finish`` funnel owns the
  release, so no status path can leak). Pool pressure evicts the
  youngest running sequence back to re-prefill (recompute-style
  preemption, counted in ``serve/kv_evictions``).
- **Speculative decoding**: a draft model proposes ``spec_k`` greedy
  tokens (k cheap sequential steps), the target verifies all of them in
  ONE batched (k+1)-token step; the accepted prefix plus the target's
  correction advance the sequence 1..k+1 tokens per round.
  ``gauge/serve/spec_accept_rate`` tracks the cumulative acceptance.
  A model whose ``decode_spec()`` offers a draft of its own (a next-token
  prediction module fed by the target's hidden state) needs no draft
  model: ``spec_k`` alone turns it on (``_self_spec_round``), its latent
  rows lie in the target's pool under the target's blocks.
- **PR 7 lifecycle unchanged**: admission queue, deadline enforcement
  (queue / mid-generation), drain semantics, the exactly-one-terminal
  accounting ledger, and the SIGTERM → drain → exit-77 relaunch path are
  inherited verbatim from ``ServingEngine`` — a preempted replica
  terminates every request exactly once (OK with full text, DRAINED with
  partial text) and releases every KV block.

Telemetry (schema-gated): counters ``serve/kv_blocks_{alloc,free}``,
``serve/decode_steps``, ``serve/prefill_chunks``, ``serve/kv_evictions``,
``serve/tokens_generated``, ``serve/spec_{proposed,accepted}``,
``serve/state_resets`` (chunks that started a recurrent state again),
``serve/steps_overlapped`` (decode steps dispatched while the decode step
before them was still unfetched), ``serve/pipeline_drains`` (fetches that
left no decode step in flight: a speculative round, an empty running set,
the drain); gauges
``serve/kv_occupancy`` ∈ [0,1], ``serve/kv_blocks_{total,used}``,
``serve/state_occupancy`` ∈ [0,1], ``serve/state_slots_{total,used}``,
``serve/spec_accept_rate`` ∈ [0,1], ``serve/running``; histograms
``serve/ttft_ms``, ``serve/tpot_ms``, ``serve/decode_ms[.b<N>]``,
``serve/prefill_ms[.c<N>]``, ``serve/verify_ms[.b<N>]``,
``serve/draft_ms``. Each compiled entry (``serve.decode.b<N>``,
``serve.prefill.c<N>``, ``serve.verify.b<N>``, ``serve.draft.b<N>``) is
cost-analyzed by the PR 5 attribution layer and mapped to its own
histogram, so decode-step MFU is a first-class column.

Spans (``profiler.spans.Span``: ``pt.serve.*`` in a device trace, and the
flight recorder): ``serve.iter`` around every iteration with work in
flight, its step the scheduler's ``batch_index``; inside it one phase open
innermost at every moment (``serve.admit``, ``blocks``, ``arrays``,
``dispatch``, ``fetch``, ``tokens``, ``retire``), the passes that run a
compiled entry in round spans that hold phases only (``serve.prefill_chunk``,
``decode_round``, ``verify_round``, ``draft_round``). So an idle stretch of
the device is booked to the host phase that held it
(``hlo_attrib.idle_by_span``). All host code, outside every compiled entry.
"""
from __future__ import annotations

import threading
import time
import traceback
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ...ops.attention import table_slots_live
from ...profiler import device_profile as _device_profile
from ...profiler.retrace import tracked_jit
from ...profiler.spans import Span
from ...profiler.telemetry import get_telemetry
from ...resilience.inject import active_injector
from ...resilience.preemption import preemption_requested
from ...resilience.watchdog import heartbeat
from .engine import ServeConfig, ServingEngine
from .kv_cache import KVCacheConfig, KVCachePool
from .request import Request, RequestStatus

__all__ = ["TokenServeConfig", "GenRequest", "TokenServingEngine",
           "DecodeScheduler", "dense_greedy_reference",
           "paged_prefill_logits"]

# a feed token that the step takes from the device's token store: the one
# the sequence's last step emitted, which the host has not fetched yet
ON_DEVICE = -1


class TokenServeConfig(ServeConfig):
    """Knobs of the token-level runtime. The PR 7 knobs (admission
    ``capacity``, ``default_deadline_s``, ``drain_grace_s``,
    ``idle_poll_s``) plus bucket handling are INHERITED from
    ``ServeConfig`` — ``decode_buckets`` are its ``buckets`` and
    ``max_running`` its ``max_batch``, so bucket validation/selection
    cannot drift between the two engines.

    Args:
        decode_buckets: ascending batch sizes for the decode/verify
            steps; one executable per bucket (per T). ``max_running``
            (default: largest bucket) bounds concurrent sequences.
        prefill_chunk: tokens per prefill chunk — the admission quantum.
            Long prompts enter in chunks of this size, one chunk per
            scheduler iteration, so running decodes never stall longer
            than one chunk.
        max_new_tokens: default generation budget per request.
        kv_blocks / kv_block_size / kv_dtype: pool geometry + storage
            ('float32' | 'bfloat16' | 'int8' — int8 stores per-token-head
            scales via ``quant.quantize_kv``).
        max_seq_len: hard per-sequence cap (prompt + generation);
            defaults to the model's position table, clamped to what the
            pool can hold for one sequence.
        spec_k: speculative tokens proposed per round (0 = off; needs a
            draft model on the engine, or a model whose ``decode_spec()``
            offers a draft of its own).
    """

    def __init__(self, capacity: int = 64,
                 decode_buckets: Sequence[int] = (1, 2, 4, 8),
                 max_running: Optional[int] = None,
                 prefill_chunk: int = 32,
                 max_new_tokens: int = 64,
                 default_deadline_s: Optional[float] = None,
                 drain_grace_s: float = 5.0,
                 idle_poll_s: float = 0.01,
                 kv_blocks: int = 64,
                 kv_block_size: int = 16,
                 kv_dtype: str = "float32",
                 max_seq_len: Optional[int] = None,
                 spec_k: int = 0):
        super().__init__(capacity=capacity, buckets=decode_buckets,
                         max_batch=max_running,
                         default_deadline_s=default_deadline_s,
                         drain_grace_s=drain_grace_s,
                         idle_poll_s=idle_poll_s)
        self.prefill_chunk = int(prefill_chunk)
        self.max_new_tokens = int(max_new_tokens)
        self.kv_blocks = int(kv_blocks)
        self.kv_block_size = int(kv_block_size)
        self.kv_dtype = kv_dtype
        self.max_seq_len = max_seq_len
        self.spec_k = int(spec_k)

    @property
    def decode_buckets(self):
        return self.buckets

    @property
    def max_running(self) -> int:
        return self.max_batch


class GenRequest(Request):
    """One generation request. ``inputs`` holds the prompt (ledger/parity
    with the PR 7 request); the generation state lives on the request so
    the scheduler, the terminal funnel, and the accounting ledger all see
    one object.

    Timing stamps beyond the PR 7 pair: ``first_token_at`` (TTFT) and
    ``last_token_at`` — TPOT is derived at the terminal transition.
    """

    def __init__(self, req_id: int, prompt: np.ndarray,
                 max_new_tokens: int, deadline_s: Optional[float] = None,
                 eos_id: Optional[int] = None):
        super().__init__(req_id, [prompt], deadline_s)
        self.prompt = np.asarray(prompt, np.int32)
        self.max_new = int(max_new_tokens)
        self.eos_id = eos_id
        self.toks: List[int] = [int(t) for t in self.prompt]
        self.n_prompt = len(self.toks)
        self.generated: List[int] = []
        self.ncache = 0          # tokens whose K/V are in the target cache
        self.draft_ncache = 0    # ditto, draft cache (speculative mode)
        # tokens steps in flight have emitted and the host has not fetched
        self.unfetched = 0
        self.evictions = 0
        # the model's own draft of the token after the pending one
        # (self-drafting); None until a prefill has made one
        self.proposal: Optional[int] = None
        self.first_token_at: Optional[float] = None
        self.last_token_at: Optional[float] = None

    @property
    def pending(self) -> int:
        """Tokens known (on the host, or emitted by a step in flight) and
        not yet in cache — 1 means decode-eligible (exactly the next token
        to feed), >1 means (re)prefilling."""
        return len(self.toks) + self.unfetched - self.ncache

    def ttft_ms(self) -> Optional[float]:
        if self.first_token_at is None:
            return None
        return (self.first_token_at - self.submitted_at) * 1e3

    def tpot_ms(self) -> Optional[float]:
        if (self.first_token_at is None or self.last_token_at is None
                or len(self.generated) < 2):
            return None
        return ((self.last_token_at - self.first_token_at)
                / (len(self.generated) - 1)) * 1e3

    # -- observability (ops plane) ----------------------------------------
    def phase(self) -> str:
        """Token-level lifecycle phase: queued (nothing cached yet),
        prefill (known tokens still entering the cache), or decode."""
        if self.status != RequestStatus.PENDING:
            return self.status
        if self.ncache == 0 and not self.generated:
            return "queued"
        return "prefill" if self.pending > 1 else "decode"

    def debug_state(self, now=None) -> dict:
        out = super().debug_state(now)
        out.update({
            "prompt_tokens": self.n_prompt,
            "tokens_generated": len(self.generated),
            "max_new_tokens": self.max_new,
            "kv_cached_tokens": self.ncache,
            "evictions": self.evictions,
            "ttft_ms": self.ttft_ms(),
        })
        return out


class _Step:
    """A dispatched prefill chunk or decode step whose tokens the host has
    not fetched: ``name`` (``decode.b<N>``, ``prefill.c<C>``), ``rows``
    (each request with its evictions at the dispatch: a row evicted since
    drops its token), the device array ``tokens`` (a row's is column
    ``col``, and is the request's next where ``emits``: every decode
    step, a prefill chunk that ends its prompt), the dispatch's time
    ``t0`` and iteration ``batch``."""

    __slots__ = ("name", "rows", "tokens", "col", "emits", "t0", "batch")

    def __init__(self, name, rows, tokens, col, emits, t0, batch):
        self.name, self.rows, self.tokens = name, rows, tokens
        self.col, self.emits, self.t0, self.batch = col, emits, t0, batch


def greedy_step(fwd, takes_hidden: bool = False, store: bool = False):
    """What a compiled entry of the scheduler runs: forward a chunk through
    the cache and return the greedy token per position (argmax stays on
    device — the D2H per step is [B, T] int32, not [B, T, V] logits).
    ``slots`` [B] names each row's state slot (0, the scratch slot, for
    padded rows and for models without state). An entry of the model's own
    draft (``takes_hidden``) takes the target's hidden states, a device
    array that never visits the host, before the tokens; a forward that
    feeds such a draft returns them after the cache.

    An entry of the pipelined rounds (``store``: a decode step, the
    target's prefill chunk) also takes the token store last and returns it
    after the cache: a row fed ``ON_DEVICE`` reads its token from the store
    at its first page, and every row writes there the token of its last
    real position (padded rows: the scratch page)."""
    n = int(takes_hidden)

    def step(params, *args):
        # args: [hidden,] tokens, qpos, cache, tables, kv_lens, slots
        # [, store]; out: tokens, cache[, store][, hidden]
        if not store:
            logits, *rest = fwd(params, *args)
            return (jnp.argmax(logits, axis=-1).astype(jnp.int32), *rest)
        *args, tokens_of = args
        tokens, qpos, cache, tables, lens, slots = args[n:]
        key = tables[:, 0]
        tokens = jnp.where(tokens < 0, tokens_of[key][:, None], tokens)
        logits, cache, *rest = fwd(params, *args[:n], tokens, qpos, cache,
                                   tables, lens, slots)
        out = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if out.shape[1] == 1:
            # a decode step's one position is its last, read statically: an
            # index from kv_lens here kept two of Falcon-H1's gathered
            # contexts out of the v5e's fast memory (97 MB more
            # temporaries, 2.9 ms a step)
            emitted = out[:, 0]
        else:
            last = jnp.clip(lens - qpos[:, 0] - 1, 0, out.shape[1] - 1)
            emitted = jnp.take_along_axis(out, last[:, None], axis=1)[:, 0]
        return (out, cache, tokens_of.at[key].set(emitted), *rest)

    return step


class DecodeScheduler:
    """The decode loop — one thread owns the device and the pool.

    Each iteration: heartbeat → drain/preemption check → admission (pop
    waiting prompts into the running set while slots exist) → deadline
    shedding → ONE prefill chunk for the oldest prefilling sequence →
    ONE decode (or speculative) round for every decode-eligible
    sequence → the tokens of the steps the iteration before dispatched
    → retire finished sequences. Work per iteration is bounded
    (≤ 1 chunk + ≤ 1 decode round), which is what makes admission unable
    to starve decodes.
    """

    def __init__(self, engine: "TokenServingEngine"):
        self._engine = engine
        self._thread = threading.Thread(
            target=self._run, name="DecodeScheduler", daemon=True)
        self._stopped = threading.Event()
        self.batch_index = 0
        self._running: List[GenRequest] = []
        self._inflight: List[_Step] = []  # dispatched, not fetched, in order
        self._newest: Optional[_Step] = None  # the last decode step, unfetched
        self._fetched_at = 0.0  # when the last fetch returned
        # the token store: the last token a step emitted for the sequence
        # whose first page is the index (a page is one sequence's at a
        # time); numpy to the device, no program of its own
        self._tokens = jax.device_put(
            np.zeros((engine._pool.config.num_blocks,), np.int32))
        self._decode_fns: Dict[int, object] = {}
        self._verify_fns: Dict[int, object] = {}
        self._draft_fns: Dict[int, object] = {}
        self._prefill_fn = None
        self._draft_prefill_fn = None
        self._spec_proposed = 0
        self._spec_accepted = 0

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
    def _make_step(self, fwd, name: str, takes_hidden: bool = False,
                   store: bool = False):
        """One compiled entry of ``greedy_step(fwd, takes_hidden, store)``.
        The cache (arg 3: the pool's pages and, where the model carries
        recurrent state, its state leaves) is donated: the pool is the
        largest serving buffer and must never exist twice on device; so is
        the token store. The jitted function is named after the entry, so
        that a device trace tells a decode step from a prefill chunk by its
        module's name."""
        step = greedy_step(fwd, takes_hidden, store)
        step.__name__ = step.__qualname__ = name.replace(".", "_")
        # sig_argnums: hash only the drift-capable inputs (all but the
        # params, the cache and the store) — flattening the full params
        # pytree per decode step would put O(leaves) host work on the
        # token hot path
        n = int(takes_hidden)
        cache_at = 3 + n
        donate = (cache_at, 7 + n) if store else (cache_at,)
        return tracked_jit(
            step, name=name, donate_argnums=donate,
            sig_argnums=tuple(i for i in range(1, 7 + n) if i != cache_at))

    def _decode_fn(self, bucket: int):
        fn = self._decode_fns.get(bucket)
        if fn is None:
            fn = self._make_step(self._engine._fwd, f"serve.decode.b{bucket}",
                                 store=True)
            self._decode_fns[bucket] = fn
        return fn

    def _verify_fn(self, bucket: int):
        fn = self._verify_fns.get(bucket)
        if fn is None:
            eng = self._engine
            fn = self._make_step(
                eng._fwd_hidden if eng.self_draft else eng._fwd,
                f"serve.verify.b{bucket}")
            self._verify_fns[bucket] = fn
        return fn

    def _draft_fn(self, bucket: int):
        fn = self._draft_fns.get(bucket)
        if fn is None:
            fn = self._make_step(self._engine._draft_fwd,
                                 f"serve.draft.b{bucket}",
                                 takes_hidden=self._engine.self_draft)
            self._draft_fns[bucket] = fn
        return fn

    def _get_prefill_fn(self, draft: bool = False):
        eng = self._engine
        if draft:
            if self._draft_prefill_fn is None:
                self._draft_prefill_fn = self._make_step(
                    eng._draft_fwd,
                    f"serve.draft_prefill.c{eng.config.prefill_chunk}",
                    takes_hidden=eng.self_draft)
            return self._draft_prefill_fn
        if self._prefill_fn is None:
            self._prefill_fn = self._make_step(
                eng._fwd_hidden if eng.self_draft else eng._fwd,
                f"serve.prefill.c{eng.config.prefill_chunk}", store=True)
        return self._prefill_fn

    def warmup(self) -> Dict[str, float]:
        """Compile every entry with a zero batch (all writes land on the
        scratch page, all attention is masked) before the first request;
        with the persistent compile cache set, a relaunched replica
        replays these in milliseconds."""
        eng = self._engine
        cfg = eng.config
        out: Dict[str, float] = {}

        def run(label, fn, pool, B, T, fwd_params, hidden=None,
                store=False):
            toks = jnp.zeros((B, T), jnp.int32)
            qpos = jnp.zeros((B, T), jnp.int32)
            tables = jnp.zeros((B, eng._table_width), jnp.int32)
            lens = jnp.zeros((B,), jnp.int32)
            before = () if hidden is None else (hidden,)
            after = (self._tokens,) if store else ()
            t0 = time.perf_counter()
            got = fn(fwd_params, *before, toks, qpos, pool.pages, tables,
                     lens, jnp.zeros((B,), jnp.int32), *after)
            np.asarray(got[0])  # block: measure compile+run
            pool.pages = got[1]
            if store:
                self._tokens = got[2]
            out[label] = (time.perf_counter() - t0) * 1e3
            return got

        for b in cfg.decode_buckets:
            run(f"decode.b{b}", self._decode_fn(b), eng._pool, b, 1,
                eng._params, store=True)
        got = run(f"prefill.c{cfg.prefill_chunk}", self._get_prefill_fn(),
                  eng._pool, 1, cfg.prefill_chunk, eng._params, store=True)
        if eng.self_draft:
            # the draft's entries take the hidden states the target's give
            run(f"draft_prefill.c{cfg.prefill_chunk}",
                self._get_prefill_fn(draft=True), eng._pool, 1,
                cfg.prefill_chunk, eng._params, hidden=got[-1])
            for b in cfg.decode_buckets:
                got = run(f"verify.b{b}", self._verify_fn(b), eng._pool, b,
                          cfg.spec_k + 1, eng._params)
                run(f"draft.b{b}", self._draft_fn(b), eng._pool, b,
                    cfg.spec_k + 1, eng._params, hidden=got[-1])
        if eng.spec_enabled:
            for b in cfg.decode_buckets:
                run(f"verify.b{b}", self._verify_fn(b), eng._pool, b,
                    cfg.spec_k + 1, eng._params)
                run(f"draft.b{b}", self._draft_fn(b), eng._draft_pool, b, 1,
                    eng._draft_params)
            run(f"draft_prefill.c{cfg.prefill_chunk}",
                self._get_prefill_fn(draft=True), eng._draft_pool, 1,
                cfg.prefill_chunk, eng._draft_params)
        return out

    # -- the loop ----------------------------------------------------------
    def _run(self):
        eng = self._engine
        cfg = eng.config
        tel = get_telemetry()
        running = self._running
        drain_deadline = None
        try:
            while True:
                heartbeat()  # a hung decode step -> watchdog 113
                if preemption_requested() and not eng.draining:
                    eng._begin_drain(reason="preempted")
                if eng.draining:
                    if drain_deadline is None:
                        drain_deadline = (time.monotonic()
                                          + cfg.drain_grace_s)
                    # in-flight generation may keep decoding inside the
                    # grace window (short generations finish with full
                    # text); at expiry — or once nothing is running —
                    # the steps in flight are fetched, what they finished
                    # goes OK, everything left DRAINED with partial text,
                    # and every block returns to the pool
                    if (not running and not self._inflight) \
                            or time.monotonic() >= drain_deadline:
                        self._drain()
                        for r in running:
                            if self._done_generating(r):
                                self._retire(r, RequestStatus.OK)
                            else:
                                self._retire(r, RequestStatus.DRAINED,
                                             detail="drained mid-generation")
                        running.clear()
                        for r in eng._queue.pop_all():
                            eng._finish(r, RequestStatus.DRAINED,
                                        detail="drained before prefill")
                        return
                if not running and not self._inflight:
                    # nothing in flight: the wait for a first prompt is
                    # no iteration's, and opens no span
                    self._admit(cfg.idle_poll_s)
                    if not running:
                        self._publish_gauges(tel)
                        continue
                # device-profile capture boundary: one scheduler iteration
                # (≤1 prefill chunk + one decode step for every running
                # sequence) is this loop's "step"; a capture holds whole
                # iterations, each under its own serve.iter span
                _device_profile.step_boundary("serve.decode")
                with Span("serve.iter", cat="serve", step=self.batch_index):
                    self._iteration()
        except BaseException:
            # same contract as the PR 7 scheduler: a crash must not
            # strand accepted requests — latch drain first (post-crash
            # submits shed REJECTED), then fail everything in flight;
            # the engine's finish funnel releases their KV blocks
            tb = traceback.format_exc()
            eng._begin_drain(reason="scheduler crashed")
            for r in running + eng._queue.pop_all():
                if not r.done():
                    eng._finish(r, RequestStatus.ERROR,
                                detail=f"scheduler crashed:\n{tb}")
            running.clear()
            raise
        finally:
            self._stopped.set()

    def _iteration(self) -> None:
        """One iteration with work in flight, under ``serve.iter``. At
        every moment of it one phase span is open innermost on this
        thread (``serve.admit``, ``serve.blocks``, ``serve.arrays``,
        ``serve.dispatch``, ``serve.fetch``, ``serve.tokens``,
        ``serve.retire``); a round span (``serve.prefill_chunk``,
        ``serve.decode_round``, ``serve.verify_round``,
        ``serve.draft_round``) holds phases only. So an idle stretch of
        the device in a trace is booked to the host phase that held it.
        The tokens of the steps the iteration before dispatched are
        fetched after this one's rounds have dispatched theirs, in fetch
        and tokens phases of the iteration itself."""
        eng = self._engine
        running = self._running
        with Span("serve.admit", cat="serve"):
            self._admit(0.0)
            self._publish_gauges(get_telemetry())
            # mid-generation deadline shedding: the slot frees and the
            # partial text is discarded (stale results are never
            # delivered as success; a step in flight drops its token)
            now = time.monotonic()
            for r in list(running):
                if r.deadline is not None and now >= r.deadline:
                    self._retire(r, RequestStatus.DEADLINE_EXCEEDED,
                                 detail="deadline expired mid-generation")
                    running.remove(r)
            inj = active_injector()
            if inj is not None:
                for r in running:  # injected straggler stalls the round
                    inj.slow_req(r.id)
            prefilling = [r for r in running if r.pending > 1]
            decoding = [r for r in running if r.pending == 1]
        if prefilling:
            self._prefill_chunk(prefilling[0])
        if decoding and (eng.self_draft or eng.spec_enabled):
            # a speculative round needs the acceptance on the host before
            # its next input: what is in flight is fetched first
            self._drain()
            decoding = [r for r in decoding if not self._done_generating(r)]
        if decoding:
            if eng.self_draft:
                self._self_spec_round(decoding)
            elif eng.spec_enabled:
                self._spec_round(decoding)
            else:
                self._decode_round(decoding)
        self._fetch(sum(s.batch < self.batch_index for s in self._inflight))
        with Span("serve.retire", cat="serve"):
            for r in list(running):
                if self._done_generating(r):
                    self._retire(r, RequestStatus.OK)
                    running.remove(r)
            self.batch_index += 1
            if inj is not None:
                inj.maybe_sigterm(self.batch_index)

    def _admit(self, wait_s: float) -> None:
        """Fill free slots from the queue, waiting up to ``wait_s`` for a
        first prompt where nothing runs (drain stops this — a prompt
        admitted mid-drain could never finish)."""
        eng = self._engine
        running = self._running
        while not eng.draining and len(running) < eng.config.max_running:
            ready, expired = eng._queue.take(
                1, timeout=0.0 if running else wait_s)
            for r in expired:
                eng._finish(r, RequestStatus.DEADLINE_EXCEEDED,
                            detail="deadline expired in queue")
            if not ready:
                break
            ready[0].trace_event(  # sampled: queue wait ends here
                "queue", dur_s=time.monotonic() - ready[0].submitted_at)
            running.append(ready[0])

    def _publish_gauges(self, tel) -> None:
        if tel.enabled:
            tel.gauge("serve/queue_depth", len(self._engine._queue))
            tel.gauge("serve/running", len(self._running))

    # -- helpers -----------------------------------------------------------
    def _done_generating(self, r: GenRequest) -> bool:
        if r.done():
            return False  # already terminal via another path
        if len(r.generated) >= r.max_new:
            return True
        return (r.eos_id is not None and r.generated
                and r.generated[-1] == r.eos_id)

    def _retire(self, r: GenRequest, status: str, detail: str = "") -> None:
        tel = get_telemetry()
        if tel.enabled:
            t = r.ttft_ms()
            if t is not None:
                tel.observe("serve/ttft_ms", t)
            t = r.tpot_ms()
            if t is not None:
                tel.observe("serve/tpot_ms", t)
        self._engine._finish(
            r, status, outputs=[np.asarray(r.generated, np.int32)],
            detail=detail)

    def _append_token(self, r: GenRequest, tok: int) -> bool:
        """Record one sampled token. Returns False when the request had
        already hit its budget/EOS (speculative rounds may over-produce)."""
        if len(r.generated) >= r.max_new or \
                (r.eos_id is not None and r.generated
                 and r.generated[-1] == r.eos_id):
            return False
        now = time.monotonic()
        if r.first_token_at is None:
            r.first_token_at = now
        r.last_token_at = now
        r.generated.append(int(tok))
        r.toks.append(int(tok))
        get_telemetry().counter("serve/tokens_generated")
        return True

    def _dispatched(self, step: _Step) -> None:
        """Put ``step`` in flight; a decode step dispatched while the one
        before it is unfetched is counted overlapped."""
        if step.name.startswith("decode"):
            if self._newest is not None:
                get_telemetry().counter("serve/steps_overlapped")
            self._newest = step
        self._inflight.append(step)

    def _drain(self) -> None:
        """Fetch every step in flight: before a round that needs the
        tokens on the host, and at the drain."""
        self._fetch(len(self._inflight))

    def _fetch(self, n: int) -> None:
        """Fetch the tokens of the ``n`` oldest steps in flight, in the
        order of their dispatch, and hand each row's to the host's record
        (``_append_token``); a row whose request has ended, or whose cache
        was evicted, since the dispatch drops it. ``serve/decode_ms`` and
        ``serve/prefill_ms`` time stretches of the host's clock that do not
        overlap: a step from the later of its dispatch and the return of the
        fetch before its own, its tokens handed to the record (the host's
        work before that ran while the chip did), to its own tokens'
        return. A fetch that leaves no decode step in flight is a drain."""
        steps, self._inflight = self._inflight[:n], self._inflight[n:]
        tel = get_telemetry()
        for s in steps:
            with Span("serve.fetch", cat="serve"):
                got = np.asarray(s.tokens)
                ms = (time.perf_counter() - max(s.t0, self._fetched_at)) * 1e3
            with Span("serve.tokens", cat="serve"):
                if s is self._newest:
                    self._newest = None
                    tel.counter("serve/pipeline_drains")
                if tel.enabled:
                    kind, size = s.name.split(".")
                    tel.observe(f"serve/{kind}_ms", ms)
                    tel.observe(f"serve/{kind}_ms.{size}", ms)
                for (r, evictions), tok in zip(s.rows, got[:, s.col]):
                    r.trace_event(s.name, dur_s=ms / 1e3)
                    if s.emits and not r.done() \
                            and r.evictions == evictions:
                        r.unfetched -= 1
                        self._append_token(r, int(tok))
                self._fetched_at = time.perf_counter()

    def _evict(self, victim: GenRequest) -> None:
        """Recompute-style preemption: free the victim's blocks and its
        state slot; it re-enters chunked prefill over its full known
        token sequence (prompt + generated so far) when capacity returns,
        and its recurrent state starts again from zero there (the step
        resets a row whose chunk begins at position 0)."""
        eng = self._engine
        eng._pool.release(victim.id)
        victim.ncache = 0
        victim.unfetched = 0  # its step in flight drops its token
        victim.proposal = None  # its draft rows went with its blocks
        if eng.spec_enabled:
            eng._draft_pool.release(victim.id)
            victim.draft_ncache = 0
        victim.evictions += 1
        get_telemetry().counter("serve/kv_evictions")

    def _ensure_blocks(self, r: GenRequest, n_tokens: int,
                       draft: bool = False, exclude=()) -> bool:
        """Grow ``r``'s allocation, evicting the YOUNGEST other running
        sequence under pool pressure. ``exclude`` protects sequences
        already accepted into the round's batch — evicting one of those
        would zero its cache cursor AFTER its feed was decided, feeding
        the step a sequence whose blocks are gone. False = no capacity
        even after evictions (r waits a round)."""
        eng = self._engine
        pool = eng._draft_pool if draft else eng._pool
        while not pool.ensure(r.id, n_tokens):
            victim = next((v for v in reversed(self._running)
                           if v is not r and v not in exclude
                           and v.ncache > 0), None)
            if victim is None:
                return False
            self._evict(victim)
        return True

    def _batch_arrays(self, reqs: List[GenRequest], bucket: int, T: int,
                      tokens: List[List[int]], draft: bool = False):
        """Stack per-sequence feeds, padding rows to ``bucket``: padded
        rows carry kv_len 0, so every write they scatter is redirected to
        the scratch page and every attention row is fully masked, and
        state slot 0, the scratch slot."""
        eng = self._engine
        pool = eng._draft_pool if draft else eng._pool
        nc = [(r.draft_ncache if draft else r.ncache) for r in reqs]
        toks = np.zeros((bucket, T), np.int32)
        qpos = np.zeros((bucket, T), np.int32)
        lens = np.zeros((bucket,), np.int32)
        tables = np.zeros((bucket, eng._table_width), np.int32)
        slots = np.zeros((bucket,), np.int32)  # padded rows: the scratch slot
        for i, r in enumerate(reqs):
            toks[i] = tokens[i]
            qpos[i] = nc[i] + np.arange(T, dtype=np.int32)
            lens[i] = nc[i] + T
            tables[i] = pool.block_table(r.id, eng._table_width)
            slots[i] = pool.slot(r.id)
        self._count_table_slots(lens)
        # one call hands all five to the device
        return tuple(jax.device_put((toks, qpos, tables, lens, slots)))

    def _count_table_slots(self, lens: np.ndarray) -> None:
        """A step's table: the slots its longest row fills, which is what
        the paged scan walks, and the table's width; their ratio is the
        share of the table the walk reads."""
        tel = get_telemetry()
        if tel.enabled:
            eng = self._engine
            tel.counter("serve/table_slots_live", int(table_slots_live(
                lens, eng.config.kv_block_size, eng._table_width, xp=np)))
            tel.counter("serve/table_slots", eng._table_width)

    # -- prefill -----------------------------------------------------------
    def _prefill_chunk(self, r: GenRequest) -> None:
        with Span("serve.prefill_chunk", cat="serve"):
            self._prefill_chunk_phases(r)

    def _prefill_chunk_phases(self, r: GenRequest) -> None:
        eng = self._engine
        cfg = eng.config
        tel = get_telemetry()
        C = cfg.prefill_chunk
        real = min(C, r.pending)
        with Span("serve.blocks", cat="serve"):
            if not self._ensure_blocks(r, r.ncache + real):
                return  # pool exhausted even after evictions; next round
            if eng.spec_enabled and not self._ensure_blocks(
                    r, r.draft_ncache + real, draft=True):
                return
        with Span("serve.arrays", cat="serve"):
            chunk = r.toks[r.ncache:r.ncache + real] + [0] * (C - real)
            toks = np.asarray(chunk, np.int32)[None]
            qpos = (r.ncache + np.arange(C, dtype=np.int32))[None]
            lens = np.asarray([r.ncache + real], np.int32)
            table = eng._pool.block_table(r.id, eng._table_width)[None]
            slot = np.asarray([eng._pool.slot(r.id)], np.int32)
            self._count_table_slots(lens)
            if r.ncache == 0 and eng._pool.config.state and tel.enabled:
                # the chunk that starts at position 0 starts the state
                # again
                tel.counter("serve/state_resets")
            t0 = time.perf_counter()
            # numpy to the device in one call: no array is made by a
            # program of its own (a device run the trace would book to
            # this round)
            toks, qpos, table, lens, slot = jax.device_put(
                (toks, qpos, table, lens, slot))
        with Span("serve.dispatch", cat="serve"):
            g, pages, self._tokens, *hidden = self._get_prefill_fn()(
                eng._params, toks, qpos, eng._pool.pages, table, lens, slot,
                self._tokens)
            eng._pool.pages = pages
        with Span("serve.tokens", cat="serve"):
            # the chunk that covers every known token emits the first
            # generated one (TTFT stamps where it is fetched), into the
            # store as well: the next decode step takes it from there
            ends = real == r.pending
            self._dispatched(_Step(f"prefill.c{C}", [(r, r.evictions)], g,
                                   real - 1, ends, t0, self.batch_index))
            r.ncache += real
            r.unfetched += ends
            if tel.enabled:
                tel.counter("serve/prefill_chunks")
        if eng.self_draft:
            # the model's own draft reads the emitted token on the host
            self._drain()
        if eng.spec_enabled:
            # the draft cache follows the target's chunk schedule so
            # proposing never needs a separate prompt pass
            with Span("serve.arrays", cat="serve"):
                dtable = eng._draft_pool.block_table(
                    r.id, eng._table_width)[None]
                dlens = np.asarray([r.draft_ncache + real], np.int32)
                t0 = time.perf_counter()
                dtable, dlens = jnp.asarray(dtable), jnp.asarray(dlens)
            with Span("serve.dispatch", cat="serve"):
                dg, dpages = self._get_prefill_fn(draft=True)(
                    eng._draft_params, toks, qpos, eng._draft_pool.pages,
                    dtable, dlens, slot)
                eng._draft_pool.pages = dpages
            with Span("serve.fetch", cat="serve"):
                np.asarray(dg)
            with Span("serve.tokens", cat="serve"):
                if tel.enabled:
                    tel.observe(f"serve/draft_prefill_ms.c{C}",
                                (time.perf_counter() - t0) * 1e3)
                r.draft_ncache += real
        if eng.self_draft:
            # the model's own draft follows the chunk: position i takes
            # the target's hidden state of i and the token at i + 1 (the
            # one just emitted, at the prompt's end), and its guess after
            # the last known token is the first proposal
            with Span("serve.arrays", cat="serve"):
                start = r.ncache - real
                nxt = np.zeros((1, C), np.int32)
                nxt[0, :real] = r.toks[start + 1:start + real + 1]
                t0 = time.perf_counter()
                nxt = jax.device_put(nxt)
            with Span("serve.dispatch", cat="serve"):
                dg, pages = self._get_prefill_fn(draft=True)(
                    eng._params, hidden[0], nxt, qpos,
                    eng._pool.pages, table, lens, slot)
                eng._pool.pages = pages
            if r.pending == 1:
                with Span("serve.fetch", cat="serve"):
                    r.proposal = int(np.asarray(dg)[0, real - 1])
            if tel.enabled:
                with Span("serve.tokens", cat="serve"):
                    tel.observe(f"serve/draft_prefill_ms.c{C}",
                                (time.perf_counter() - t0) * 1e3)

    # -- plain decode ------------------------------------------------------
    def _decode_round(self, decoding: List[GenRequest],
                      protect=()) -> None:
        """One decode step for every decode-eligible sequence.
        ``protect`` extends the eviction-exclusion set beyond this
        round's own batch — the speculative path passes its
        already-ensured group, whose members must not lose their blocks
        to the tail's allocations after their feeds were decided."""
        with Span("serve.decode_round", cat="serve"):
            self._decode_round_phases(decoding, protect)

    def _decode_round_phases(self, decoding: List[GenRequest],
                             protect) -> None:
        eng = self._engine
        tel = get_telemetry()
        group = []
        with Span("serve.blocks", cat="serve"):
            for r in decoding:
                if r.pending != 1:
                    continue  # evicted by a neighbor's allocation
                if len(r.generated) + r.unfetched >= r.max_new:
                    continue  # the steps in flight bring its last token
                if len(group) >= eng.config.max_running:
                    break
                if self._ensure_blocks(r, r.ncache + 1,
                                       exclude=group + list(protect)):
                    group.append(r)
        if not group:
            return
        with Span("serve.arrays", cat="serve"):
            bucket = eng.config.bucket_for(len(group))
            arrays = self._batch_arrays(
                group, bucket, 1,
                [[ON_DEVICE if r.unfetched else r.toks[-1]] for r in group])
        with Span("serve.dispatch", cat="serve"):
            t0 = time.perf_counter()
            g, pages, self._tokens = self._decode_fn(bucket)(
                eng._params, arrays[0], arrays[1], eng._pool.pages,
                *arrays[2:], self._tokens)
            eng._pool.pages = pages
        with Span("serve.tokens", cat="serve"):
            self._dispatched(_Step(f"decode.b{bucket}",
                                   [(r, r.evictions) for r in group], g, 0,
                                   True, t0, self.batch_index))
            for r in group:
                r.ncache += 1
                r.unfetched += 1
                r.proposal = None  # no draft of the model's own followed
            if tel.enabled:
                tel.counter("serve/decode_steps")
                tel.observe("serve/batch_occupancy", len(group) / bucket)

    # -- speculative decode from the model's own draft ----------------------
    def _self_spec_round(self, decoding: List[GenRequest]) -> None:
        """One round of drafting from the served model's own next-token
        module (``spec_k`` 1): the target verifies the pending token and
        the standing proposal in one 2-token step and hands its hidden
        states, which stay on the device, to the draft entry; that takes
        (hidden state of position i, token at i + 1), writes its one
        layer's latent rows under the same blocks and proposes for the
        next round. Acceptance, rollback (a rejected position's rows are
        masked by the cache cursor and overwritten when the position is
        fed again) and the counters are ``_spec_round``'s."""
        eng = self._engine
        cfg = eng.config
        tel = get_telemetry()
        group, tail = [], []
        # the group is formed before the tail's decode round, outside any
        # round: its blocks phase lies in the iteration itself
        with Span("serve.blocks", cat="serve"):
            for r in decoding:
                if r.pending != 1:
                    continue
                if len(group) >= cfg.max_running:
                    break
                # no room for the k-ahead write, or no standing proposal
                # (a sequence that fell back to plain decode stays there)
                if r.ncache + 2 > eng.max_seq_len or r.proposal is None:
                    tail.append(r)
                    continue
                if self._ensure_blocks(r, r.ncache + 2, exclude=group):
                    group.append(r)
        if tail:
            self._decode_round(tail, protect=group)
        if not group:
            return
        bucket = cfg.bucket_for(len(group))
        with Span("serve.verify_round", cat="serve"):
            with Span("serve.arrays", cat="serve"):
                arrays = self._batch_arrays(
                    group, bucket, 2,
                    [[r.toks[-1], r.proposal] for r in group])
            with Span("serve.dispatch", cat="serve"):
                t0 = time.perf_counter()
                g, pages, hidden = self._verify_fn(bucket)(
                    eng._params, arrays[0], arrays[1], eng._pool.pages,
                    *arrays[2:])
                eng._pool.pages = pages
            with Span("serve.fetch", cat="serve"):
                g_np = np.asarray(g)
                ms = (time.perf_counter() - t0) * 1e3
            with Span("serve.tokens", cat="serve"):
                if tel.enabled:
                    tel.counter("serve/decode_steps")
                    # fetched before anything more is dispatched
                    tel.counter("serve/pipeline_drains")
                    tel.observe("serve/verify_ms", ms)
                    tel.observe(f"serve/verify_ms.b{bucket}", ms)
                    tel.observe("serve/batch_occupancy", len(group) / bucket)
                nxt = np.zeros((bucket, 2), np.int32)
                lens = np.zeros((bucket,), np.int32)
                accepted = []
                for i, r in enumerate(group):
                    r.trace_event(f"decode.spec.b{bucket}", dur_s=ms / 1e3)
                    a = int(r.proposal == int(g_np[i, 0]))
                    for t in g_np[i, :1 + a]:
                        if not self._append_token(r, int(t)):
                            break
                    # the draft sees what the target has confirmed:
                    # position ncache with the token that followed it and,
                    # where the proposal was accepted, the next with the
                    # target's own
                    nxt[i] = g_np[i]
                    lens[i] = r.ncache + 1 + a
                    r.ncache = min(r.ncache + 1 + a, len(r.toks) - 1)
                    accepted.append(a)
        with Span("serve.draft_round", cat="serve"):
            with Span("serve.arrays", cat="serve"):
                t0 = time.perf_counter()
                nxt, lens = jax.device_put((nxt, lens))
            with Span("serve.dispatch", cat="serve"):
                dg, pages = self._draft_fn(bucket)(
                    eng._params, hidden, nxt, arrays[1], eng._pool.pages,
                    arrays[2], lens, arrays[4])
                eng._pool.pages = pages
            with Span("serve.fetch", cat="serve"):
                dg_np = np.asarray(dg)
            with Span("serve.tokens", cat="serve"):
                for i, (r, a) in enumerate(zip(group, accepted)):
                    r.proposal = int(dg_np[i, a])
                self._spec_proposed += len(group)
                self._spec_accepted += sum(accepted)
                if tel.enabled:
                    ms = (time.perf_counter() - t0) * 1e3
                    tel.observe("serve/draft_ms", ms)
                    tel.observe(f"serve/draft_ms.b{bucket}", ms)
                    tel.counter("serve/spec_proposed", len(group))
                    tel.counter("serve/spec_accepted", sum(accepted))
                    tel.gauge(
                        "serve/spec_accept_rate",
                        self._spec_accepted / max(self._spec_proposed, 1))

    # -- speculative decode ------------------------------------------------
    def _spec_round(self, decoding: List[GenRequest]) -> None:
        """Draft proposes k tokens per sequence (k cheap steps), target
        verifies the pending token + all k proposals in ONE (k+1)-token
        step; the longest proposal prefix matching the target's greedy
        choice is accepted, plus the target's own next token."""
        eng = self._engine
        cfg = eng.config
        tel = get_telemetry()
        k = cfg.spec_k
        group = []
        tail = []  # too close to max_seq_len for k-ahead writes
        # formed before the tail's decode round, in the iteration itself
        with Span("serve.blocks", cat="serve"):
            for r in decoding:
                if r.pending != 1:
                    continue
                if len(group) >= cfg.max_running:
                    break
                # the verify step writes positions ncache..ncache+k: a
                # sequence within k tokens of max_seq_len cannot take a
                # spec round (the writes would overflow its block table /
                # position range) — it finishes its last tokens on the
                # plain decode path instead
                if r.ncache + 1 + k > eng.max_seq_len:
                    tail.append(r)
                    continue
                # target writes k+1 entries; draft catches up + writes k
                if not self._ensure_blocks(r, r.ncache + 1 + k,
                                           exclude=group):
                    continue
                if not self._ensure_blocks(r, len(r.toks) - 1 + k,
                                           draft=True, exclude=group):
                    continue
                group.append(r)
        if tail:
            # the tail's allocations must not evict spec-group members
            # whose feeds were already decided from their ensured blocks
            self._decode_round(tail, protect=group)
        if not group:
            return
        bucket = cfg.bucket_for(len(group))
        with Span("serve.draft_round", cat="serve"):
            proposals = self._spec_drafts(group, bucket)
        with Span("serve.verify_round", cat="serve"):
            self._spec_verify(group, bucket, proposals)

    def _spec_drafts(self, group: List[GenRequest], bucket: int):
        """The draft model's pass of a speculative round: its cache caught
        up with what the target has confirmed, then ``spec_k`` proposals
        for every sequence of the group."""
        eng = self._engine
        cfg = eng.config
        tel = get_telemetry()
        # draft catch-up, gap == 1 (the steady state after a fully
        # accepted round): ONE batched T=1 draft step for all of them —
        # not a chunk-padded per-sequence prefill on the hot path
        gap1 = [r for r in group if len(r.toks) - 1 - r.draft_ncache == 1]
        if gap1:
            with Span("serve.arrays", cat="serve"):
                b1 = cfg.bucket_for(len(gap1))
                arrays = self._batch_arrays(
                    gap1, b1, 1, [[r.toks[r.draft_ncache]] for r in gap1],
                    draft=True)
            with Span("serve.dispatch", cat="serve"):
                t0 = time.perf_counter()
                dg, dpages = self._draft_fn(b1)(
                    eng._draft_params, arrays[0], arrays[1],
                    eng._draft_pool.pages, *arrays[2:])
                eng._draft_pool.pages = dpages
            with Span("serve.fetch", cat="serve"):
                np.asarray(dg)  # catch-up: only the cache write matters
            with Span("serve.tokens", cat="serve"):
                if tel.enabled:
                    ms = (time.perf_counter() - t0) * 1e3
                    tel.observe("serve/draft_ms", ms)
                    tel.observe(f"serve/draft_ms.b{b1}", ms)
                for r in gap1:
                    r.draft_ncache += 1
        # chunked catch-up for larger gaps (post-eviction re-prefill)
        C = cfg.prefill_chunk
        for r in group:
            while len(r.toks) - 1 - r.draft_ncache > 0:
                with Span("serve.arrays", cat="serve"):
                    gap = len(r.toks) - 1 - r.draft_ncache
                    real = min(C, gap)
                    chunk = r.toks[r.draft_ncache:r.draft_ncache + real] \
                        + [0] * (C - real)
                    qpos = (r.draft_ncache
                            + np.arange(C, dtype=np.int32))[None]
                    dtable = eng._draft_pool.block_table(
                        r.id, eng._table_width)[None]
                    dlens = np.asarray([r.draft_ncache + real], np.int32)
                    t0 = time.perf_counter()
                    args = (jnp.asarray(np.asarray(chunk, np.int32)[None]),
                            jnp.asarray(qpos), jnp.asarray(dtable),
                            jnp.asarray(dlens), jnp.zeros((1,), jnp.int32))
                with Span("serve.dispatch", cat="serve"):
                    dg, dpages = self._get_prefill_fn(draft=True)(
                        eng._draft_params, args[0], args[1],
                        eng._draft_pool.pages, *args[2:])
                    eng._draft_pool.pages = dpages
                with Span("serve.fetch", cat="serve"):
                    np.asarray(dg)
                with Span("serve.tokens", cat="serve"):
                    if tel.enabled:
                        tel.observe(f"serve/draft_prefill_ms.c{C}",
                                    (time.perf_counter() - t0) * 1e3)
                    r.draft_ncache += real
        # k sequential draft steps propose greedily (each step timed into
        # the serve/draft_ms.b<N> hist its serve.draft.b<N> entry owns, so
        # the draft's decode-step MFU is attributed like the target's)
        proposals = [[] for _ in group]
        feed = [[r.toks[-1]] for r in group]
        for _ in range(cfg.spec_k):
            with Span("serve.arrays", cat="serve"):
                arrays = self._batch_arrays(group, bucket, 1, feed,
                                            draft=True)
            with Span("serve.dispatch", cat="serve"):
                t0 = time.perf_counter()
                dg, dpages = self._draft_fn(bucket)(
                    eng._draft_params, arrays[0], arrays[1],
                    eng._draft_pool.pages, *arrays[2:])
                eng._draft_pool.pages = dpages
            with Span("serve.fetch", cat="serve"):
                dg_np = np.asarray(dg)
            with Span("serve.tokens", cat="serve"):
                if tel.enabled:
                    ms = (time.perf_counter() - t0) * 1e3
                    tel.observe("serve/draft_ms", ms)
                    tel.observe(f"serve/draft_ms.b{bucket}", ms)
                for i, r in enumerate(group):
                    r.draft_ncache += 1
                    proposals[i].append(int(dg_np[i, 0]))
                feed = [[p[-1]] for p in proposals]
        return proposals

    def _spec_verify(self, group: List[GenRequest], bucket: int,
                     proposals: List[List[int]]) -> None:
        """The target's pass of a speculative round: one batched
        (k+1)-token verification, then the longest matching prefix of
        every sequence's proposals and the target's correction."""
        eng = self._engine
        tel = get_telemetry()
        k = eng.config.spec_k
        with Span("serve.arrays", cat="serve"):
            arrays = self._batch_arrays(
                group, bucket, k + 1,
                [[r.toks[-1]] + proposals[i] for i, r in enumerate(group)])
        with Span("serve.dispatch", cat="serve"):
            t0 = time.perf_counter()
            g, pages = self._verify_fn(bucket)(eng._params, arrays[0],
                                               arrays[1], eng._pool.pages,
                                               *arrays[2:])
            eng._pool.pages = pages
        with Span("serve.fetch", cat="serve"):
            g_np = np.asarray(g)
            ms = (time.perf_counter() - t0) * 1e3
        with Span("serve.tokens", cat="serve"):
            for r in group:  # sampled traces: one spec round = one slice
                r.trace_event(f"decode.spec.b{bucket}", dur_s=ms / 1e3)
            if tel.enabled:
                tel.counter("serve/decode_steps")
                # fetched before anything more is dispatched
                tel.counter("serve/pipeline_drains")
                tel.observe("serve/verify_ms", ms)
                tel.observe(f"serve/verify_ms.b{bucket}", ms)
                tel.observe("serve/batch_occupancy", len(group) / bucket)
            round_accepted = 0
            for i, r in enumerate(group):
                len_old = len(r.toks)
                a = 0
                while a < k and proposals[i][a] == int(g_np[i, a]):
                    a += 1
                new_toks = proposals[i][:a] + [int(g_np[i, a])]
                for t in new_toks:
                    if not self._append_token(r, t):
                        break
                # target cache advanced over the pending token + a
                # accepted proposals; rejected entries are overwritten
                # when their positions are legitimately re-fed (and masked
                # until then)
                r.ncache = min(r.ncache + 1 + a, len(r.toks) - 1)
                # draft entries beyond the accepted prefix are rolled back
                # the same way (a == k leaves the draft one token behind —
                # next round's catch-up chunk covers it)
                r.draft_ncache = min(len_old + min(a, k - 1),
                                     r.draft_ncache)
                self._spec_proposed += k
                self._spec_accepted += a
                round_accepted += a
            if tel.enabled:
                tel.counter("serve/spec_proposed", k * len(group))
                tel.counter("serve/spec_accepted", round_accepted)
                tel.gauge("serve/spec_accept_rate",
                          self._spec_accepted / max(self._spec_proposed, 1))

def dense_greedy_reference(model, prompt: Sequence[int], max_new: int,
                           eos_id: Optional[int] = None) -> List[int]:
    """Greedy decode by FULL-PREFIX recompute through the eval-mode
    Layer model — the one-shot-predictor-era reference the paged decode
    path is parity-gated against (and the baseline the decode bench must
    beat). O(L) recompute per token by construction."""
    import paddle_tpu

    toks = [int(t) for t in prompt]
    out: List[int] = []
    for _ in range(int(max_new)):
        ids = np.asarray(toks, np.int64)[None]
        logits = np.asarray(model(paddle_tpu.Tensor(ids)).numpy())
        t = int(logits[0, -1].argmax())
        toks.append(t)
        out.append(t)
        if eos_id is not None and t == eos_id:
            break
    return out


def paged_prefill_logits(model, prompt: Sequence[int], chunk: int,
                         block_size: int = 16,
                         kv_dtype: str = "float32") -> np.ndarray:
    """Logits ``[len(prompt), vocab]`` of one prompt pushed through the
    paged path the engine serves with — the model's own
    ``decode_spec()['forward_chunk']`` over a ``KVCachePool``, ``chunk``
    tokens at a time — outside any scheduler.
    The twin of ``dense_greedy_reference``: parity gates compare this
    against the eval-mode Layer forward (the paged cache is an
    optimization, never a numerics fork)."""

    from ...jit.functionalize import get_params

    prompt = np.asarray(prompt, np.int32)
    n = len(prompt)
    width = -(-n // block_size)
    spec = model.decode_spec(kv_dtype)
    pool = KVCachePool(_pool_config(spec, width + 1, block_size, kv_dtype,
                                    state_slots=1))
    pool.ensure(0, n)
    table = jnp.asarray(pool.block_table(0, width)[None])
    slot = jnp.asarray([pool.slot(0)], jnp.int32)
    fwd = jax.jit(spec["forward_chunk"])  # chunks share one compile
    params = get_params(model)
    pages = pool.pages
    rows = []
    for c0 in range(0, n, chunk):
        real = min(chunk, n - c0)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :real] = prompt[c0:c0 + real]
        qpos = (c0 + np.arange(chunk, dtype=np.int32))[None]
        lens = np.asarray([c0 + real], np.int32)
        logits, pages = fwd(params, jnp.asarray(toks), jnp.asarray(qpos),
                            pages, table, jnp.asarray(lens), slot)
        rows.append(np.asarray(logits)[0, :real])
    return np.concatenate(rows, axis=0)


def _pool_config(spec: dict, num_blocks: int, block_size: int, kv_dtype: str,
                 state_slots: int) -> KVCacheConfig:
    """The pool a model's ``decode_spec`` asks for."""
    return KVCacheConfig(
        spec["num_layers"], spec["num_heads"], spec["head_dim"],
        num_blocks=num_blocks, block_size=block_size, dtype=kv_dtype,
        num_kv_heads=spec["num_kv_heads"], layout=spec["kv_layout"],
        state=spec["state"], state_slots=state_slots,
        counters=spec.get("counters"))


class TokenServingEngine(ServingEngine):
    """Token-level serving over a causal LM that gives ``decode_spec()``
    (``GPTForCausalLM``, ``FalconH1ForCausalLM``) — the decode twin of
    the PR 7 one-shot engine, sharing its whole request lifecycle
    (admission, deadlines, drain, accounting, preemption exit) and
    substituting the decode scheduler + paged KV pool for the one-shot
    batch loop.

    ::

        eng = TokenServingEngine(model, TokenServeConfig(
            decode_buckets=(1, 2, 4, 8), prefill_chunk=32,
            kv_blocks=128, kv_dtype="int8"))
        eng.install_preemption().start()
        req = eng.submit(prompt_ids, max_new_tokens=64)
        req.wait()
        req.outputs[0]          # generated token ids (possibly partial
                                # when status == 'drained')
    """

    def __init__(self, model, config: Optional[TokenServeConfig] = None,
                 draft_model=None):
        from ...jit.functionalize import get_params

        self.config = config or TokenServeConfig()
        cfg = self.config
        # the model says how it is decoded and what cache it needs
        spec = model.decode_spec(cfg.kv_dtype)
        self._params = get_params(model)
        self._fwd = spec["forward_chunk"]
        if cfg.spec_k > 0 and spec["state"]:
            raise ValueError(
                "spec_k > 0 with a model that holds recurrent state: a "
                "rejected proposal has already moved the state, and it "
                "cannot be rolled back")
        pool_cfg = _pool_config(spec, cfg.kv_blocks, cfg.kv_block_size,
                                cfg.kv_dtype, state_slots=cfg.max_running)
        max_seq = min(cfg.max_seq_len or spec["max_positions"],
                      spec["max_positions"])
        if pool_cfg.blocks_for(max_seq) > pool_cfg.usable_blocks:
            raise ValueError(
                f"KV pool ({pool_cfg.usable_blocks} usable blocks of "
                f"{cfg.kv_block_size}) cannot hold ONE max-length sequence "
                f"({max_seq} tokens) — raise kv_blocks or lower max_seq_len")
        self.max_seq_len = max_seq
        self._pool = KVCachePool(pool_cfg)
        self._table_width = pool_cfg.blocks_for(max_seq)
        self._publish_counters = spec.get("publish_counters")
        self.spec_enabled = draft_model is not None and cfg.spec_k > 0
        own = spec.get("draft")
        # the model's own draft: no second model, no second pool
        self.self_draft = (cfg.spec_k > 0 and draft_model is None
                           and own is not None)
        if cfg.spec_k > 0 and draft_model is None and own is None:
            raise ValueError("spec_k > 0 needs a draft_model, or a model "
                             "whose decode_spec() offers a draft of its own")
        if self.self_draft:
            if cfg.spec_k > own["max_k"]:
                raise ValueError(
                    f"the model's own draft proposes {own['max_k']} token(s) "
                    f"a round; spec_k is {cfg.spec_k}")
            self._fwd_hidden = own["forward_hidden"]
            self._draft_fwd = own["forward_draft"]
            self._draft_params = self._draft_pool = None
        elif self.spec_enabled:
            dspec = draft_model.decode_spec(cfg.kv_dtype)
            if dspec["state"]:
                raise ValueError("a draft model that holds recurrent state "
                                 "cannot roll a rejected proposal back")
            self._draft_params = get_params(draft_model)
            self._draft_fwd = dspec["forward_chunk"]
            self._draft_pool = KVCachePool(_pool_config(
                dspec, cfg.kv_blocks, cfg.kv_block_size, cfg.kv_dtype,
                state_slots=0))
        else:
            self._draft_params = self._draft_fwd = self._draft_pool = None
        self._init_runtime()

    def _make_scheduler(self):
        return DecodeScheduler(self)

    @property
    def pool(self) -> KVCachePool:
        return self._pool

    def _publish_start_gauges(self) -> None:
        pass  # no predictor, no serving dtype gauge — base start() shared

    def submit(self, prompt_ids, max_new_tokens: Optional[int] = None,
               deadline_s: Optional[float] = None,
               eos_id: Optional[int] = None) -> GenRequest:
        """Admit or shed one generation request. Same contract as the
        PR 7 submit: ALWAYS returns a request; a shed one is already
        terminal."""
        if not self._started:
            raise RuntimeError("TokenServingEngine.start() first")
        prompt = np.asarray(prompt_ids)
        if prompt.ndim != 1 or prompt.size < 1 \
                or not np.issubdtype(prompt.dtype, np.integer):
            raise ValueError("prompt_ids must be a non-empty 1-D integer "
                             f"array, got shape {prompt.shape} "
                             f"{prompt.dtype}")
        max_new = (self.config.max_new_tokens if max_new_tokens is None
                   else int(max_new_tokens))
        if max_new < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new}")
        if len(prompt) + max_new > self.max_seq_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new}) "
                f"exceeds max_seq_len {self.max_seq_len}")
        req_id = self._allocate_request_id()
        req = GenRequest(req_id, prompt.astype(np.int32), max_new,
                         self._resolve_deadline(req_id, deadline_s),
                         eos_id=eos_id)
        return self._admit(req)

    def _finish(self, req, status, outputs=None, detail="", error=None):
        # the single terminal funnel also owns KV release: whatever path
        # terminates a request (OK, deadline, drain, crash, reject), its
        # blocks return to the pool here — leaks are structurally
        # impossible rather than per-call-site discipline (release is
        # idempotent and a no-op for requests that never held cache)
        self._pool.release(req.id)
        if self.spec_enabled:
            self._draft_pool.release(req.id)
        super()._finish(req, status, outputs=outputs, detail=detail,
                        error=error)

    def shutdown(self) -> dict:
        """The base teardown, and then the device memory goes: a shut-down
        engine serves nothing more, but the ops plane keeps the last
        engine for its ledger (``ops_server.set_serving_engine``), and
        with it would keep the weights and the whole pool resident."""
        acct = super().shutdown()
        self.publish_counters()
        self._params = self._draft_params = None
        self._scheduler._tokens = None
        for pool in (self._pool, self._draft_pool):
            if pool is not None:
                pool.pages = None
        return acct

    def publish_counters(self) -> dict:
        """What the compiled steps have counted in the cache pytree (a
        served expert layer's experts hit and pairs routed), fetched now
        and published as the model's ``decode_spec()`` says. The caller's
        fetch: no step makes one. Also made at shutdown."""
        if self._publish_counters is None or not self._pool.pages:
            return {}
        return self._publish_counters(self._pool.read_counters())

    def kv_accounting(self) -> dict:
        out = self._pool.accounting()
        if self.spec_enabled:
            out["draft"] = self._draft_pool.accounting()
        return out
