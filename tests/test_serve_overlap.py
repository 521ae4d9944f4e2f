"""One decode step in flight (``inference/serving/decode.py``): the token
scheduler dispatches an iteration's steps before it fetches the tokens of
the iteration before, a step takes a row's input token from the device's
token store, and the host learns every token one iteration late.

Whatever the overlap, what a request emits is what greedy decoding emits:
for GPT the full-prefix recompute (``dense_greedy_reference``, here
compiled once over a right-padded prefix), for
Falcon-H1 (recurrent-state slots) and openPangu (latent pool, expert
counters) the full forward's first choice at every position. Driven through
prompts of one to four chunks, budgets that end at different steps, an
``eos_id`` met mid-stream, and an eviction, a deadline and a drain that each
meet a step in flight; then the pipeline's counters, the speculative rounds
that drain it, and the compiled entries."""
import functools
import time

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import (RequestStatus, TokenServeConfig,
                                          TokenServingEngine,
                                          dense_greedy_reference)
from paddle_tpu.jit.functionalize import functionalize, get_params
from paddle_tpu.profiler.telemetry import get_telemetry
from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM


@pytest.fixture(autouse=True)
def _clean():
    get_telemetry().reset()
    yield


def gpt(seed=0, hidden=32, layers=2, scale=4.0):
    """A tiny GPT with its matrices scaled up from the seed's: at the
    initializer's own scale a greedy stream repeats one token, and a step
    fed the wrong one would go unseen."""
    paddle.seed(seed)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=96, hidden_size=hidden, num_layers=layers, num_heads=2,
        max_position_embeddings=128, hidden_dropout=0.0,
        attention_dropout=0.0))
    m.eval()
    for name, p in m.named_parameters():
        if p.ndim == 2 and "wte" not in name:
            p.set_value(p.numpy() * scale)
    return m


@functools.lru_cache(maxsize=4)
def forward_of(model):
    apply = functionalize(model, training=False)
    return jax.jit(lambda params, ids: apply(params, {}, ids)[0])


def greedy(model, prompt, n, eos=None, width=64):
    """``dense_greedy_reference`` compiled once: the prefix right-padded to
    ``width``, whose padding a causal model's logits at the prefix's last
    position do not see."""
    fwd, params = forward_of(model), get_params(model)
    toks, out = [int(t) for t in prompt], []
    while len(out) < n and (eos is None or eos not in out):
        ids = np.zeros((1, width), np.int64)
        ids[0, :len(toks)] = toks
        t = int(np.asarray(fwd(params, ids))[0, len(toks) - 1].argmax())
        toks.append(t)
        out.append(t)
    return out


def engine(model, draft=None, **kw):
    cfg = dict(capacity=16, decode_buckets=(1, 2, 4), prefill_chunk=8,
               kv_blocks=48, kv_block_size=8, max_seq_len=96)
    cfg.update(kw)
    return TokenServingEngine(model, TokenServeConfig(**cfg),
                              draft_model=draft)


def prompts_of(lengths, seed, vocab=96):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, n).astype(np.int32) for n in lengths]


def serve(eng, prompts, budgets, **kw):
    """Submit every prompt, wait for all; the requests."""
    reqs = [eng.submit(p, max_new_tokens=n, **kw)
            for p, n in zip(prompts, budgets)]
    for r in reqs:
        assert r.wait(300) and r.status == RequestStatus.OK, \
            (r.status, r.detail)
    return reqs


def emitted(r):
    return [int(t) for t in r.outputs[0]]


def counters():
    tel = get_telemetry()
    return {k: tel.counter_value(f"serve/{k}") for k in (
        "decode_steps", "steps_overlapped", "pipeline_drains",
        "kv_evictions")}


def assert_pipelined(eng):
    """Every decode step was dispatched with the one before it unfetched,
    but the first after each drain; nothing leaked."""
    c = counters()
    assert c["steps_overlapped"] == c["decode_steps"] - c["pipeline_drains"]
    assert c["steps_overlapped"] > 0 and c["pipeline_drains"] > 0
    acct = eng.kv_accounting()
    assert acct["leaked_blocks"] == 0 and acct.get("leaked_slots", 0) == 0
    assert acct["owners"] == [] and not eng._scheduler._inflight


def compiled(sched):
    """How often each of a scheduler's compiled entries has compiled."""
    fns = {**{f"decode.b{b}": f for b, f in sched._decode_fns.items()},
           **{f"verify.b{b}": f for b, f in sched._verify_fns.items()},
           **{f"draft.b{b}": f for b, f in sched._draft_fns.items()},
           "prefill": sched._prefill_fn,
           "draft_prefill": sched._draft_prefill_fn}
    return {k: f.tracker.compiles for k, f in fns.items() if f is not None}


def wrap(obj, name, before=None, after=None):
    """Put ``before(*args)`` / ``after(*args)`` around ``obj.name``."""
    inner = getattr(obj, name)

    def outer(*args, **kw):
        if before is not None:
            before(*args)
        out = inner(*args, **kw)
        if after is not None:
            after(*args)
        return out

    setattr(obj, name, outer)


# -- GPT ----------------------------------------------------------------------

def test_a_mixed_load_emits_what_greedy_decoding_does_and_compiles_nothing():
    """Prompts of one to four chunks, budgets of 1 to 14, five requests over
    four rows (one is admitted while the others decode), and one request
    that meets its ``eos_id`` mid-stream, where the row runs a step past it
    before the host has seen it."""
    model = gpt()
    prompts = prompts_of((3, 9, 21, 30, 14), seed=7)
    budgets = (12, 5, 9, 1, 14)
    want = [greedy(model, p, n) for p, n in zip(prompts, budgets)]
    assert want[0][:4] == dense_greedy_reference(model, prompts[0], 4)
    stream = want[-1]
    k = next(i for i in range(3, len(stream)) if stream[i] not in stream[:i])
    eos = stream[k]
    want[-1] = greedy(model, prompts[-1], 14, eos)
    assert want[-1] == stream[:k + 1]
    eng = engine(model).start(warmup=True)
    before = compiled(eng._scheduler)
    try:
        reqs = serve(eng, prompts[:-1], budgets[:-1])
        reqs += serve(eng, prompts[-1:], budgets[-1:], eos_id=eos)
    finally:
        eng.shutdown()
    assert [emitted(r) for r in reqs] == want
    assert_pipelined(eng)
    # the same five entries, each compiled once, by the warm-up
    assert compiled(eng._scheduler) == before
    assert sorted(before) == ["decode.b1", "decode.b2", "decode.b4",
                              "prefill"]
    assert set(before.values()) == {1}


def test_an_eviction_that_meets_a_step_in_flight_drops_its_token():
    """Three prompts of 20 over 8 usable blocks of 8: sequences are evicted
    while their decode step is in flight, drop its token and compute it
    again after their prefill."""
    model = gpt()
    prompts = prompts_of((20, 20, 20), seed=7)
    want = [greedy(model, p, 16) for p in prompts]
    eng = engine(model, kv_blocks=9, max_seq_len=48)
    unfetched = []
    wrap(eng._scheduler, "_evict",
         before=lambda victim: unfetched.append(victim.unfetched))
    eng.start()
    try:
        reqs = serve(eng, prompts, (16,) * 3)
    finally:
        eng.shutdown()
    assert [emitted(r) for r in reqs] == want
    assert max(unfetched) >= 1
    assert counters()["kv_evictions"] == len(unfetched)
    assert_pipelined(eng)


def test_a_deadline_that_meets_a_step_in_flight_drops_its_token():
    """The middle request's deadline passes once it has four tokens: the
    next admission sheds it with a step in flight, whose token goes
    nowhere; the others go on as greedy decoding has them."""
    model = gpt()
    prompts = prompts_of((5, 11, 17), seed=8)
    want = [greedy(model, p, 12) for p in prompts]
    eng = engine(model)
    sched, shed = eng._scheduler, {}

    def expire(r, _tok):
        if r.n_prompt == 11 and len(r.generated) == 4:
            r.deadline = time.monotonic()

    def record(r, status, *_):
        if status == RequestStatus.DEADLINE_EXCEEDED:
            shed[r.n_prompt] = (r.unfetched, list(r.generated))

    wrap(sched, "_append_token", after=expire)
    wrap(sched, "_retire", before=record)
    eng.start()
    try:
        reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
        for r in reqs:
            assert r.wait(300)
    finally:
        eng.shutdown()
    assert [r.status for r in reqs] == [
        RequestStatus.OK, RequestStatus.DEADLINE_EXCEEDED, RequestStatus.OK]
    assert emitted(reqs[0]) == want[0] and emitted(reqs[2]) == want[2]
    in_flight, generated = shed[11]
    assert in_flight >= 1 and generated == want[1][:4]
    assert_pipelined(eng)


def test_speculative_rounds_drain_the_pipeline_and_emit_as_before():
    """A draft model's rounds need the acceptance on the host before their
    next input: each fetches what is in flight first and counts a drain;
    the tokens are plain greedy decoding's."""
    model, draft = gpt(), gpt(3, 16, 1)
    prompts = prompts_of((5, 13, 20), seed=9)
    want = [greedy(model, p, 10) for p in prompts]
    eng = engine(model, draft=draft, spec_k=3).start()
    try:
        reqs = serve(eng, prompts, (10,) * 3)
    finally:
        eng.shutdown()
    assert [emitted(r) for r in reqs] == want
    rounds = get_telemetry().hist_summary("serve/verify_ms")["count"]
    c = counters()
    assert rounds > 0 and c["pipeline_drains"] >= rounds
    assert c["steps_overlapped"] == c["decode_steps"] - c["pipeline_drains"]
    assert eng.kv_accounting()["leaked_blocks"] == 0
    assert eng.kv_accounting()["draft"]["leaked_blocks"] == 0


# -- Falcon-H1 and openPangu --------------------------------------------------

def test_falcon_state_slots_overlap_token_for_token_and_drain_clean():
    """Falcon-H1's recurrent state in slots beside the pages: prompts of one
    to three chunks and budgets that end apart are greedy; then a drain
    with no grace meets two rows with a step in flight, fetches it, and
    returns every block and every slot."""
    from tests.test_falcon_h1 import assert_greedy, ids_of, program

    model = program()
    eng = engine(model, kv_block_size=4, drain_grace_s=0.0)
    sched, in_flight = eng._scheduler, []
    wrap(sched, "_drain",
         before=lambda: in_flight.append(len(sched._inflight)))
    eng.start(warmup=False)
    try:
        prompts = [ids_of(1, n, seed=20 + n)[0] for n in (5, 13, 21)]
        budgets = (6, 3, 9)
        for p, n, r in zip(prompts, budgets, serve(eng, prompts, budgets)):
            assert len(r.outputs[0]) == n
            assert_greedy(model, p, r.outputs[0])
        long = [ids_of(1, 9, seed=60 + i)[0] for i in range(2)]
        reqs = [eng.submit(p, max_new_tokens=60) for p in long]
        t_end = time.monotonic() + 300
        while min(len(r.generated) for r in reqs) < 3:
            assert time.monotonic() < t_end
            time.sleep(0.01)
        eng.drain(wait=True)
    finally:
        eng.shutdown()
    for p, r in zip(long, reqs):
        assert r.status == RequestStatus.DRAINED
        assert 3 <= len(r.outputs[0]) < 60
        assert_greedy(model, p, r.outputs[0])
    assert max(in_flight) >= 1
    assert_pipelined(eng)
    assert eng.kv_accounting()["slot_owners"] == []


def test_pangu_latent_pool_overlap_token_for_token_and_counts_its_experts():
    """openPangu's latent pool with its ``moe_counts`` leaf in the donated
    cache: greedy token for token, and two expert layers counted in every
    decode step, overlapped or not."""
    from tests.test_pangu_ultra_moe import assert_greedy, ids_of, program

    model = program(nextn=False)
    eng = engine(model, kv_block_size=4).start(warmup=False)
    try:
        prompts = [ids_of(1, n, seed=20 + n)[0] for n in (5, 13, 21)]
        budgets = (7, 4, 10)
        for p, n, r in zip(prompts, budgets, serve(eng, prompts, budgets)):
            assert len(r.outputs[0]) == n
            assert_greedy(model, p, r.outputs[0])
    finally:
        eng.shutdown()
    tel = get_telemetry()
    assert tel.counter_value("moe/layer_steps.decode") \
        == 2 * tel.counter_value("serve/decode_steps")
    assert tel.counter_value("moe/layer_steps.chunk") \
        == 2 * tel.counter_value("serve/prefill_chunks")
    assert_pipelined(eng)
