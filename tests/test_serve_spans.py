"""The token scheduler's own spans (``inference/serving/decode.py``): every
iteration with work in flight opens ``serve.iter`` with its batch index,
and inside it one phase span is open innermost at every moment, a round
span holding phases alone; the tokens of the steps in flight are fetched
in phases of the iteration itself. Driven through prefill chunks, decode
rounds and an eviction, through the speculative round of a draft model and
through the round of a model's own draft; read back from the flight
recorder.

The tokens each run emits are written below as the scheduler gave them
before it opened any span: the spans change no token and no compile."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import (TokenServeConfig,
                                          TokenServingEngine)
from paddle_tpu.profiler import spans
from paddle_tpu.profiler.telemetry import get_telemetry
from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

PHASES = {"serve.admit", "serve.blocks", "serve.arrays", "serve.dispatch",
          "serve.fetch", "serve.tokens", "serve.retire"}
ROUNDS = {"serve.prefill_chunk", "serve.decode_round", "serve.verify_round",
          "serve.draft_round"}
# what an iteration holds between its admission and its retirement: the
# rounds, a speculative round's group formed before the tail's decode, and
# the fetch of the steps in flight (the iteration before's, after this
# one's rounds have dispatched; all of them before a speculative round)
IN_ITER = ROUNDS | {"serve.blocks", "serve.fetch", "serve.tokens"}

# the emitted tokens of each run below, from the scheduler as it was
# before it opened spans
GOLDEN = {
    "plain": [[44] * 16, [19] * 16, [61] * 16],
    "draft_model": [[83, 83, 51, 51, 51, 46, 46, 46, 46, 46], [42] * 10],
    "own_draft": [[6, 4, 6, 4, 6, 6, 4, 6, 4, 6, 6, 4],
                  [4, 3, 4, 3, 4, 4, 3, 4, 3, 4, 3, 4]],
}


def gpt(seed, hidden, layers):
    paddle.seed(seed)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=96, hidden_size=hidden, num_layers=layers, num_heads=2,
        max_position_embeddings=128, hidden_dropout=0.0,
        attention_dropout=0.0))
    m.eval()
    return m


def pangu():
    from paddle_tpu.text.models.pangu_ultra_moe import (
        PanguUltraMoEForCausalLM, pangu_ultra_moe_tiny)

    paddle.seed(2)
    m = PanguUltraMoEForCausalLM(pangu_ultra_moe_tiny(
        vocab_size=8, num_hidden_layers=2))
    m.eval()
    return m


RUNS = {
    # 3 prompts of 20 in chunks of 8 over 8 usable blocks of 8: a prompt
    # is evicted and prefilled again
    "plain": dict(model=lambda: gpt(0, 32, 2), prompts=(20, 20, 20),
                  new=16, seed=7,
                  config=dict(kv_blocks=9, kv_block_size=8, max_seq_len=48)),
    "draft_model": dict(model=lambda: gpt(0, 32, 2),
                        draft=lambda: gpt(3, 16, 1), prompts=(5, 13),
                        new=10, seed=7, config=dict(spec_k=3)),
    "own_draft": dict(model=pangu, prompts=(5, 13), new=12, seed=9, vocab=8,
                      config=dict(spec_k=1, kv_block_size=4,
                                  decode_buckets=(1, 2))),
}


def serve(name):
    """(tokens each request emitted, the scheduler thread's span events,
    the engine) of one run, the flight recorder made large enough to hold
    every event of it."""
    run = RUNS[name]
    cfg = dict(capacity=16, decode_buckets=(1, 2, 4), prefill_chunk=8,
               kv_blocks=48, kv_block_size=8, max_seq_len=96)
    cfg.update(run["config"])
    draft = run.get("draft")
    eng = TokenServingEngine(run["model"](), TokenServeConfig(**cfg),
                             draft_model=draft() if draft else None)
    rng = np.random.RandomState(run["seed"])
    prompts = [rng.randint(0, run.get("vocab", 96), n).astype(np.int32)
               for n in run["prompts"]]
    eng.start()  # every entry compiled before the loop runs
    try:
        reqs = [eng.submit(p, max_new_tokens=run["new"]) for p in prompts]
        for r in reqs:
            assert r.wait(300) and r.status == "ok", (r.status, r.detail)
    finally:
        eng.shutdown()
    tid = eng._scheduler._thread.ident
    events = [e for e in spans.flight_recorder().tail() if e[5] == tid]
    return [[int(t) for t in r.outputs[0]] for r in reqs], events, eng


@pytest.fixture(scope="module", params=sorted(RUNS))
def served(request):
    get_telemetry().reset()
    keep = spans._flight
    spans._flight = spans.FlightRecorder(capacity=1 << 20)
    try:
        tokens, events, eng = serve(request.param)
        counters = {k: get_telemetry().counter_value(k)
                    for k in ("serve/kv_evictions", "serve/spec_proposed",
                              "serve/prefill_chunks", "serve/decode_steps")}
    finally:
        spans._flight = keep
    return request.param, tokens, events, eng, counters


def tree(events):
    """span id -> {name, step, parent, ts, dur, children}; every span the
    scheduler opened was closed."""
    out = {}
    for phase, name, _cat, ts, dur, _tid, sid, parent, step in events:
        if phase == "B":
            out[sid] = {"name": name, "step": step, "parent": parent,
                        "ts": ts, "children": []}
        else:
            out[sid]["dur"] = dur
    assert all("dur" in s for s in out.values())
    for sid, s in out.items():
        if s["parent"] in out:
            out[s["parent"]]["children"].append(s)
    return out


def test_the_tokens_are_the_ones_before_the_spans(served):
    name, tokens, _, _, counters = served
    assert tokens == GOLDEN[name]
    assert counters["serve/prefill_chunks"] > len(RUNS[name]["prompts"])
    if name == "plain":
        assert counters["serve/kv_evictions"] >= 1
    else:
        assert counters["serve/spec_proposed"] > 0


def test_every_iteration_opens_serve_iter_with_its_batch_index(served):
    _, _, events, eng, _ = served
    found = tree(events)
    iters = sorted((s for s in found.values() if s["name"] == "serve.iter"),
                   key=lambda s: s["ts"])
    # no span outside an iteration: the wait for work opens none
    assert {s["name"] for s in found.values() if s["parent"] == 0} \
        == {"serve.iter"}
    assert [s["step"] for s in iters] == list(range(eng._scheduler
                                                    .batch_index))
    for it in iters:
        names = [c["name"] for c in sorted(it["children"],
                                           key=lambda c: c["ts"])]
        assert names[0] == "serve.admit" and names[-1] == "serve.retire"
        assert set(names[1:-1]) <= IN_ITER, names
        # children inherit the iteration's step
        assert {c["step"] for c in it["children"]} == {it["step"]}


def test_a_round_holds_phases_and_phases_hold_nothing(served):
    name, _, events, _, _ = served
    found = tree(events).values()
    rounds = [s for s in found if s["name"] in ROUNDS]
    want = {"plain": {"serve.prefill_chunk", "serve.decode_round"},
            "draft_model": {"serve.prefill_chunk", "serve.draft_round",
                            "serve.verify_round"},
            "own_draft": {"serve.prefill_chunk", "serve.draft_round",
                          "serve.verify_round"}}[name]
    assert want <= {s["name"] for s in rounds}
    inside = outside = 0.0
    for s in rounds:
        assert s["children"] and {c["name"] for c in s["children"]} <= PHASES
        inside += sum(c["dur"] for c in s["children"])
        outside += s["dur"] - sum(c["dur"] for c in s["children"])
    assert all(not s["children"] for s in found if s["name"] in PHASES)
    assert {s["name"] for s in found} <= PHASES | ROUNDS | {"serve.iter"}
    # the glue between two phases of a round is a few lines of Python
    assert outside < 0.1 * (inside + outside)


def test_no_compile_past_the_warm_up(served):
    _, _, _, eng, _ = served
    sched = eng._scheduler
    fns = [*sched._decode_fns.values(), *sched._verify_fns.values(),
           *sched._draft_fns.values(), sched._prefill_fn,
           sched._draft_prefill_fn]
    assert all(fn.tracker.compiles == 1 for fn in fns if fn is not None)
