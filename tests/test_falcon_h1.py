"""Falcon-H1 against its plain reference (``benchmark/reference/
falcon_h1.py``) on seeded random weights, at a small size on the CPU: one
block and the whole model, every multiplier in its place, grouped-query
paged attention in both tiers, chunked prefill and decode through pages
and recurrent state, a slot reused and a sequence evicted and admitted
again, the vocabulary's slices adding up, and the engine's refusals.

The multipliers of ``CONFIG`` are not the published ones. Those are set
for trained weights 5120 wide; with weights from a seed at width 64 they
leave the mixers 1e-3 of the residual stream and the state 1e-4 of a
mixer's output (the gated norm's epsilon outweighs what it normalises), so
that a test would pass with the state lost. Here each is chosen so that
every path shows in the logits: the state is a third of y, a mixer's
output as large as the stream, attention and the MLP a fifth of it.

Tolerances. Both sides are float32 on the CPU and part by the order of
their sums. Gaps are read relative to the reference's largest logit:
``close`` holds 1e-4 of it (measured: under 1e-6) and fails everything the
tests plant.
"""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.families import falcon_h1 as family
from benchmark.families import falcon_h1_serve as serve_family
from benchmark.reference import common
from benchmark.reference import falcon_h1 as reference
from paddle_tpu.inference.serving import (KVCacheConfig, KVCachePool,
                                          TokenServeConfig,
                                          TokenServingEngine)
from paddle_tpu.inference.serving.decode import _pool_config
from paddle_tpu.jit.functionalize import get_params
from paddle_tpu.ops import attention as att
from paddle_tpu.profiler.telemetry import get_telemetry
from paddle_tpu.text.models.falcon_h1 import falcon_h1_tiny

EINSUM = common.make_einsum("float32")
SEED = 11

# the reference's configuration of a tiny model: every part of the block
CONFIG = {
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 96, "max_position_embeddings": 512, "rms_norm_eps": 1e-5,
    "rope_theta": 1e11, "mamba_d_ssm": 64, "mamba_n_heads": 4,
    "mamba_d_head": 16, "mamba_n_groups": 2, "mamba_d_state": 16,
    "mamba_d_conv": 4, "mamba_chunk_size": 8,
    "embedding_multiplier": 5.656854249492381,
    "lm_head_multiplier": 0.0078125, "attention_in_multiplier": 1.0,
    "attention_out_multiplier": 2.0, "key_multiplier": 16.0,
    "ssm_in_multiplier": 1.0, "ssm_out_multiplier": 1.0,
    "ssm_multipliers": [4.0, 4.0, 8.0, 8.0, 1.0],
    "mlp_multipliers": [4.0, 2.0],
    "deployment": {"vocab_size_published": 96 * 8,
                   "num_hidden_layers_published": 24},
    "assumed": {"initializer_range": 0.02, "conv_std": 0.2887,
                "dt_bias_std": 3.0},
}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def program(config=CONFIG, seed=SEED):
    return serve_family.build_model(
        config, serve_family.weights(config, seed, "float32"))


def ref_params(config=CONFIG, seed=SEED):
    return common.init_params(reference.param_specs(config), seed)


def ref_logits(ids, config=CONFIG, seed=SEED):
    return np.asarray(reference.logits(ref_params(config, seed),
                                       jnp.asarray(ids), config, EINSUM))


def ids_of(rows, length, seed=0, vocab=CONFIG["vocab_size"]):
    return np.random.default_rng(seed).integers(
        0, vocab, (rows, length)).astype(np.int32)


def close(got, want, tol=1e-4):
    scale = float(np.abs(want).max())
    np.testing.assert_allclose(got, want, atol=tol * scale, rtol=0)


# -- the model against the reference -------------------------------------------

def test_the_harness_names_every_parameter_of_the_program():
    model = program()
    assert set(family.names_of(CONFIG).values()) == set(get_params(model))
    assert set(family.names_of(CONFIG)) == set(reference.param_specs(CONFIG))


def test_one_block_matches_the_reference():
    model = program()
    p = ref_params()
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (2, 19, CONFIG["hidden_size"])).astype(np.float32))
    want = reference.block({n: p[f"l1_{n}"] for n in reference.LAYER_LEAVES},
                           x, CONFIG, EINSUM)
    got = model.model.layers[1](paddle.to_tensor(np.asarray(x))).numpy()
    close(np.asarray(got), np.asarray(want), tol=1e-5)


def test_the_whole_models_logits_match_the_reference():
    ids = ids_of(2, 37)
    close(program()(paddle.to_tensor(ids)).numpy(), ref_logits(ids))


MULTIPLIERS = (["embedding_multiplier", "lm_head_multiplier",
                "attention_in_multiplier", "attention_out_multiplier",
                "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier"]
               + [f"ssm_multipliers.{i}" for i in range(5)]
               + [f"mlp_multipliers.{i}" for i in range(2)])


ONE_LAYER = {**CONFIG, "num_hidden_layers": 1}
_base = {}


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_each_multiplier_matters_and_stands_where_the_reference_has_it(name):
    """With one multiplier set to 1 (to 2 where it is 1) the output moves,
    in the program as in the reference (one layer: every multiplier is in
    it)."""
    ids = ids_of(1, 13, seed=2)
    if "logits" not in _base:
        _base["logits"] = ref_logits(ids, ONE_LAYER)
    base = _base["logits"]
    changed = copy.deepcopy(ONE_LAYER)
    key, _, index = name.partition(".")
    was = changed[key][int(index)] if index else changed[key]
    if index:
        changed[key][int(index)] = 2.0 if was == 1 else 1.0
    else:
        changed[key] = 2.0 if was == 1 else 1.0
    want = ref_logits(ids, changed)
    moved = np.abs(want - base).max() / np.abs(base).max()
    assert moved > 1e-3, (name, moved)
    close(program(changed)(paddle.to_tensor(ids)).numpy(), want)


def test_the_vocabulary_slices_side_by_side_are_the_uncut_models_logits():
    """Eight chips hold an eighth of the rows each. Whichever holds the
    ids embeds them (here slice 3); every slice's head then gives its own
    columns of the uncut model's logits."""
    slices, rows = 8, 12
    uncut = {**CONFIG, "vocab_size": slices * rows}
    p = ref_params(uncut)
    held = 3
    local = ids_of(2, 17, seed=3, vocab=rows)
    want = np.asarray(reference.logits(p, jnp.asarray(local + held * rows),
                                       uncut, EINSUM))
    cut = {**CONFIG, "vocab_size": rows}
    names = family.names_of(cut)
    parts = []
    for s in range(slices):
        named = {names[n]: v for n, v in p.items()}
        named[names["embed"]] = p["embed"][held * rows:(held + 1) * rows]
        named[names["head_w"]] = p["head_w"][:, s * rows:(s + 1) * rows]
        parts.append(np.asarray(serve_family.build_model(cut, named)(
            paddle.to_tensor(local)).numpy()))
    close(np.concatenate(parts, axis=-1), want)


def test_the_rotary_embedding_is_the_references():
    import paddle_tpu.nn.functional as F

    x = np.random.default_rng(7).standard_normal((2, 9, 3, 16)).astype(
        np.float32)
    positions = np.broadcast_to(np.arange(9, dtype=np.int32), (2, 9))
    want = np.asarray(reference.rope(jnp.asarray(x), 1e11))
    got = F.rotary_embedding(paddle.to_tensor(x), positions, theta=1e11)
    np.testing.assert_allclose(got.numpy(), want, atol=1e-6)
    # positions that do not start at 0: row 1 of a cache, three tokens in
    late = F.rotary_embedding(jnp.asarray(x[:, 3:]), jnp.asarray(
        positions[:, 3:]), 1e11)
    np.testing.assert_allclose(late, want[:, 3:], atol=1e-6)


# -- grouped queries through the pages -----------------------------------------

@pytest.mark.parametrize("impl", ["_paged_gather_impl", "_paged_scan_impl"])
@pytest.mark.parametrize("flat", [False, True])
def test_grouped_query_paged_attention_matches_dense(impl, flat):
    rng = np.random.default_rng(4)
    B, T, Hq, Hkv, D, bs, M = 3, 5, 6, 2, 8, 4, 6
    lens = np.array([19, 7, 24])
    k = rng.standard_normal((B, M * bs, Hkv, D)).astype(np.float32)
    v = rng.standard_normal((B, M * bs, Hkv, D)).astype(np.float32)
    q = rng.standard_normal((B, T, Hq, D)).astype(np.float32)
    # pages: row b's table is b * M + 1 ... (page 0 is scratch)
    pages_k = np.zeros((B * M + 1, bs, Hkv, D), np.float32)
    pages_v = np.zeros_like(pages_k)
    tables = np.zeros((B, M), np.int32)
    for b in range(B):
        tables[b] = 1 + b * M + np.arange(M)
        pages_k[tables[b]] = k[b].reshape(M, bs, Hkv, D)
        pages_v[tables[b]] = v[b].reshape(M, bs, Hkv, D)
    qpos = (lens[:, None] - T + np.arange(T)[None]).astype(np.int32)
    if flat:
        pages_k, pages_v = (t.reshape(B * M + 1, bs, Hkv * D)
                            for t in (pages_k, pages_v))
    got = getattr(att, impl)(jnp.asarray(q), jnp.asarray(pages_k),
                             jnp.asarray(pages_v), jnp.asarray(tables),
                             jnp.asarray(qpos),
                             jnp.asarray(lens, jnp.int32))
    rep = Hq // Hkv
    for b in range(B):
        kk, vv = (np.repeat(t[b, :lens[b]], rep, axis=1) for t in (k, v))
        s = np.einsum("thd,shd->hts", q[b], kk) / np.sqrt(D)
        mask = np.arange(lens[b])[None, :] <= qpos[b][:, None]
        s = np.where(mask[None], s, -np.inf)
        w = np.exp(s - s.max(-1, keepdims=True))
        w /= w.sum(-1, keepdims=True)
        np.testing.assert_allclose(np.asarray(got)[b],
                                   np.einsum("hts,shd->thd", w, vv),
                                   atol=2e-5)


def test_the_forced_tier_takes_grouped_queries(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_ATTN_PAGED_POLICY", "paged_scan")
    get_telemetry().reset()
    q = jnp.zeros((1, 1, 4, 8))
    pages = jnp.zeros((3, 4, 16))  # two key heads of 8, flattened
    out = att.paged_attention(q, pages, pages, jnp.ones((1, 2), jnp.int32),
                              jnp.zeros((1, 1), jnp.int32),
                              jnp.ones((1,), jnp.int32))
    assert out.shape == (1, 1, 4, 8)
    gauges = get_telemetry().snapshot()["gauges"]
    assert gauges["attn/tier.paged.t1.d8"] == 6  # paged_scan's id


# -- prefill in chunks, then decode, through pages and state -------------------

class Cached:
    """The model's own cached forward over a pool, driven by hand: rows of
    a bucket, each a sequence with its blocks and its slot."""

    def __init__(self, model, rows, blocks=40, block=4, slots=4, width=12):
        spec = model.decode_spec("float32")
        self.pool = KVCachePool(_pool_config(spec, blocks, block, "float32",
                                             state_slots=slots))
        self.fwd = jax.jit(spec["forward_chunk"])
        self.params = get_params(model)
        self.rows, self.width = rows, width
        self.n = {}  # owner -> tokens cached

    def feed(self, feeds: dict, T: int):
        """``feeds``: owner -> its next tokens (at most T); the other rows
        of the bucket are padding. Returns owner -> logits of its tokens."""
        toks = np.zeros((self.rows, T), np.int32)
        qpos = np.zeros((self.rows, T), np.int32)
        lens = np.zeros((self.rows,), np.int32)
        tables = np.zeros((self.rows, self.width), np.int32)
        slots = np.zeros((self.rows,), np.int32)
        for row, (owner, new) in enumerate(feeds.items()):
            have = self.n.get(owner, 0)
            assert self.pool.ensure(owner, have + len(new))
            toks[row, :len(new)] = new
            qpos[row] = have + np.arange(T)
            lens[row] = have + len(new)
            tables[row] = self.pool.block_table(owner, self.width)
            slots[row] = self.pool.slot(owner)
            self.n[owner] = have + len(new)
        logits, self.pool.pages = self.fwd(
            self.params, *map(jnp.asarray, (toks, qpos)), self.pool.pages,
            *map(jnp.asarray, (tables, lens, slots)))
        return {owner: np.asarray(logits)[row, :len(new)]
                for row, (owner, new) in enumerate(feeds.items())}

    def run(self, owner, ids, chunk, decode_from):
        """Prefill ``ids[:decode_from]`` in chunks of ``chunk`` (the last
        one uneven), then decode the rest a token at a time, in a bucket
        whose other rows are padding: logits of every position."""
        out = []
        for lo in range(0, decode_from, chunk):
            out.append(self.feed({owner: ids[lo:min(lo + chunk,
                                                   decode_from)]},
                                 chunk)[owner])
        for t in range(decode_from, len(ids)):
            out.append(self.feed({owner: ids[t:t + 1]}, 1)[owner])
        return np.concatenate(out)

    def forget(self, owner):
        self.pool.release(owner)
        self.n.pop(owner, None)


def test_chunked_prefill_then_decode_agrees_with_the_full_forward():
    """Two sequences of uneven lengths in a bucket of four rows: each
    prefilled in chunks of 8 (13 = 8 + 5, 21 = 8 + 8 + 5), then decoded
    side by side with two padded rows, against the reference's one full
    forward at every position."""
    model = program()
    ids = ids_of(2, 30, seed=5)
    want = ref_logits(ids)
    c = Cached(model, rows=4)
    got = {0: [], 1: []}
    for owner, n in ((0, 13), (1, 21)):
        for lo in range(0, n, 8):
            got[owner].append(c.feed({owner: ids[owner, lo:min(lo + 8, n)]},
                                     8)[owner])
    for t in range(13, 22):  # both decode, out of step by eight positions
        step = c.feed({0: ids[0, t:t + 1], 1: ids[1, t + 8:t + 9]}, 1)
        got[0].append(step[0])
        got[1].append(step[1])
    close(np.concatenate(got[0]), want[0, :22])
    close(np.concatenate(got[1]), want[1, :30])
    # and the state is what carries it: with the slots' state wiped, the
    # next step's logits are far off
    c.pool.pages["ssm"] = tuple(jnp.zeros_like(t)
                                for t in c.pool.pages["ssm"])
    off = c.feed({0: ids[0, 22:23]}, 1)[0]
    assert np.abs(off - want[0, 22:23]).max() > 1e-2 * np.abs(want).max()


def test_a_reused_slot_and_a_readmitted_sequence_carry_nothing_over():
    model = program()
    ids = ids_of(3, 20, seed=6)
    want = ref_logits(ids)
    c = Cached(model, rows=2, slots=1)  # one slot: every sequence takes it
    close(c.run(0, ids[0], chunk=8, decode_from=11), want[0])
    slot = c.pool.slot(0)
    c.forget(0)
    # a second sequence in the slot the first has left its state in
    close(c.run(1, ids[1], chunk=8, decode_from=9), want[1])
    assert c.pool.slot(1) == slot
    # a sequence evicted mid-generation and admitted again: its blocks and
    # its slot go, it is prefilled again over all it had, and decodes on
    c.forget(1)
    c.run(2, ids[2][:14], chunk=8, decode_from=10)
    c.forget(2)
    close(c.run(2, ids[2], chunk=8, decode_from=14), want[2])
    assert c.pool.accounting()["leaked_slots"] == 1  # sequence 2 holds it
    c.forget(2)
    assert c.pool.accounting()["leaked_slots"] == 0


# -- through the engine --------------------------------------------------------

def engine(model, **kw):
    defaults = dict(capacity=16, decode_buckets=(1, 2, 4), prefill_chunk=8,
                    kv_blocks=48, kv_block_size=4, max_seq_len=96)
    defaults.update(kw)
    return TokenServingEngine(model, TokenServeConfig(**defaults))


def assert_greedy(model, prompt, emitted):
    """Every emitted token is the full forward's first choice at its
    position (one whole-sequence forward over prompt + emitted; a token
    within rounding of the best counts as the best)."""
    toks = np.concatenate([prompt, np.asarray(emitted, np.int32)])
    logits = model(paddle.to_tensor(toks[None, :-1])).numpy()[0]
    at = logits[len(prompt) - 1:]
    picked = at[np.arange(len(emitted)), np.asarray(emitted)]
    assert (at.max(-1) - picked).max() <= 1e-5 * np.abs(logits).max()


def test_the_engine_serves_it_greedy_like_the_full_forward():
    get_telemetry().reset()
    model = program()
    eng = engine(model).start(warmup=False)
    try:
        prompts = [ids_of(1, n, seed=20 + n)[0] for n in (5, 13, 21)]
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        for r, p in zip(reqs, prompts):
            assert r.wait(300.0) and r.status == "ok"
            assert len(r.outputs[0]) == 6
            assert_greedy(model, p, r.outputs[0])
        tel = get_telemetry()
        assert tel.counter_value("serve/state_resets") == 3
        gauges = tel.snapshot()["gauges"]
        assert gauges["serve/state_slots_total"] == 4
        assert gauges["ssm/form.step"] == 1 and gauges["ssm/form.chunked"] == 1
        assert gauges["ssm/chunk"] == CONFIG["mamba_chunk_size"]
    finally:
        eng.shutdown()
    assert eng.kv_accounting()["leaked_slots"] == 0
    assert eng.kv_accounting()["leaked_blocks"] == 0


def test_eviction_starts_the_state_again_and_keeps_parity():
    get_telemetry().reset()
    model = program()
    # 11 usable blocks of 4: two sequences of 16 + 12 do not both fit
    eng = engine(model, kv_blocks=12, max_seq_len=40,
                 decode_buckets=(1, 2)).start(warmup=False)
    try:
        prompts = [ids_of(1, 16, seed=31)[0], ids_of(1, 14, seed=32)[0]]
        reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
        for r, p in zip(reqs, prompts):
            assert r.wait(600.0) and r.status == "ok"
            assert len(r.outputs[0]) == 12
            assert_greedy(model, p, r.outputs[0])
        assert get_telemetry().counter_value("serve/kv_evictions") >= 1
        assert get_telemetry().counter_value("serve/state_resets") >= 3
    finally:
        eng.shutdown()
    assert eng.kv_accounting()["leaked_slots"] == 0


def test_speculation_over_recurrent_state_is_refused():
    model = program()
    with pytest.raises(ValueError, match="recurrent state"):
        engine(model, spec_k=2)
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

    gpt = GPTForCausalLM(GPTConfig(
        vocab_size=96, hidden_size=32, num_layers=1, num_heads=2,
        max_position_embeddings=128, hidden_dropout=0.0,
        attention_dropout=0.0))
    with pytest.raises(ValueError, match="recurrent state"):
        TokenServingEngine(gpt, TokenServeConfig(spec_k=2, kv_blocks=48),
                           draft_model=model)


def test_the_tiny_preset_builds_and_runs():
    paddle.seed(0)
    from paddle_tpu.text.models.falcon_h1 import FalconH1ForCausalLM

    model = FalconH1ForCausalLM(falcon_h1_tiny())
    model.eval()
    out = model(paddle.to_tensor(ids_of(1, 9, vocab=256)))
    assert out.shape == [1, 9, 256] and np.isfinite(out.numpy()).all()
    assert KVCacheConfig(2, 4, 8, num_kv_heads=2).num_kv_heads == 2
