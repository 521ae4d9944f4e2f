"""LongCat-Flash against its plain reference (``benchmark/reference/
longcat_flash.py``) on seeded random weights, at a small size on the CPU:
the eager forward and a double layer, chunked prefill and decode through
the latent pages (two rows a double layer), the shares of the shortcut
expert layer adding up to the uncut double layer, a router forced onto the
identity experts, the double layer's topology and both LoRA scales held by
the agreement, the served step's counts against a hand count, and
openPangu's served entry unchanged by the cached MLA both models now call.

Tolerances. Both sides are float32 on the CPU and part by the order of
their sums. Gaps are read relative to the reference's largest logit:
``close`` holds 1e-4 of it (measured: under 1e-6) and fails everything the
tests plant (the smallest planted change moves the logits by 1e-2 of it or
more). A double layer alone is held to 1e-5 (no head to amplify it).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.families import longcat_flash as family
from benchmark.families import longcat_flash_serve as serve_family
from benchmark.reference import common
from benchmark.reference import longcat_flash as reference
from paddle_tpu.incubate.moe import (held_experts_part, held_load,
                                     identity_experts_part)
from paddle_tpu.inference.serving import (KVCachePool, TokenServeConfig,
                                          TokenServingEngine)
from paddle_tpu.inference.serving.decode import _pool_config
from paddle_tpu.jit.functionalize import get_params
from paddle_tpu.profiler.telemetry import get_telemetry
from paddle_tpu.text.models.longcat_flash import (LongcatFlashForCausalLM,
                                                  longcat_flash_tiny,
                                                  shortcut_moe)

EINSUM = common.make_einsum("float32")
SEED = 11

# the reference's configuration of a tiny model: two double layers, 4
# heads, 8 routed experts of which 4 held and 4 identity ones, 3 a token
CONFIG = {
    "hidden_size": 64, "ffn_hidden_size": 128, "expert_ffn_hidden_size": 32,
    "num_layers": 2, "num_attention_heads": 4, "q_lora_rank": 24,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "n_routed_experts": 4, "zero_expert_num": 4, "moe_topk": 3,
    "routed_scaling_factor": 6.0,
    "vocab_size": 96, "max_position_embeddings": 512, "rms_norm_eps": 1e-5,
    "rope_theta": 10000000,
    "deployment": {"n_routed_experts_published": 8,
                   "vocab_size_published": 96 * 8,
                   "num_layers_published": 8},
    "assumed": {"initializer_range": 0.08, "max_seq_len": 96},
}
UNCUT = {**CONFIG, "n_routed_experts": 8}
# the rows and length of every eager forward: the eager operations of one
# shape compile once for the whole file
SHAPE = (2, 21)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


_MAKERS = {}  # the leaves' shapes and laws -> one compiled maker of them


def ref_params(config=CONFIG, seed=SEED):
    """``common.init_params``, compiled once for every seed of a shape."""
    specs = reference.param_specs(config)
    key = repr(sorted(specs.items()))
    if key not in _MAKERS:
        _MAKERS[key] = jax.jit(lambda k: {
            n: common.init_leaf(specs, n, k) for n in specs})
    return _MAKERS[key](common.seed_key(seed))


def program(config=CONFIG, seed=SEED):
    """The model with the reference's weights of ``seed``: the harness's
    ``weights`` makes the same leaves (the first test holds it)."""
    names = family.names_of(config)
    return serve_family.build_model(
        config, {names[n]: v for n, v in ref_params(config, seed).items()})


def ref_logits(ids, config=CONFIG, seed=SEED, params=None):
    return np.asarray(reference.logits(
        params or ref_params(config, seed), jnp.asarray(ids), config, EINSUM))


def ids_of(rows, length, seed=0, vocab=CONFIG["vocab_size"]):
    return np.random.default_rng(seed).integers(
        0, vocab, (rows, length)).astype(np.int32)


def close(got, want, tol=1e-4):
    scale = float(np.abs(want).max())
    np.testing.assert_allclose(got, want, atol=tol * scale, rtol=0)


def far(got, want, by=1e-2):
    return np.abs(got - want).max() > by * np.abs(want).max()


def layer_params(p, i):
    return {n: p[f"l{i}_{n}"] for n in reference.LAYER_LEAVES}


# -- the eager model against the reference ---------------------------------------

def test_the_harness_names_every_parameter_of_the_program():
    made = serve_family.weights(CONFIG, SEED, "float32")
    model = serve_family.build_model(CONFIG, made)
    names = family.names_of(CONFIG)
    assert set(names.values()) == set(get_params(model)) == set(made)
    assert set(names) == set(reference.param_specs(CONFIG))
    # the harness's weights are the reference's, leaf for leaf
    for n, v in ref_params().items():
        np.testing.assert_array_equal(np.asarray(made[names[n]]),
                                      np.asarray(v))
    assert model.config.router_width == 12
    assert model.config.experts_held == range(4)


@pytest.mark.parametrize("layer", [0, 1])
def test_one_double_layer_matches_the_reference(layer):
    model = program()
    x = jnp.asarray(np.random.default_rng(layer).standard_normal(
        (*SHAPE, CONFIG["hidden_size"])).astype(np.float32))
    want = reference.block(layer_params(ref_params(), layer), x, CONFIG,
                           EINSUM)
    got = model.model.layers[layer](paddle.to_tensor(np.asarray(x))).numpy()
    close(np.asarray(got), np.asarray(want), tol=1e-5)


@pytest.mark.parametrize("seed", [11, 12, 13, 14])
def test_the_eager_forward_matches_the_reference(seed):
    ids = ids_of(*SHAPE, seed=seed)
    model = program(seed=seed)
    close(model(paddle.to_tensor(ids)).numpy(), ref_logits(ids, seed=seed))


def test_the_tiny_preset_builds_and_runs():
    paddle.seed(0)
    model = LongcatFlashForCausalLM(longcat_flash_tiny())
    model.eval()
    out = model(paddle.to_tensor(ids_of(*SHAPE, vocab=256)))
    assert out.shape == [*SHAPE, 256] and np.isfinite(out.numpy()).all()
    c = model.config
    assert (c.router_width, c.moe_topk, c.layers_held) == (12, 3, 2)
    assert c.q_scale == pytest.approx((64 / 24) ** 0.5)
    assert c.kv_scale == pytest.approx(2 ** 0.5)


# -- what the reference's agreement holds ------------------------------------------

def _variant_logits(ids, p, topology):
    """The reference's forward with the double layer wired otherwise:
    ``moe_from_a2`` feeds the experts the second half's normed residual,
    ``m_before_attn2`` adds their output before the second attention."""
    z = reference.sizes(CONFIG)
    eps = z["eps"]
    x = p["embed"][jnp.asarray(ids)]
    for i in range(CONFIG["num_layers"]):
        q = layer_params(p, i)
        a0, a1 = reference.sub(q, "attn0_"), reference.sub(q, "attn1_")
        x = x + reference.attention(a0, reference.rms_norm(
            x, a0["attn_norm"], eps), z, EINSUM)
        g = reference.rms_norm(x, a0["post_attn_norm"], eps)
        x = x + reference.ffn(q, 0, g, EINSUM)
        if topology == "m_before_attn2":
            x = x + reference.moe(g, q, z, EINSUM)
        x = x + reference.attention(a1, reference.rms_norm(
            x, a1["attn_norm"], eps), z, EINSUM)
        g2 = reference.rms_norm(x, a1["post_attn_norm"], eps)
        x = x + reference.ffn(q, 1, g2, EINSUM)
        if topology == "moe_from_a2":
            x = x + reference.moe(
                reference.rms_norm(x - reference.ffn(q, 1, g2, EINSUM),
                                   a1["post_attn_norm"], eps), q, z, EINSUM)
    return np.asarray(reference.head(p["final_norm"], p["head_w"], x,
                                     CONFIG, EINSUM))


@pytest.mark.parametrize("topology", ["moe_from_a2", "m_before_attn2"])
def test_the_double_layers_topology_is_the_published_one(topology):
    """A reference that feeds the experts from a2, or adds their output
    before the second attention, reads off the program by far; the
    published wiring reads on it."""
    ids = ids_of(*SHAPE, seed=3)
    p = ref_params()
    got = program()(paddle.to_tensor(ids)).numpy()
    close(got, ref_logits(ids, params=p))
    assert far(got, _variant_logits(ids, p, topology))


@pytest.mark.parametrize("scale", ["mla_scale_q_lora", "mla_scale_kv_lora"])
def test_each_lora_scale_is_held(scale):
    """The reference without either scale reads off the program by far;
    a program built without it reads on that reference."""
    ids = ids_of(*SHAPE, seed=4)
    dropped = {**CONFIG, scale: False}
    want = ref_logits(ids, config=dropped)
    assert far(program()(paddle.to_tensor(ids)).numpy(), want)
    close(program(dropped)(paddle.to_tensor(ids)).numpy(), want)


@pytest.mark.parametrize("part", ["routed_scale", "identity_experts"])
def test_the_expert_layers_parts_matter(part):
    ids = ids_of(*SHAPE, seed=2)
    base = ref_logits(ids)
    config = ({**CONFIG, "routed_scaling_factor": 1.0}
              if part == "routed_scale" else {**CONFIG, "zero_expert_num": 1})
    want = ref_logits(ids, config=config)
    assert far(base, want, by=1e-3)
    close(program(config)(paddle.to_tensor(ids)).numpy(), want)


# -- the share ties to the model ----------------------------------------------------

@pytest.mark.parametrize("seed,rows", [(21, 1), (22, 2), (23, 2)])
def test_the_shares_add_up_to_the_uncut_double_layer(seed, rows,
                                                     monkeypatch):
    """Two chips hold four of the eight routed experts each. The double
    layer with the expert branch's held parts of both chips, its identity
    part and the dense path counted once is the uncut reference's double
    layer."""
    z = reference.sizes(UNCUT)
    p = layer_params(ref_params(UNCUT, seed), 0)
    x = jnp.asarray(np.random.default_rng(seed).standard_normal(
        (rows, SHAPE[1], z["h"])).astype(np.float32))
    want = reference.block(p, x, UNCUT, EINSUM)

    def shared_moe(g, q, zz, einsum, experts=None):
        flat = g.reshape(-1, z["h"])
        chosen, weights = reference.route(g, q["router_w"], z, einsum)
        chosen, weights = (chosen.reshape(flat.shape[0], -1),
                           weights.reshape(flat.shape[0], -1))
        total, _ = identity_experts_part(flat, chosen, weights, z["routed"])
        pairs = 0
        for first in range(0, z["routed"], 4):
            part, _ = held_experts_part(
                flat, chosen, weights, q["e_gate_w"][first:first + 4],
                q["e_up_w"][first:first + 4], q["e_down_w"][first:first + 4],
                first)
            total = total + part
            pairs += int(held_load(chosen, first, 4)[1])
        zero = int(identity_experts_part(flat, chosen, weights,
                                         z["routed"])[1])
        # every pair lies on one chip or is an identity expert's
        assert pairs + zero == flat.shape[0] * z["top_k"]
        return total.reshape(g.shape)

    monkeypatch.setattr(reference, "moe", shared_moe)
    got = reference.block(p, x, UNCUT, EINSUM)
    close(np.asarray(got), np.asarray(want), tol=1e-5)


@pytest.mark.parametrize("tokens", [1, 7, 16, 64])
def test_a_router_forced_onto_identity_experts(tokens):
    """With the selection bias on the identity columns alone every chosen
    expert is an identity one: the layer gives 6 * (sum of the chosen p) *
    g, no held expert is hit and every pair is counted an identity pair."""
    cfg = longcat_flash_tiny(experts_held=range(4))
    rng = np.random.default_rng(tokens)
    g = jnp.asarray(rng.standard_normal((tokens, 64)).astype(np.float32))
    router = jnp.asarray(rng.standard_normal((64, 12)).astype(np.float32))
    bias = jnp.concatenate([jnp.zeros(8), jnp.full(4, 10.0)])
    stacks = [jnp.asarray(rng.standard_normal(s).astype(np.float32))
              for s in ((4, 64, 32), (4, 64, 32), (4, 32, 64))]
    y, counts = shortcut_moe(cfg, g, router, bias, *stacks)
    p = jax.nn.softmax(g @ router, axis=-1)
    top3 = jnp.sort(p[:, 8:], axis=-1)[:, 1:].sum(-1)
    close(np.asarray(y), np.asarray(6.0 * top3[:, None] * g), tol=1e-6)
    assert np.asarray(counts).tolist() == [0, 0, 3 * tokens, tokens]


def test_the_identity_part_from_a_known_routing():
    x = jnp.asarray([[1.0, 2.0], [3.0, -1.0], [0.5, 0.5]])
    chosen = jnp.asarray([[0, 8, 9], [8, 2, 11], [-1, -1, -1]])
    weights = jnp.asarray([[0.5, 0.25, 1.0], [2.0, 3.0, 0.5],
                           [1.0, 1.0, 1.0]])
    y, pairs = identity_experts_part(x, chosen, weights, 8)
    np.testing.assert_allclose(np.asarray(y), [[1.25, 2.5], [7.5, -2.5],
                                               [0.0, 0.0]])
    assert int(pairs) == 4
    # and the held experts' part takes none of the identity ids
    w = jnp.ones((4, 2, 3))
    part, stats = held_experts_part(x, chosen, weights, w, w,
                                    jnp.ones((4, 3, 2)), 0)
    assert float(stats[0]) == pytest.approx(2 / 9)  # experts 0 and 2 only
    assert held_load(chosen, 0, 4).tolist() == [2, 2]


# -- prefill in chunks, then decode, through the latent pages ------------------------

class Cached:
    """The model's own cached forward over a pool, driven by hand: rows of
    a bucket, each a sequence with its blocks. One compiled forward serves
    every model of the configuration: the weights are an argument."""

    _fwd = {}

    def __init__(self, model, rows, blocks=40, block=4, width=12):
        spec = model.decode_spec("float32")
        self.pool = KVCachePool(_pool_config(spec, blocks, block, "float32",
                                             state_slots=0))
        key = (model.config.layers_held, model.config.mla_scale_q_lora,
               model.config.mla_scale_kv_lora)
        if key not in Cached._fwd:
            Cached._fwd[key] = jax.jit(spec["forward_chunk"])
        self.fwd = Cached._fwd[key]
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
        for row, (owner, new) in enumerate(feeds.items()):
            have = self.n.get(owner, 0)
            assert self.pool.ensure(owner, have + len(new))
            toks[row, :len(new)] = new
            qpos[row] = have + np.arange(T)
            lens[row] = have + len(new)
            tables[row] = self.pool.block_table(owner, self.width)
            self.n[owner] = have + len(new)
        logits, self.pool.pages = self.fwd(
            self.params, *map(jnp.asarray, (toks, qpos)), self.pool.pages,
            *map(jnp.asarray, (tables, lens)), jnp.zeros((self.rows,),
                                                         jnp.int32))
        return {owner: np.asarray(logits)[row, :len(new)]
                for row, (owner, new) in enumerate(feeds.items())}

    def run(self, owner, ids, chunk, decode_from):
        out = []
        for lo in range(0, decode_from, chunk):
            out.append(self.feed({owner: ids[lo:min(lo + chunk,
                                                   decode_from)]},
                                 chunk)[owner])
        for t in range(decode_from, len(ids)):
            out.append(self.feed({owner: ids[t:t + 1]}, 1)[owner])
        return np.concatenate(out)


@pytest.mark.parametrize("seed", [11, 12, 13, 14, 15])
@pytest.mark.parametrize("chunk,decode_from", [(8, 13), (4, 4), (16, 30)])
def test_chunked_prefill_then_decode_matches_the_full_forward(
        seed, chunk, decode_from):
    ids = ids_of(1, 36, seed=seed)
    want = ref_logits(ids, seed=seed)[0]
    got = Cached(program(seed=seed), rows=2).run(0, ids[0], chunk,
                                                 decode_from)
    close(got, want)


@pytest.mark.parametrize("expand_from", [1000, 8])
def test_rows_of_unequal_length_in_one_bucket(expand_from, monkeypatch):
    """Two sequences in a bucket of four rows, prefilled in chunks of 8
    (13 = 8 + 5, 21 = 8 + 8 + 5) and decoded side by side with two padded
    rows, against the reference at every position; the chunks through the
    absorbed form, and through the expanded one (a group of 8 cached rows,
    so that a walk is several): both meet the scaled latent."""
    from paddle_tpu.ops import attention as att

    monkeypatch.setattr(att, "_MLA_EXPAND_MIN_T", expand_from)
    monkeypatch.setattr(att, "_MLA_GROUP", dict.fromkeys(att._MLA_GROUP, 8))
    ids = ids_of(2, 30, seed=5)
    want = ref_logits(ids)
    model = program()
    c = Cached(model, rows=4)
    # the patched rule is traced anew, not taken from another test's trace
    c.fwd = jax.jit(model.decode_spec("float32")["forward_chunk"])
    got = {0: [], 1: []}
    for owner, n in ((0, 13), (1, 21)):
        for lo in range(0, n, 8):
            got[owner].append(c.feed({owner: ids[owner, lo:min(lo + 8, n)]},
                                     8)[owner])
    for t in range(13, 22):
        step = c.feed({0: ids[0, t:t + 1], 1: ids[1, t + 8:t + 9]}, 1)
        got[0].append(step[0])
        got[1].append(step[1])
    close(np.concatenate(got[0]), want[0, :22])
    close(np.concatenate(got[1]), want[1, :30])


@pytest.mark.parametrize("walk_rows", [1, 3])
def test_rows_walked_in_groups_by_length_match_the_reference(walk_rows,
                                                             monkeypatch):
    """The served step's latent walks in groups of ``WALK_ROWS`` rows
    sorted by length (32 in a served bucket of 128; patched to 1 and 3
    here, so that a bucket of four rows, two of them padding, walks in
    groups): two sequences of unequal length prefilled and decoded side by
    side read the reference at every position."""
    from paddle_tpu.text.models import longcat_flash as lf

    monkeypatch.setattr(lf, "WALK_ROWS", walk_rows)
    ids = ids_of(2, 30, seed=6)
    want = ref_logits(ids)
    model = program()
    c = Cached(model, rows=4)
    c.fwd = jax.jit(model.decode_spec("float32")["forward_chunk"])
    got = {0: [], 1: []}
    for owner, n in ((0, 5), (1, 21)):
        for lo in range(0, n, 8):
            got[owner].append(c.feed({owner: ids[owner, lo:min(lo + 8, n)]},
                                     8)[owner])
    for t in range(5, 14):
        step = c.feed({0: ids[0, t:t + 1], 1: ids[1, t + 16:t + 17]}, 1)
        got[0].append(step[0])
        got[1].append(step[1])
    close(np.concatenate(got[0]), want[0, :14])
    close(np.concatenate(got[1]), want[1, :30])


def test_the_pool_holds_two_latent_rows_a_token_and_double_layer():
    spec = program().decode_spec("bfloat16")
    assert spec["kv_layout"] == "latent" and spec["head_dim"] == 128
    assert spec["num_layers"] == 4  # two double layers of two attentions
    assert "draft" not in spec
    pool = KVCachePool(_pool_config(spec, 12, 4, "bfloat16", state_slots=0))
    assert set(pool.pages) == {"latent", "moe_counts"}
    assert [a.shape for a in pool.pages["latent"]] == [(12, 4, 128)] * 4
    np.testing.assert_array_equal(pool.read_counters()["moe_counts"],
                                  np.zeros((2, 5)))
    with pytest.raises(ValueError, match="int8"):
        program().decode_spec("int8")


@pytest.mark.parametrize("seed", [31, 32, 33])
def test_the_served_step_counts_against_a_hand_count(seed):
    """One prefill chunk of two live rows (and two padded ones, routed
    nowhere), the counts against the reference's own routing of the same
    positions: experts hit, pairs held, identity pairs, positions."""
    ids = ids_of(2, 8, seed=seed)
    model = program(seed=seed)
    c = Cached(model, rows=4)
    c.feed({0: ids[0, :8], 1: ids[1, :5]}, 8)
    steps, hit, held, zero, positions = \
        c.pool.read_counters()["moe_counts"][1]
    # by hand: the reference's residual stream, layer by layer, and its
    # router's choice at every live position
    z = reference.sizes(CONFIG)
    p = ref_params(seed=seed)
    streams = [p["embed"][jnp.asarray(ids[row:row + 1, :n])]
               for row, n in ((0, 8), (1, 5))]
    want = np.zeros(4, np.int64)
    for i in range(CONFIG["num_layers"]):
        q = layer_params(p, i)
        a0 = reference.sub(q, "attn0_")
        chosen = []
        for j, x in enumerate(streams):
            a = x + reference.attention(a0, reference.rms_norm(
                x, a0["attn_norm"], z["eps"]), z, EINSUM)
            g = reference.rms_norm(a, a0["post_attn_norm"], z["eps"])
            chosen.append(np.asarray(reference.route(
                g, q["router_w"], z, EINSUM)[0]).reshape(-1))
            streams[j] = reference.block(q, x, CONFIG, EINSUM)
        chosen = np.concatenate(chosen)
        mine = chosen[chosen < z["held"]]
        want += [len(set(mine.tolist())), len(mine),
                 np.sum(chosen >= z["routed"]), 8 + 5]
    assert steps == 2
    assert (hit, held, zero, positions) == tuple(want)


def test_a_decode_step_counts_apart_from_a_chunk():
    model = program()
    ids = ids_of(2, 12, seed=8)
    c = Cached(model, rows=4)
    c.feed({0: ids[0, :8]}, 8)
    c.feed({1: ids[1, :8]}, 8)
    counts = c.pool.read_counters()["moe_counts"]
    assert counts[0].tolist() == [0] * 5 and counts[1][0] == 4
    assert counts[1][4] == 2 * 16  # 16 positions, two double layers
    for t in range(8, 11):
        c.feed({0: ids[0, t:t + 1], 1: ids[1, t:t + 1]}, 1)
    steps, hit, held, zero, positions = \
        c.pool.read_counters()["moe_counts"][0]
    assert steps == 6 and positions == 3 * 2 * 2
    assert held + zero <= positions * 3 and 0 < hit <= held
    c.feed({}, 1)  # padded rows alone: nothing counts
    assert c.pool.read_counters()["moe_counts"][0].tolist()[0] == steps


# -- through the engine --------------------------------------------------------------

def test_the_engine_serves_it_greedy_and_publishes_its_counts():
    get_telemetry().reset()
    model = program()
    prompts = [ids_of(1, n, seed=20 + n)[0] for n in (5, 13, 21)]
    eng = TokenServingEngine(model, TokenServeConfig(
        capacity=16, decode_buckets=(1, 2, 4), prefill_chunk=8,
        kv_blocks=48, kv_block_size=4, max_seq_len=96)).start(warmup=False)
    try:
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        for r in reqs:
            assert r.wait(600.0) and r.status == "ok"
    finally:
        eng.shutdown()
    # every emitted token is the first choice of the model's own cached
    # forward at its position (a token within rounding of the best counts)
    for p, r in zip(prompts, reqs):
        out = np.asarray(r.outputs[0], np.int32)
        toks = np.concatenate([p, out])
        logits = Cached(model, rows=2).run(0, toks[:-1], 8, len(p))
        at = logits[len(p) - 1:]
        picked = at[np.arange(len(out)), out]
        assert len(out) == 6
        assert (at.max(-1) - picked).max() <= 1e-5 * np.abs(logits).max()
    tel = get_telemetry()
    gauges = tel.snapshot()["gauges"]
    assert gauges["moe/experts_held"] == 4 and gauges["moe/zero_experts"] == 4
    steps = tel.counter_value("moe/layer_steps.decode")
    assert steps == 2 * tel.counter_value("serve/decode_steps")
    decode_positions = tel.counter_value("moe/positions.decode")
    assert decode_positions == 2 * sum(len(r.outputs[0]) - 1 for r in reqs)
    zero = tel.counter_value("moe/pairs_zero.decode")
    assert 0 < zero < 3 * decode_positions
    assert serve_family.experts_hit() == pytest.approx(
        tel.counter_value("moe/experts_hit.decode") / steps)
    assert eng.kv_accounting()["leaked_blocks"] == 0


# -- openPangu's served entry is the program it was ---------------------------------

PANGU = {
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 4, "first_k_dense_replace": 2,
    "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "n_routed_experts": 4, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5, "vocab_size": 96,
    "max_position_embeddings": 512, "rms_norm_eps": 1e-5,
    "rope_theta": 25600000, "num_nextn_predict_layers": 1,
    "deployment": {"n_routed_experts_published": 8,
                   "vocab_size_published": 96 * 8,
                   "num_hidden_layers_published": 12,
                   "first_k_dense_replace_published": 3},
    "assumed": {"initializer_range": 0.02, "max_seq_len": 96},
}
FIXTURE = os.path.join(os.path.dirname(__file__), "mla_fixtures",
                       "pangu_tiny_chunk_then_decode_f32.npy")


@pytest.fixture(scope="module")
def pangu_logits():
    """openPangu's tiny served entry over a chunk of 8 and a decode step,
    four rows, as ``mla_fixtures/pangu_tiny_chunk_then_decode_f32.npy``
    holds them from the program before its cached MLA became
    ``pangu_ultra_moe.cached_mla``."""
    from benchmark.families import pangu_ultra_moe_serve as pangu

    with jax.default_matmul_precision("highest"):
        model = pangu.build_model(PANGU, pangu.weights(PANGU, 11, "float32"))
        spec = model.decode_spec("float32")
        pool = KVCachePool(_pool_config(spec, 40, 4, "float32", 0))
        params, fwd = get_params(model), jax.jit(spec["forward_chunk"])
        rng = np.random.default_rng(0)
        tables = jnp.asarray(np.arange(1, 49, dtype=np.int32).reshape(4, 12)
                             % 40)
        out = {}
        for kind, T, start in (("chunk", 8, 0), ("decode", 1, 8)):
            toks = jnp.asarray(rng.integers(0, 96, (4, T)).astype(np.int32))
            qpos = jnp.asarray(np.tile(np.arange(T, dtype=np.int32) + start,
                                       (4, 1)))
            lens = jnp.full((4,), start + T, jnp.int32)
            logits, pool.pages = fwd(params, toks, qpos, pool.pages, tables,
                                     lens, jnp.zeros((4,), jnp.int32))
            out[kind] = np.asarray(logits).reshape(-1)
    return out


@pytest.mark.parametrize("kind", ["chunk", "decode"])
def test_openpangus_served_output_is_unchanged_by_the_shared_mla(
        pangu_logits, kind):
    before = np.load(FIXTURE)
    before = {"chunk": before[:4 * 8 * 96], "decode": before[4 * 8 * 96:]}
    close(pangu_logits[kind], before[kind], tol=1e-6)
