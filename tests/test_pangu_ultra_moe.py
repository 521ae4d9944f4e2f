"""openPangu-Ultra-MoE against its plain reference (``benchmark/reference/
pangu_ultra_moe.py``) on seeded random weights, at a small size on the CPU:
the eager forward and the next-token module's logits, chunked prefill and
decode through the latent pages (rows of unequal length in one bucket, a
sequence evicted and admitted again, a block reused), the absorbed form of
latent attention against the expanded one on one cache, the experts'
shares adding up to the uncut layer, the served step's counts, and the
scheduler drafting from the model's own module.

Tolerances. Both sides are float32 on the CPU and part by the order of
their sums. Gaps are read relative to the reference's largest logit:
``close`` holds 1e-4 of it (measured: under 1e-6) and fails everything the
tests plant.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.families import pangu_ultra_moe as family
from benchmark.families import pangu_ultra_moe_serve as serve_family
from benchmark.reference import common
from benchmark.reference import pangu_ultra_moe as reference
from paddle_tpu.incubate.moe import held_load
from paddle_tpu.inference.serving import (KVCacheConfig, KVCachePool,
                                          TokenServeConfig,
                                          TokenServingEngine)
from paddle_tpu.inference.serving.decode import _pool_config
from paddle_tpu.jit.functionalize import get_params
from paddle_tpu.ops import attention as att
from paddle_tpu.profiler.telemetry import get_telemetry
from paddle_tpu.text.models.pangu_ultra_moe import (
    PanguUltraMoEForCausalLM, pangu_ultra_moe_tiny)

EINSUM = common.make_einsum("float32")
SEED = 11

# the reference's configuration of a tiny model: two dense layers and two
# expert layers, 4 heads, 8 routed experts of which 2 a token and 4 held
CONFIG = {
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


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def program(config=CONFIG, seed=SEED, nextn=True):
    return serve_family.build_model(
        config, serve_family.weights(config, seed, "float32", nextn=nextn))


def ref_params(config=CONFIG, seed=SEED, nextn=True):
    return common.init_params(reference.param_specs(config, nextn), seed)


def ref_logits(ids, config=CONFIG, seed=SEED):
    return np.asarray(reference.logits(ref_params(config, seed),
                                       jnp.asarray(ids), config, EINSUM))


def ids_of(rows, length, seed=0, vocab=CONFIG["vocab_size"]):
    return np.random.default_rng(seed).integers(
        0, vocab, (rows, length)).astype(np.int32)


def close(got, want, tol=1e-4):
    scale = float(np.abs(want).max())
    np.testing.assert_allclose(got, want, atol=tol * scale, rtol=0)


# -- (a) the eager model against the reference ---------------------------------

@pytest.mark.parametrize("nextn", [False, True])
def test_the_harness_names_every_parameter_of_the_program(nextn):
    model = program(nextn=nextn)
    names = family.names_of(CONFIG, nextn)
    assert set(names.values()) == set(get_params(model))
    assert set(names) == set(reference.param_specs(CONFIG, nextn))
    assert model.config.nextn_held == int(nextn)


@pytest.mark.parametrize("layer,kind", [(0, "dense"), (3, "moe")])
def test_one_block_matches_the_reference(layer, kind):
    model = program()
    p = ref_params()
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (2, 19, CONFIG["hidden_size"])).astype(np.float32))
    want = reference.block(
        {n: p[f"l{layer}_{n}"] for n in reference.layer_leaves(kind)}, x,
        kind, CONFIG, EINSUM)
    got = model.model.layers[layer](paddle.to_tensor(np.asarray(x))).numpy()
    close(np.asarray(got), np.asarray(want), tol=1e-5)


@pytest.mark.parametrize("which", ["logits", "nextn_logits"])
def test_the_eager_forward_matches_the_reference(which):
    ids = ids_of(2, 37)
    model = program()
    if which == "logits":
        got, want = model(paddle.to_tensor(ids)).numpy(), ref_logits(ids)
    else:
        got = model.nextn_logits(paddle.to_tensor(ids)).numpy()
        want = np.asarray(reference.nextn_logits(
            ref_params(), jnp.asarray(ids), CONFIG, EINSUM))
        assert want.shape == (2, 36, CONFIG["vocab_size"])
    close(got, want)


def test_a_share_without_the_module_refuses_its_logits():
    with pytest.raises(ValueError, match="no next-token module"):
        program(nextn=False).nextn_logits(paddle.to_tensor(ids_of(1, 5)))


@pytest.mark.parametrize("part", ["sandwich", "rotation", "query_norm",
                                  "routed_scale"])
def test_each_part_matters_and_stands_where_the_reference_has_it(part):
    """With one part altered in the reference's weights or configuration
    the logits move, so the agreement above holds each of them."""
    ids = ids_of(1, 13, seed=2)
    base = ref_logits(ids)
    p, config = dict(ref_params()), dict(CONFIG)
    if part == "sandwich":
        p["l1_post_attn_norm"] = p["l1_post_attn_norm"] * 2.0
    elif part == "rotation":
        config["rope_theta"] = 100.0
    elif part == "query_norm":
        p["l0_q_a_norm"] = p["l0_q_a_norm"].at[::2].set(3.0)
    else:
        config["routed_scaling_factor"] = 1.0
    want = np.asarray(reference.logits(p, jnp.asarray(ids), config, EINSUM))
    assert np.abs(want - base).max() > 1e-3 * np.abs(base).max()
    names = family.names_of(config, True)
    model = serve_family.build_model(
        config, {names[n]: v for n, v in p.items()})
    close(model(paddle.to_tensor(ids)).numpy(), want)


def test_the_tiny_preset_builds_and_runs():
    paddle.seed(0)
    model = PanguUltraMoEForCausalLM(pangu_ultra_moe_tiny())
    model.eval()
    out = model(paddle.to_tensor(ids_of(1, 9, vocab=256)))
    assert out.shape == [1, 9, 256] and np.isfinite(out.numpy()).all()
    assert model.config.layer_kinds == ["dense", "moe", "moe"]


# -- (d) the share ties to the model --------------------------------------------

def test_the_experts_shares_add_up_to_the_uncut_layer():
    """Two chips hold four of the eight routed experts each. The parts
    their held experts give, with the shared expert (which every chip
    computes alike) counted once, add up to the uncut reference's layer."""
    from paddle_tpu.incubate.moe import held_experts_part, route_top_k

    z = reference.sizes(CONFIG)
    uncut = {**CONFIG, "n_routed_experts": z["routed"]}
    specs = reference.param_specs(uncut)
    p = common.init_params(specs, SEED)
    x = jnp.asarray(np.random.default_rng(3).standard_normal(
        (2, 11, z["h"])).astype(np.float32))
    experts = {n: p[f"l2_e_{n}"] for n in ("gate_w", "up_w", "down_w")}
    shared = {n: p[f"l2_s_{n}"] for n in ("gate_w", "up_w", "down_w")}
    want = reference.uncut_moe(x, p["l2_router_w"], experts, shared,
                               reference.sizes(uncut), EINSUM)
    flat = x.reshape(-1, z["h"])
    chosen, weights = route_top_k(
        jax.nn.sigmoid(flat @ p["l2_router_w"]), jnp.zeros(z["routed"]),
        z["top_k"], z["scale"], True)
    total = reference.gated_mlp(x, shared["gate_w"], shared["up_w"],
                                shared["down_w"], EINSUM).reshape(flat.shape)
    hit = pairs = 0
    for first in range(0, z["routed"], z["held"]):
        held = slice(first, first + z["held"])
        part, _ = held_experts_part(
            flat, chosen, weights, experts["gate_w"][held],
            experts["up_w"][held], experts["down_w"][held], first)
        total = total + part
        load = np.asarray(held_load(chosen, first, z["held"]))
        hit, pairs = hit + load[0], pairs + load[1]
    close(np.asarray(total).reshape(want.shape), np.asarray(want), tol=1e-5)
    # every pair lies on one chip, and every expert that got one is hit
    assert pairs == flat.shape[0] * z["top_k"]
    assert hit == len(np.unique(np.asarray(chosen)))


def test_held_load_counts_from_a_known_routing():
    chosen = jnp.asarray([[0, 5], [5, 7], [2, 5], [-1, -1]])
    np.testing.assert_array_equal(held_load(chosen, 4, 4), [2, 4])
    np.testing.assert_array_equal(held_load(chosen, 0, 4), [2, 2])


# -- (c) the two forms of latent attention on one cache --------------------------

def latent_case(B=3, T=5, H=4, nope=16, rope=8, v=16, latent=32, bs=4, M=9,
                lens=(19, 7, 33), seed=4, pad=0):
    """``pad`` columns of a row past the latent and the rotated key hold
    what a pool stores there (zeros) or, to show that nothing reads them
    against a query, anything."""
    rng = np.random.default_rng(seed)
    rows = rng.standard_normal((B, M * bs, latent + rope + pad)).astype(
        np.float32)
    pages = np.zeros((B * M + 1, bs, latent + rope + pad), np.float32)
    tables = np.zeros((B, M), np.int32)
    for b in range(B):
        tables[b] = 1 + b * M + np.arange(M)
        pages[tables[b]] = rows[b].reshape(M, bs, -1)
    lens = np.asarray(lens, np.int32)
    qpos = (lens[:, None] - T + np.arange(T)[None]).astype(np.int32)
    return {
        "q_nope": rng.standard_normal((B, T, H, nope)).astype(np.float32),
        "q_rope": rng.standard_normal((B, T, H, rope)).astype(np.float32),
        "w_kvb": (0.2 * rng.standard_normal(
            (latent, H * (nope + v)))).astype(np.float32),
        "pages": pages, "tables": tables, "qpos": qpos, "lens": lens,
        "rows": rows, "v": v}


def dense_latent(c):
    """Keys and values a head from every cached row, plain softmax."""
    B, T, H, nope = c["q_nope"].shape
    latent = c["w_kvb"].shape[0]
    rope = c["q_rope"].shape[-1]
    out = np.zeros((B, T, H, c["v"]), np.float32)
    w = c["w_kvb"].reshape(latent, H, -1)
    for b in range(B):
        n = c["lens"][b]
        if n == 0:
            continue
        kv = np.einsum("sc,chk->shk", c["rows"][b, :n, :latent], w)
        s = (np.einsum("thn,shn->hts", c["q_nope"][b], kv[..., :nope])
             + np.einsum("thr,sr->hts", c["q_rope"][b],
                         c["rows"][b, :n, latent:latent + rope]))
        s = s / np.sqrt(nope + c["q_rope"].shape[-1])
        seen = np.arange(n)[None, :] <= c["qpos"][b][:, None]
        s = np.where(seen[None], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[b] = np.einsum("hts,shv->thv", p, kv[..., nope:])
    return out


def run_form(c, form, group=None, monkeypatch=None, rows_per_walk=None):
    if group:
        monkeypatch.setattr(att, "_MLA_GROUP", dict.fromkeys(att._MLA_GROUP,
                                                             group))
    return np.asarray(att.mla_paged_attention(
        *map(jnp.asarray, (c["q_nope"], c["q_rope"], c["w_kvb"], c["pages"],
                           c["tables"], c["qpos"], c["lens"])),
        v_dim=c["v"], form=form, rows_per_walk=rows_per_walk))


@pytest.mark.parametrize("group", [8, 16, 512])
@pytest.mark.parametrize("form", ["mla_absorbed", "mla_expanded"])
def test_each_form_matches_dense_latent_attention(form, group, monkeypatch):
    """Groups of 2 and 4 table slots and the whole table in one; the
    longest row's length is no multiple of a group, the table's width (9
    slots) none of the group's slots."""
    c = latent_case()
    np.testing.assert_allclose(run_form(c, form, group, monkeypatch),
                               dense_latent(c), atol=2e-5)


@pytest.mark.parametrize("rows_per_walk", [1, 2, 4, 6])
@pytest.mark.parametrize("T", [1, 3])
@pytest.mark.parametrize("form", ["mla_absorbed", "mla_expanded"])
def test_rows_walked_in_groups_by_length_match_one_walk(
        form, T, rows_per_walk, monkeypatch):
    """Six rows of unequal length, one of them padding (length 0), sorted
    by length and walked in groups of 1, 2 and 4 rows (the last group of
    4 holds two), groups of 2 table slots: the longest group walks 5
    steps, the shortest live row 1. Each live row reads what one walk of all six
    gives it, and the dense attention; 6 rows a walk is one walk."""
    c = latent_case(B=6, T=T, lens=(19, 7, 33, 0, 3, 28), seed=9)
    whole = run_form(c, form, 8, monkeypatch)
    grouped = run_form(c, form, 8, monkeypatch, rows_per_walk)
    live = c["lens"] > 0
    # the same operations on each row; a product over fewer rows may sum
    # in another order on the CPU, which float32 shows in its last digits
    np.testing.assert_allclose(grouped[live], whole[live], rtol=0,
                               atol=1e-6)
    np.testing.assert_allclose(grouped[live], dense_latent(c)[live],
                               atol=2e-5)


@pytest.mark.parametrize("T", [1, 2, 6])
def test_the_absorbed_form_agrees_with_the_expanded_on_one_cache(T):
    c = latent_case(T=T, seed=5)
    np.testing.assert_allclose(run_form(c, "mla_absorbed"),
                               run_form(c, "mla_expanded"), atol=2e-5)


@pytest.mark.parametrize("form", ["mla_absorbed", "mla_expanded"])
def test_a_rows_padding_to_whole_lanes_meets_zeros(form):
    c = latent_case(pad=24, seed=7)
    np.testing.assert_allclose(run_form(c, form), dense_latent(c), atol=2e-5)
    c["pages"] = c["pages"][..., :36]  # no room for the rotated columns
    with pytest.raises(ValueError, match="holds no latent"):
        run_form(c, form)


def test_the_form_is_a_rule_on_the_queries_a_row():
    assert att._mla_form(1) == att._mla_form(2) == "mla_absorbed"
    assert att._mla_form(512) == "mla_expanded"
    get_telemetry().reset()
    c = latent_case(T=1, seed=6)
    run_form(c, None)
    gauges = get_telemetry().snapshot()["gauges"]
    assert gauges["attn/tier.mla.t1"] == 7  # mla_absorbed's id
    with pytest.raises(ValueError, match="no form"):
        run_form(c, "paged_scan")


def test_a_row_without_cache_reads_nothing_and_stays_finite():
    c = latent_case(lens=(0, 7, 0), T=1)
    c["qpos"] = np.maximum(c["qpos"], 0)
    for form in ("mla_absorbed", "mla_expanded"):
        out = run_form(c, form)
        assert np.isfinite(out).all()
        np.testing.assert_allclose(out[1], dense_latent(c)[1], atol=2e-5)


# -- the latent leaf of the pool --------------------------------------------------

def test_the_pool_holds_one_latent_row_a_token_and_layer():
    spec = program().decode_spec("bfloat16")
    # 32 + 8 columns a row, stored in whole 128-lane groups
    assert spec["kv_layout"] == "latent" and spec["head_dim"] == 128
    assert spec["num_layers"] == 5  # four layers and the module's one
    pool = KVCachePool(_pool_config(spec, 12, 4, "bfloat16", state_slots=0))
    assert set(pool.pages) == {"latent", "moe_counts"}
    assert [a.shape for a in pool.pages["latent"]] == [(12, 4, 128)] * 5
    assert pool.pages["latent"][0].dtype == jnp.bfloat16
    assert pool.ensure(7, 9) and pool.used_blocks == 3
    assert pool.release(7) == 3 and pool.accounting()["leaked_blocks"] == 0
    np.testing.assert_array_equal(pool.read_counters()["moe_counts"],
                                  np.zeros((2, 3)))
    with pytest.raises(ValueError, match="int8"):
        KVCacheConfig(2, 4, 40, num_kv_heads=1, layout="latent", dtype="int8")
    with pytest.raises(ValueError, match="int8"):
        program().decode_spec("int8")


# -- (b) prefill in chunks, then decode, through the latent pages ---------------

class Cached:
    """The model's own cached forward over a pool, driven by hand: rows of
    a bucket, each a sequence with its blocks."""

    def __init__(self, model, rows, blocks=40, block=4, width=12):
        spec = model.decode_spec("float32")
        self.pool = KVCachePool(_pool_config(spec, blocks, block, "float32",
                                             state_slots=0))
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

    def forget(self, owner):
        self.pool.release(owner)
        self.n.pop(owner, None)


@pytest.mark.parametrize("expand_from", [1000, 8])
def test_rows_of_unequal_length_in_one_bucket(expand_from, monkeypatch):
    """Two sequences in a bucket of four rows: each prefilled in chunks of
    8 (13 = 8 + 5, 21 = 8 + 8 + 5), then decoded side by side with two
    padded rows, against the reference's one full forward at every
    position; with the chunks through the absorbed form, and through the
    expanded one (a group of 8 cached rows, so that a walk is several)."""
    monkeypatch.setattr(att, "_MLA_EXPAND_MIN_T", expand_from)
    monkeypatch.setattr(att, "_MLA_GROUP", dict.fromkeys(att._MLA_GROUP, 8))
    get_telemetry().reset()
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
    gauges = get_telemetry().snapshot()["gauges"]
    assert gauges["attn/tier.mla.t8"] == (7 if expand_from > 8 else 8)
    assert gauges["attn/tier.mla.t1"] == 7
    # and the latent rows are what carries it: with the pages wiped, the
    # next step's logits are far off
    c.pool.pages["latent"] = tuple(jnp.zeros_like(t)
                                   for t in c.pool.pages["latent"])
    off = c.feed({0: ids[0, 22:23]}, 1)[0]
    assert np.abs(off - want[0, 22:23]).max() > 1e-2 * np.abs(want).max()


def test_an_evicted_sequence_and_a_reused_block_carry_nothing_over():
    model = program()
    ids = ids_of(3, 20, seed=6)
    want = ref_logits(ids)
    c = Cached(model, rows=2, blocks=8)  # 7 usable blocks of 4: one sequence
    close(c.run(0, ids[0], chunk=8, decode_from=11), want[0])
    blocks = set(c.pool.owned(0))
    c.forget(0)
    # a second sequence in the blocks the first has left its rows in
    close(c.run(1, ids[1], chunk=8, decode_from=9), want[1])
    assert set(c.pool.owned(1)) & blocks
    # a sequence evicted mid-generation and admitted again: its blocks go,
    # it is prefilled again over all it had, and decodes on
    c.forget(1)
    c.run(2, ids[2][:14], chunk=8, decode_from=10)
    c.forget(2)
    close(c.run(2, ids[2], chunk=8, decode_from=14), want[2])
    c.forget(2)
    assert c.pool.accounting()["leaked_blocks"] == 0


def test_the_served_step_counts_its_expert_layers():
    """Two expert layers a step. A decode step of two live rows (and two
    padded ones, which are routed nowhere) hits at most 2 x 2 held experts
    a layer; the counts say how many, and the pairs routed here."""
    model = program()
    ids = ids_of(2, 12, seed=8)
    c = Cached(model, rows=4)
    c.feed({0: ids[0, :8]}, 8)
    c.feed({1: ids[1, :8]}, 8)
    before = c.pool.read_counters()["moe_counts"].copy()
    assert before[0].tolist() == [0, 0, 0] and before[1][0] == 4
    for t in range(8, 11):
        c.feed({0: ids[0, t:t + 1], 1: ids[1, t:t + 1]}, 1)
    steps, hit, pairs = c.pool.read_counters()["moe_counts"][0]
    assert steps == 6 and 0 < hit <= pairs <= 6 * 2 * 2
    # a step of padded rows alone runs no expert layer that counts
    c.feed({}, 1)
    assert c.pool.read_counters()["moe_counts"][0].tolist() == [steps, hit,
                                                                pairs]


# -- through the engine --------------------------------------------------------

def engine(model, **kw):
    defaults = dict(capacity=16, decode_buckets=(1, 2, 4), prefill_chunk=8,
                    kv_blocks=48, kv_block_size=4, max_seq_len=96)
    defaults.update(kw)
    return TokenServingEngine(model, TokenServeConfig(**defaults))


def assert_greedy(model, prompt, emitted):
    """Every emitted token is the full forward's first choice at its
    position (a token within rounding of the best counts as the best)."""
    toks = np.concatenate([prompt, np.asarray(emitted, np.int32)])
    logits = model(paddle.to_tensor(toks[None, :-1])).numpy()[0]
    at = logits[len(prompt) - 1:]
    picked = at[np.arange(len(emitted)), np.asarray(emitted)]
    assert (at.max(-1) - picked).max() <= 1e-5 * np.abs(logits).max()


def serve(model, prompts, new, **kw):
    eng = engine(model, **kw).start(warmup=False)
    try:
        reqs = [eng.submit(p, max_new_tokens=new) for p in prompts]
        for r in reqs:
            assert r.wait(600.0) and r.status == "ok"
        return [list(r.outputs[0]) for r in reqs], eng
    finally:
        eng.shutdown()


def test_the_engine_serves_it_greedy_and_publishes_its_counts():
    get_telemetry().reset()
    model = program(nextn=False)
    prompts = [ids_of(1, n, seed=20 + n)[0] for n in (5, 13, 21)]
    outs, eng = serve(model, prompts, 6)
    for p, out in zip(prompts, outs):
        assert len(out) == 6
        assert_greedy(model, p, out)
    tel = get_telemetry()
    gauges = tel.snapshot()["gauges"]
    assert gauges["moe/experts_held"] == 4
    assert gauges["serve/kv_blocks_total"] == 47
    # published at shutdown, from the pool's counters: no step fetched them
    steps = tel.counter_value("moe/layer_steps.decode")
    assert steps == 2 * tel.counter_value("serve/decode_steps")
    assert 0 < tel.counter_value("moe/experts_hit.decode") \
        <= tel.counter_value("moe/pairs_here.decode")
    assert tel.counter_value("moe/layer_steps.chunk") \
        == 2 * tel.counter_value("serve/prefill_chunks")
    assert serve_family.experts_hit() == pytest.approx(
        tel.counter_value("moe/experts_hit.decode") / steps)
    assert eng.kv_accounting()["leaked_blocks"] == 0
    assert eng.publish_counters() == {}  # the pages went with the shutdown


def test_eviction_under_a_full_pool_keeps_parity():
    get_telemetry().reset()
    model = program(nextn=False)
    # 11 usable blocks of 4: two sequences of 16 + 12 do not both fit
    prompts = [ids_of(1, 16, seed=31)[0], ids_of(1, 14, seed=32)[0]]
    outs, eng = serve(model, prompts, 12, kv_blocks=12, max_seq_len=40,
                      decode_buckets=(1, 2))
    for p, out in zip(prompts, outs):
        assert len(out) == 12
        assert_greedy(model, p, out)
    assert get_telemetry().counter_value("serve/kv_evictions") >= 1
    assert eng.kv_accounting()["leaked_blocks"] == 0


# -- (e) the module as the scheduler's draft ------------------------------------

# a vocabulary of 8: with weights from a seed the module's guess is the
# target's choice often enough that both ways of a round are driven
NARROW = {**CONFIG, "vocab_size": 8}


@pytest.mark.parametrize("pool", ["roomy", "full"])
def test_drafting_from_the_module_leaves_greedy_output_as_it_was(pool):
    kw = ({} if pool == "roomy" else
          dict(kv_blocks=12, max_seq_len=44, decode_buckets=(1, 2)))
    model = program(NARROW)
    prompts = [ids_of(1, n, seed=40 + n, vocab=8)[0] for n in (5, 13, 18)]
    plain, _ = serve(model, prompts, 14, **kw)
    get_telemetry().reset()
    drafted, eng = serve(model, prompts, 14, spec_k=1, **kw)
    assert drafted == plain
    tel = get_telemetry()
    proposed = tel.counter_value("serve/spec_proposed")
    accepted = tel.counter_value("serve/spec_accepted")
    assert 0 < accepted < proposed
    # every token came from a prefill's end, a plain step or a round (one,
    # and one more where the proposal was accepted); a request's last
    # round may bring one more than its budget takes
    rounds = proposed + tel.counter_value("serve/prefill_chunks")
    made = sum(map(len, drafted))
    assert made <= rounds + accepted
    assert tel.snapshot()["gauges"]["serve/spec_accept_rate"] \
        == pytest.approx(accepted / proposed)
    if pool == "full":
        assert tel.counter_value("serve/kv_evictions") >= 1
    # the target's rows and the module's lie under the same blocks
    acct = eng.kv_accounting()
    assert acct["leaked_blocks"] == 0 and acct["owners"] == []
    assert "draft" not in acct


def test_the_last_positions_of_a_sequence_fall_back_to_plain_decode():
    """Within two positions of ``max_seq_len`` a round's second write
    would overflow the table: those tokens come one a step."""
    model = program(NARROW)
    prompt = ids_of(1, 9, seed=50, vocab=8)[0]
    plain, _ = serve(model, [prompt], 11, max_seq_len=20)
    drafted, _ = serve(model, [prompt], 11, max_seq_len=20, spec_k=1)
    assert drafted == plain and len(plain[0]) == 11


def test_what_the_engine_refuses():
    with pytest.raises(ValueError, match="proposes 1 token"):
        engine(program(), spec_k=2)
    with pytest.raises(ValueError, match="draft of its own"):
        engine(program(nextn=False), spec_k=1)
