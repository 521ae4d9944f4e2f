"""The serve side of the LongCat-Flash family:
``text.models.longcat_flash.LongcatFlashForCausalLM`` with the harness's
weights resident in bfloat16, under ``inference.serving.TokenServingEngine``
as the cell's file sets it up. Found by ``drivers/serve.py`` as
``<family>_serve``.

Also here, because they belong to the yardstick: the parameters held, the
bytes a decode step has to move, the work of the latent attention, of the
expert layers and of the dense feed-forwards in it, and the operations a
served token costs, from shapes and the run's own counters, whatever
implements them.
"""
from __future__ import annotations

import math

import jax

from benchmark.families.longcat_flash import names_of
# held experts hit a decode step's expert layer, from the engine's counters
from benchmark.families.pangu_ultra_moe_serve import experts_hit
from benchmark.lib import report
from benchmark.reference import common
from benchmark.reference import longcat_flash as reference
from benchmark.reference.longcat_flash import margins as reference_margins  # noqa: F401,E501 (the entry)

KV_BYTES = {"float32": 4, "bfloat16": 2}
W_BYTES = 2       # the weights are served in bfloat16
ACT_BYTES = 2     # so are the activations between products
BIG_LEAF = 1 << 26  # elements: a leaf this large is made in a call of its own
COUNTS = ("layer_steps", "experts_hit", "pairs_here", "pairs_zero",
          "positions")

# what this process built last: the configuration's ``assumed`` group
# (``build_model``) and the cell's ``engine`` group (``build_engine``)
_last_assumed = {}
_last_engine = {}


def weights(config: dict, seed: int, dtype: str) -> dict:
    """The seed's weights under the program's names, in the type they are
    served in. Made on the device a double layer at a time, a large leaf
    (an expert stack, a dense feed-forward's matrix) alone, each leaf cast
    inside the call that makes it: no float32 copy of a leaf outlives its
    cast."""
    specs = reference.param_specs(config)
    names = names_of(config)
    key = common.seed_key(seed)
    groups = [["embed"], ["head_w", "final_norm"]]
    for i in range(config["num_layers"]):
        leaves = [n for n in specs if n.startswith(f"l{i}_")]
        big = [n for n in leaves if math.prod(specs[n][0]) >= BIG_LEAF]
        groups += [[n] for n in big] + [[n for n in leaves if n not in big]]
    assert sorted(n for g in groups for n in g) == sorted(specs)
    out = {}
    for group in groups:
        made = jax.jit(lambda k, g=tuple(group): {
            names[n]: common.init_leaf(specs, n, k).astype(dtype)
            for n in g})(key)
        out.update(made)
    return out


def build_model(config: dict, named_weights: dict):
    import paddle_tpu as paddle
    from paddle_tpu.jit.functionalize import set_params
    from paddle_tpu.text.models.longcat_flash import (
        LongcatFlashConfig, LongcatFlashForCausalLM)

    dep = config["deployment"]
    cfg = LongcatFlashConfig(
        vocab_size=dep["vocab_size_published"],
        vocab_rows_held=config["vocab_size"],
        num_layers=dep["num_layers_published"],
        layers_held=config["num_layers"],
        n_routed_experts=dep["n_routed_experts_published"],
        experts_held=range(config["n_routed_experts"]),
        zero_expert_num=config["zero_expert_num"],
        hidden_size=config["hidden_size"],
        ffn_hidden_size=config["ffn_hidden_size"],
        expert_ffn_hidden_size=config["expert_ffn_hidden_size"],
        num_attention_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        mla_scale_q_lora=config["mla_scale_q_lora"],
        mla_scale_kv_lora=config["mla_scale_kv_lora"],
        routed_scaling_factor=config["routed_scaling_factor"],
        moe_topk=config["moe_topk"],
        max_position_embeddings=config["max_position_embeddings"],
        rms_norm_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        initializer_range=config["assumed"]["initializer_range"])
    # the harness brings every weight: the model draws none of its own (its
    # own float32 initialisation would not fit beside them)
    with paddle.LazyGuard():
        model = LongcatFlashForCausalLM(cfg)
    _last_assumed.clear()
    _last_assumed.update(config["assumed"])
    set_params(model, named_weights)
    model.eval()
    return model


def build_engine(model, engine: dict):
    """``TokenServingEngine`` as the cell's ``engine`` group states it: no
    deadline, ``spec_k`` the group's (0 where it names none), and the
    admission cap ``max_seq_len``, which is the configuration's
    (``assumed``)."""
    from paddle_tpu.inference.serving import (TokenServeConfig,
                                              TokenServingEngine)

    _last_engine.clear()
    _last_engine.update(engine)
    return TokenServingEngine(model, TokenServeConfig(
        capacity=engine["capacity"],
        decode_buckets=tuple(engine["decode_buckets"]),
        max_running=engine["max_running"],
        prefill_chunk=engine["prefill_chunk"],
        kv_blocks=engine["kv_blocks"],
        kv_block_size=engine["kv_block_size"],
        kv_dtype=engine["kv_dtype"],
        max_seq_len=_last_assumed["max_seq_len"],
        default_deadline_s=None, spec_k=engine.get("spec_k", 0),
        drain_grace_s=engine.get("drain_grace_s", 5.0)))


# -- counts from shapes and the run's counters -----------------------------------

def _mla_params(z: dict) -> int:
    """One attention: W_qa, W_qb, W_kva, W_kvb, W_o and the two latents'
    gains."""
    h, heads = z["h"], z["heads"]
    return (h * z["q_rank"] + z["q_rank"] * heads * (z["nope"] + z["rope"])
            + h * (z["latent"] + z["rope"])
            + z["latent"] * heads * (z["nope"] + z["v_dim"])
            + heads * z["v_dim"] * h + z["q_rank"] + z["latent"])


def _ffn_params(z: dict) -> int:
    return 3 * z["h"] * z["ffn"]


def _expert_params(z: dict) -> int:
    return 3 * z["h"] * z["expert"]


def _router_params(z: dict) -> int:
    return z["h"] * (z["routed"] + z["zero"])


def _outside_experts(z: dict) -> int:
    """One double layer's parameters but its routed experts': two
    attentions, two dense feed-forwards, the router, four norms."""
    return (2 * _mla_params(z) + 2 * _ffn_params(z) + _router_params(z)
            + 4 * z["h"])


def parameters(config: dict) -> int:
    """Every parameter this chip holds for the cell: the double layers with
    the experts held, the embedding, the final norm and the head."""
    z = reference.sizes(config)
    return (z["layers"] * (_outside_experts(z) + z["held"] * _expert_params(z))
            + 2 * z["rows"] * z["h"] + z["h"])


def kv_bytes_per_token(config: dict, kv_dtype: str) -> int:
    """A cached token over every layer held: one latent row an attention,
    two attentions a double layer."""
    z = reference.sizes(config)
    return 2 * z["layers"] * (z["latent"] + z["rope"]) * KV_BYTES[kv_dtype]


def run_counts():
    """The served steps' counts (``counter/moe/<COUNTS>.<decode|chunk>``,
    published by the engine at shutdown), or None where the program counts
    no identity pairs."""
    from paddle_tpu.profiler.telemetry import get_telemetry

    tel = get_telemetry()
    out = {f"{name}.{kind}": tel.counter_value(f"moe/{name}.{kind}")
           for name in COUNTS for kind in ("decode", "chunk")}
    if not out["positions.decode"] + out["positions.chunk"]:
        return None
    return out


def expected_hit(config: dict, rows: float) -> float:
    """What uniform routing would hit of the held experts with ``rows``
    tokens a step: held (1 - (1 - k / columns)^rows)."""
    z = reference.sizes(config)
    return z["held"] * (1.0 - (1.0 - z["top_k"] / (z["routed"] + z["zero"]))
                        ** rows)


def moe_step_bytes(config: dict, hit: float) -> float:
    """The least the ``moe`` scopes of a decode step must read: of every
    expert layer the ``hit`` held experts that received a pair (an expert
    without one has nothing to do) and the router. An identity expert has
    no weights. Bound by bytes: two pairs an expert."""
    z = reference.sizes(config)
    return W_BYTES * z["layers"] * (hit * _expert_params(z)
                                    + _router_params(z))


def ffn_step_bytes(config: dict, rows: float) -> float:
    """The least the ``ffn`` scopes of a decode step must move: the two
    dense feed-forwards of every double layer read once, and each row's
    input read and output written."""
    z = reference.sizes(config)
    return 2 * z["layers"] * (W_BYTES * _ffn_params(z)
                              + rows * 2 * z["h"] * ACT_BYTES)


def mla_step_work(config: dict, kv_dtype: str, rows: float,
                  live_tokens: float) -> tuple:
    """(operations, bytes) the ``mla`` scopes of a decode step need at the
    least, in the absorbed form, over the two attentions of every double
    layer: a row's query taken into the latent space and its output out of
    it (W_kvb once an attention), and for every cached position of the rows
    decoding (``live_tokens``, summed over them) one latent row read once
    an attention and met by every head: 2 (576 + 512) operations a head."""
    z = reference.sizes(config)
    attentions = 2 * z["layers"]
    width = z["latent"] + z["rope"]
    absorb = 2 * z["heads"] * z["latent"] * (z["nope"] + z["v_dim"])
    ops = attentions * (rows * absorb + live_tokens * z["heads"]
                        * 2 * (width + z["latent"]))
    moved = attentions * (
        live_tokens * width * KV_BYTES[kv_dtype]
        + W_BYTES * z["latent"] * z["heads"] * (z["nope"] + z["v_dim"]))
    return ops, moved


def decode_step_bytes(config: dict, kv_dtype: str, live_tokens: float,
                      rows: float = None) -> float:
    """The least a decode step must move: the weights outside the routed
    experts once, the experts that received a pair (the run's own count;
    uniform routing's expectation for the engine's ``max_running`` rows
    where the program counts none), the head, and one latent row an
    attention of every position its sequences attend to."""
    z = reference.sizes(config)
    if rows is None:
        rows = _last_engine.get("max_running", 0)
    hit = experts_hit()
    if hit is None:
        hit = expected_hit(config, rows)
    outside = z["layers"] * _outside_experts(z) + z["h"] + z["h"] * z["rows"]
    return (W_BYTES * (outside + z["layers"] * hit * _expert_params(z))
            + live_tokens * kv_bytes_per_token(config, kv_dtype))


def pairs_per_position(config: dict) -> tuple:
    """(pairs to held experts, pairs to identity experts) a position and
    expert layer: the run's own counts where the program made them (and
    printed, with the identity experts' share of a decode step's pairs),
    else uniform routing's k held / columns and k identity / columns."""
    z = reference.sizes(config)
    counts = run_counts()
    if counts is None:
        columns = z["routed"] + z["zero"]
        return (z["top_k"] * z["held"] / columns,
                z["top_k"] * z["zero"] / columns)
    positions = counts["positions.decode"] + counts["positions.chunk"]
    report.note("moe_counts", **counts, pairs_zero_share_decode=(
        counts["pairs_zero.decode"]
        / max(z["top_k"] * counts["positions.decode"], 1)))
    return ((counts["pairs_here.decode"] + counts["pairs_here.chunk"])
            / positions,
            (counts["pairs_zero.decode"] + counts["pairs_zero.chunk"])
            / positions)


def forward_flops(config: dict, tokens: int, attended: int,
                  emitted: int) -> float:
    """Operations the served work needs: 2 a parameter of the matmuls a
    token passes (two attentions' five, two dense feed-forwards, the
    router, a held expert's for each pair routed here) and 2 h for each
    pair routed to an identity expert (its weighted add, and nothing
    more), for each of ``tokens`` positions forwarded; 2 (192 + 128) a
    head and attention for each pair of a query and a cached position
    (``attended``: keys and values a head as if they were there); and the
    head, 2 h a row, for the ``emitted`` tokens alone."""
    z = reference.sizes(config)
    here, zero = pairs_per_position(config)
    per_token = z["layers"] * (
        2 * (_mla_params(z) - z["q_rank"] - z["latent"])
        + 2 * _ffn_params(z) + _router_params(z)
        + here * _expert_params(z) + zero * z["h"])
    pair = (2 * (z["nope"] + z["rope"] + z["v_dim"]) * z["heads"]
            * 2 * z["layers"])
    return (2.0 * tokens * per_token + pair * attended
            + 2.0 * emitted * z["h"] * z["rows"])
