"""The serve side of the openPangu-Ultra-MoE family:
``text.models.pangu_ultra_moe.PanguUltraMoEForCausalLM`` with the harness's
weights resident in bfloat16, under ``inference.serving.TokenServingEngine``
as the cell's file sets it up. Found by ``drivers/serve.py`` as
``<family>_serve``.

Also here, because they belong to the yardstick: the parameters held, the
bytes a decode step has to move, the work of the latent attention and of
the expert layers in it, and the operations a served token costs, from
shapes and the run's own counters, whatever implements them.
"""
from __future__ import annotations

import math

import jax

from benchmark.families.pangu_ultra_moe import names_of
from benchmark.reference import common
from benchmark.reference import pangu_ultra_moe as reference
from benchmark.reference.pangu_ultra_moe import margins as reference_margins  # noqa: F401,E501 (the entry)

KV_BYTES = {"float32": 4, "bfloat16": 2}
W_BYTES = 2       # the weights are served in bfloat16
BIG_LEAF = 1 << 27  # elements: a leaf this large is made in a call of its own

# what this process built last: the configuration's ``assumed`` group
# (``build_model``) and the cell's ``engine`` group (``build_engine``)
_last_assumed = {}
_last_engine = {}


def weights(config: dict, seed: int, dtype: str, nextn: bool = False) -> dict:
    """The seed's weights under the program's names, in the type they are
    served in. Made on the device a layer at a time, an expert stack alone
    (1 GB in float32), each leaf cast inside the call that makes it: no
    float32 copy of a leaf outlives its cast."""
    specs = reference.param_specs(config, nextn)
    names = names_of(config, nextn)
    key = common.seed_key(seed)
    prefixes = [f"l{i}_" for i in range(config["num_hidden_layers"])] \
        + (["nextn_"] if nextn else [])
    groups = [["embed"], ["head_w", "final_norm"]]
    for prefix in prefixes:
        leaves = [n for n in specs if n.startswith(prefix)]
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
    from paddle_tpu.text.models.pangu_ultra_moe import (
        PanguUltraMoEConfig, PanguUltraMoEForCausalLM)

    dep = config["deployment"]
    cfg = PanguUltraMoEConfig(
        vocab_size=dep["vocab_size_published"],
        vocab_rows_held=config["vocab_size"],
        num_hidden_layers=dep["num_hidden_layers_published"],
        layers_held=config["num_hidden_layers"],
        first_k_dense_replace=dep["first_k_dense_replace_published"],
        dense_layers_held=config["first_k_dense_replace"],
        n_routed_experts=dep["n_routed_experts_published"],
        experts_held=range(config["n_routed_experts"]),
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        n_shared_experts=config["n_shared_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        routed_scaling_factor=config["routed_scaling_factor"],
        num_attention_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"], rope_theta=config["rope_theta"],
        rms_norm_eps=config["rms_norm_eps"],
        max_position_embeddings=config["max_position_embeddings"],
        num_nextn_predict_layers=config["num_nextn_predict_layers"],
        # the module is built where its weights came: a cell that does not
        # speculate brings none (the configuration's ``changed``)
        nextn_held=int(any(n.startswith("nextn.") for n in named_weights)),
        initializer_range=config["assumed"]["initializer_range"])
    # the harness brings every weight: the model draws none of its own (its
    # own float32 initialisation would not fit beside them)
    with paddle.LazyGuard():
        model = PanguUltraMoEForCausalLM(cfg)
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

def _attention_params(z: dict) -> int:
    """One layer's attention matmuls: W_qa, W_qb, W_kva, W_kvb, W_o."""
    h, heads = z["h"], z["heads"]
    return (h * z["q_rank"] + z["q_rank"] * heads * (z["nope"] + z["rope"])
            + h * (z["latent"] + z["rope"])
            + z["latent"] * heads * (z["nope"] + z["v_dim"])
            + heads * z["v_dim"] * h)


def _norm_params(z: dict) -> int:
    """One layer's gains: four of the block, the two latents'."""
    return 4 * z["h"] + z["q_rank"] + z["latent"]


def _expert_params(z: dict) -> int:
    return 3 * z["h"] * z["expert"]


def _outside_experts(z: dict, kind: str) -> int:
    """One layer's parameters but its routed experts'."""
    ffn = (3 * z["h"] * z["dense"] if kind == "dense" else
           z["shared"] * _expert_params(z) + z["h"] * z["routed"])
    return _attention_params(z) + _norm_params(z) + ffn


def parameters(config: dict) -> int:
    """Every parameter this chip holds for the cell: the layers with the
    experts held, the embedding, the final norm and the head (no
    next-token module: the cell does not speculate)."""
    z = reference.sizes(config)
    kinds = reference.layer_kinds(config)
    return (sum(_outside_experts(z, k) for k in kinds)
            + kinds.count("moe") * z["held"] * _expert_params(z)
            + 2 * z["rows"] * z["h"] + z["h"])


def kv_bytes_per_token(config: dict, kv_dtype: str) -> int:
    """A cached token over every layer held: one latent row a layer, the
    compressed latent and the rotated shared key."""
    z = reference.sizes(config)
    return z["layers"] * (z["latent"] + z["rope"]) * KV_BYTES[kv_dtype]


def experts_hit():
    """Held experts that received at least one pair, mean over the decode
    steps' expert layers: from the engine's own counters
    (``counter/moe/experts_hit.decode`` over ``moe/layer_steps.decode``,
    published when the engine shut down); None where the program counts
    none."""
    from paddle_tpu.profiler.telemetry import get_telemetry

    tel = get_telemetry()
    steps = tel.counter_value("moe/layer_steps.decode")
    if not steps:
        return None
    return tel.counter_value("moe/experts_hit.decode") / steps


def expected_hit(config: dict, rows: float) -> float:
    """What uniform routing would hit of the held experts with ``rows``
    tokens a step: held (1 - (1 - k / routed)^rows)."""
    z = reference.sizes(config)
    return z["held"] * (1.0 - (1.0 - z["top_k"] / z["routed"]) ** rows)


def moe_step_bytes(config: dict, hit: float) -> float:
    """The least the ``moe`` scopes of a decode step must read: of every
    expert layer the ``hit`` experts that received a pair (an expert
    without one has nothing to do), the shared expert and the router.
    Bound by bytes: two pairs an expert."""
    z = reference.sizes(config)
    layers = reference.layer_kinds(config).count("moe")
    return W_BYTES * layers * ((hit + z["shared"]) * _expert_params(z)
                               + z["h"] * z["routed"])


def mla_step_work(config: dict, kv_dtype: str, rows: float,
                  live_tokens: float) -> tuple:
    """(operations, bytes) the ``mla`` scopes of a decode step need at the
    least, in the absorbed form: a row's query taken into the latent space
    and its output out of it (W_kvb once a layer), and for every cached
    position of the rows decoding (``live_tokens``, summed over them) one
    latent row read once a layer and met by every head: 2 (576 + 512)
    operations a head."""
    z = reference.sizes(config)
    width = z["latent"] + z["rope"]
    absorb = 2 * z["heads"] * z["latent"] * (z["nope"] + z["v_dim"])
    ops = z["layers"] * (rows * absorb + live_tokens * z["heads"]
                         * 2 * (width + z["latent"]))
    moved = z["layers"] * (
        live_tokens * width * KV_BYTES[kv_dtype]
        + W_BYTES * z["latent"] * z["heads"] * (z["nope"] + z["v_dim"]))
    return ops, moved


def decode_step_bytes(config: dict, kv_dtype: str, live_tokens: float,
                      rows: float = None) -> float:
    """The least a decode step must move: the weights outside the routed
    experts once, the experts that received a pair (the run's own count;
    uniform routing's expectation for the engine's ``max_running`` rows
    where the program counts none), the head, and one latent row a layer
    of every position its sequences attend to."""
    z = reference.sizes(config)
    kinds = reference.layer_kinds(config)
    if rows is None:
        rows = _last_engine.get("max_running", 0)
    hit = experts_hit()
    if hit is None:
        hit = expected_hit(config, rows)
    outside = sum(_outside_experts(z, k) for k in kinds) \
        + z["h"] + z["h"] * z["rows"]
    return (W_BYTES * (outside + kinds.count("moe") * hit * _expert_params(z))
            + live_tokens * kv_bytes_per_token(config, kv_dtype))


def forward_flops(config: dict, tokens: int, attended: int,
                  emitted: int) -> float:
    """Operations the served work needs: 2 a parameter of the matmuls a
    token passes (attention's five, the dense MLP or the shared expert and
    the router, and of the held experts the k held / routed that uniform
    routing sends a token here) for each of ``tokens`` positions forwarded;
    2 (192 + 128) a head and layer for each pair of a query and a cached
    position (``attended`` pairs: keys and values a head as if they were
    there: the absorbed form's own 2 (576 + 512) is an implementation's
    price, not the model's); and the head, 2 h a row, for the ``emitted``
    tokens alone."""
    z = reference.sizes(config)
    per_token = 0.0
    for kind in reference.layer_kinds(config):
        per_token += _attention_params(z)
        if kind == "dense":
            per_token += 3 * z["h"] * z["dense"]
        else:
            per_token += (z["h"] * z["routed"] + _expert_params(z) * (
                z["shared"] + z["top_k"] * z["held"] / z["routed"]))
    pair = 2 * (z["nope"] + z["rope"] + z["v_dim"]) * z["heads"] * z["layers"]
    return (2.0 * tokens * per_token + pair * attended
            + 2.0 * emitted * z["h"] * z["rows"])
