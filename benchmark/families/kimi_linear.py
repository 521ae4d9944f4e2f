"""The Kimi-Linear family: ``text.models.kimi_linear.KimiLinearForCausalLM``
under ``fleet.ParallelTrainStep``, built as ``families/gpt.py`` builds GPT
(bf16 compute, bf16 resident parameters, f32 masters and moments), holding
this chip's share: the configuration's ``num_experts`` experts of the
``deployment.num_experts_routed`` the router scores, and its ``vocab_size``
rows of the vocabulary.

``lib/layout.py`` formats ``{i}`` as the index inside a stack of like
layers. This model's layers are of three kinds by a per-layer pattern, so
the reference stacks nothing and ``NAMES`` gives every layer's leaves
their own names (``l0_kda_q_w`` -> ``model.layers.0.mixer.q_proj.weight``).
``NAMES`` is a module constant that the driver reads before it has a
configuration, so it is made from the benchmark's one configuration of
this family; ``build`` refuses a configuration whose layer pattern is
another (the CPU rehearsal keeps the pattern at a tiny width).

Besides the harness's entries, the operation and byte counts of the two
new kernels-to-be, of the shapes alone (``kda_work``, ``moe_work``): what
``benchmark/metrics/{kda,moe}_roofline_pct.train.py`` divide by the time
the trace reads.
"""
from __future__ import annotations

import json
import os

import numpy as np

# at import, so that a checkout whose program lacks the model fails here,
# before a weight is made or a chip is asked for anything
from paddle_tpu.text.models.kimi_linear import (KimiLinearConfig,
                                                KimiLinearForCausalLM)
from benchmark.reference import kimi_linear as reference

_MIXER = {
    "kda": {"q_w": "q_proj.weight", "k_w": "k_proj.weight",
            "v_w": "v_proj.weight", "q_conv": "q_conv", "k_conv": "k_conv",
            "v_conv": "v_conv", "f1_w": "f_a.weight", "f2_w": "f_b.weight",
            "a_log": "A_log", "dt_bias": "dt_bias", "b_w": "b_proj.weight",
            "g1_w": "g_a.weight", "g2_w": "g_b.weight",
            "o_norm": "o_norm.weight", "o_w": "o_proj.weight"},
    "mla": {"q_w": "q_proj.weight", "kva_w": "kv_a_proj.weight",
            "kv_norm": "kv_a_norm.weight", "kvb_w": "kv_b_proj.weight",
            "o_w": "o_proj.weight"},
}
_GATED = {"gate_w": "gate_proj.weight", "up_w": "up_proj.weight",
          "down_w": "down_proj.weight"}
_FFN = {
    "dense": _GATED,
    "moe": {"router_w": "gate.weight", "e_gate_w": "w_gate", "e_up_w": "w_up",
            "e_down_w": "w_down",
            **{"s_" + n: "shared." + t for n, t in _GATED.items()}},
}


def names_of(config: dict) -> dict:
    """reference leaf -> the program's parameter name, a layer at a time."""
    names = {"embed": "model.embed.weight", "final_norm": "model.norm.weight",
             "head_w": "lm_head.weight"}
    for i, (mixer, ffn) in enumerate(reference.layer_kinds(config)):
        at = f"model.layers.{i}."
        names[f"l{i}_attn_norm"] = at + "attn_norm.weight"
        names[f"l{i}_ffn_norm"] = at + "ffn_norm.weight"
        names.update({f"l{i}_{mixer}_{leaf}": at + "mixer." + target
                      for leaf, target in _MIXER[mixer].items()})
        names.update({f"l{i}_{ffn}_{leaf}": at + "ffn." + target
                      for leaf, target in _FFN[ffn].items()})
    return names


with open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "configs",
        "kimi-linear-48b-a3b.json")) as _f:
    NAMES = names_of(json.load(_f))


def build(config: dict, cell: dict, mesh, named_weights: dict):
    """The timed object. Call it through ``call``."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.engine import ParallelTrainStep
    from paddle_tpu.jit.functionalize import set_params

    if names_of(config) != NAMES:
        raise SystemExit("benchmark/families/kimi_linear.py names the layers "
                         "of configs/kimi-linear-48b-a3b.json; this "
                         "configuration has another depth or pattern")
    lin, dep, a = (config["linear_attn_config"], config["deployment"],
                   config["assumed"])
    model = KimiLinearForCausalLM(KimiLinearConfig(
        vocab_size=dep["vocab_size_published"],
        vocab_rows_held=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_experts=dep["num_experts_routed"],
        experts_held=range(*dep["experts_held"]),
        num_experts_per_token=config["num_experts_per_token"],
        num_shared_experts=config["num_shared_experts"],
        first_k_dense_replace=config["first_k_dense_replace"],
        routed_scaling_factor=config["routed_scaling_factor"],
        moe_renormalize=config["moe_renormalize"],
        num_attention_heads=config["num_attention_heads"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"], kv_lora_rank=config["kv_lora_rank"],
        rms_norm_eps=config["rms_norm_eps"],
        kda_layers=tuple(lin["kda_layers"]),
        full_attn_layers=tuple(lin["full_attn_layers"]),
        kda_num_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        short_conv_kernel_size=lin["short_conv_kernel_size"],
        gate_rank=a["gate_rank"], initializer_range=a["initializer_range"],
        l2_norm_eps=a["l2_norm_eps"]))
    set_params(model, named_weights)
    o = cell["optimizer"]
    opt = paddle.optimizer.Adam(
        learning_rate=o["lr"], beta1=o["beta1"], beta2=o["beta2"],
        epsilon=o["epsilon"], parameters=model.parameters(),
        multi_precision=True)
    # labels ride as a forward input: the model computes the loss itself
    return ParallelTrainStep(
        model, loss_fn=lambda out, lbl: out, optimizer=opt, mesh=mesh,
        zero_stage=0, remat=cell["remat"],
        compute_dtype=jnp.dtype(cell["compute_dtype"]))


def call(step, batch: dict):
    return step((batch["ids"], batch["labels"]), (batch["labels"],))


def make_batches(config: dict, traffic: dict, seed: int, n: int) -> list:
    """``n`` different batches of int32 ids, uniform over the rows of the
    vocabulary held here; the label of a position is the next id (the last
    wraps to the first)."""
    rng = np.random.default_rng([int(seed), 0x6B696D69])
    ids = rng.integers(0, config["vocab_size"],
                       (n, traffic["batch"], traffic["seq_len"]),
                       dtype=np.int32)
    return [{"ids": x, "labels": np.roll(x, -1, axis=1)} for x in ids]


def tokens_per_step(traffic: dict) -> int:
    return traffic["batch"] * traffic["seq_len"]


def _held_share(z: dict) -> float:
    """Held experts a token and layer, at its expectation: 0.25."""
    return z["top_k"] * z["held"] / z["routed"]


def _kda_flops_per_token(z: dict) -> float:
    """The recurrence, forward: the decay of S, S~^T k, the write, S^T q
    are 7 operations an element of the dk x dv state, a head."""
    return 7.0 * z["kda_dim"] * z["kda_dim"] * z["kda_heads"]


def flops_per_token(config: dict, traffic: dict) -> float:
    """Forward and backward, nothing recomputed: 6 a parameter of the
    per-token matmuls (the experts at their expectation: 0.25 of a held
    expert a token and layer; what the absent experts would cost is not
    this chip's), 6 h a row of the head, the causal half of MLA's score
    square at 192 + 128 a head, and the KDA recurrence's 7 dk dv a head
    (three times that with the backward)."""
    z = reference.sizes(config)
    h, kd = z["h"], z["kda_heads"] * z["kda_dim"]
    gated = lambda w: 3 * h * w  # noqa: E731
    per_mixer = {
        "kda": 4 * h * kd + 2 * (h * z["gate_rank"] + z["gate_rank"] * kd)
        + h * z["kda_heads"],
        "mla": h * z["heads"] * (z["nope"] + z["rope"])
        + h * (z["latent"] + z["rope"])
        + z["latent"] * z["heads"] * (z["nope"] + z["v_dim"])
        + z["heads"] * z["v_dim"] * h}
    per_ffn = {"dense": gated(z["dense"]),
               "moe": h * z["routed"] + (1 + _held_share(z))
               * gated(z["expert"])}
    kinds = reference.layer_kinds(config)
    n_matmul = sum(per_mixer[m] + per_ffn[f] for m, f in kinds)
    mixers = [m for m, _ in kinds]
    scores = (mixers.count("mla") * 3.0 * traffic["seq_len"] * z["heads"]
              * (z["nope"] + z["rope"] + z["v_dim"]))
    recurrence = mixers.count("kda") * 3.0 * _kda_flops_per_token(z)
    return 6.0 * n_matmul + 6.0 * h * z["rows"] + scores + recurrence


def kda_work(config: dict, traffic: dict) -> dict:
    """Operations and bytes a step of what the ``kda`` scope has to do,
    whatever implements it: the recurrence forward and backward (three
    times the forward), against q, k, v (2 bytes), g, beta (4) read and o
    (2) written, and the same again for the gradients."""
    z = reference.sizes(config)
    layers = [m for m, _ in reference.layer_kinds(config)].count("kda")
    tokens = tokens_per_step(traffic)
    width = z["kda_heads"] * z["kda_dim"]
    a_token = 2 * (3 * width * 2 + width * 4 + z["kda_heads"] * 4 + width * 2)
    return {"flops": layers * tokens * 3.0 * _kda_flops_per_token(z),
            "bytes": float(layers * tokens * a_token)}


def moe_work(config: dict, traffic: dict) -> dict:
    """Of the ``moe`` scope: the router over all routed experts and the
    expected 0.25 held experts a token, forward and backward (6 a
    parameter), against the held stacks read once forward and twice
    backward (2 bytes a weight). The shared expert is ``mlp``'s."""
    z = reference.sizes(config)
    layers = [f for _, f in reference.layer_kinds(config)].count("moe")
    tokens = tokens_per_step(traffic)
    expert = 3 * z["h"] * z["expert"]
    return {"flops": layers * tokens * 6.0
            * (z["h"] * z["routed"] + _held_share(z) * expert),
            "bytes": float(layers * 3 * z["held"] * expert * 2)}
