"""The openPangu-Ultra-MoE family: ``text.models.pangu_ultra_moe.
PanguUltraMoEForCausalLM`` holding this chip's share (the configuration's
``n_routed_experts`` experts of the ``deployment.n_routed_experts_published``,
its ``num_hidden_layers`` layers of which the first
``first_k_dense_replace`` are dense, its ``vocab_size`` rows of the
vocabulary). The model is served, not trained (no cut of it fits a chip's
train state), so what a cell needs of it is in ``pangu_ultra_moe_serve.py``;
here are the names: reference leaf -> the program's parameter, through the
reference's own ``param_specs``.
"""
from __future__ import annotations

# at import, so that a checkout whose program lacks the model fails here,
# before a weight is made or a chip is asked for anything
from paddle_tpu.text.models.pangu_ultra_moe import (  # noqa: F401
    PanguUltraMoEConfig, PanguUltraMoEForCausalLM)
from benchmark.reference import pangu_ultra_moe as reference

_ATTN = {
    "attn_norm": "input_layernorm.weight",
    "q_a_w": "attn.q_a_proj.weight", "q_a_norm": "attn.q_a_norm.weight",
    "q_b_w": "attn.q_b_proj.weight", "kv_a_w": "attn.kv_a_proj.weight",
    "kv_a_norm": "attn.kv_a_norm.weight", "kv_b_w": "attn.kv_b_proj.weight",
    "o_w": "attn.o_proj.weight",
    "post_attn_norm": "post_attention_layernorm.weight",
    "pre_mlp_norm": "pre_mlp_layernorm.weight",
    "post_mlp_norm": "post_mlp_layernorm.weight",
}
_FFN = {
    "dense": {"gate_w": "mlp.gate_proj.weight", "up_w": "mlp.up_proj.weight",
              "down_w": "mlp.down_proj.weight"},
    "moe": {"router_w": "mlp.gate.weight", "e_gate_w": "mlp.w_gate",
            "e_up_w": "mlp.w_up", "e_down_w": "mlp.w_down",
            "s_gate_w": "mlp.shared.gate_proj.weight",
            "s_up_w": "mlp.shared.up_proj.weight",
            "s_down_w": "mlp.shared.down_proj.weight"},
}
_NEXTN = {"nextn_enorm": "nextn.enorm.weight",
          "nextn_hnorm": "nextn.hnorm.weight",
          "nextn_proj_w": "nextn.eh_proj.weight",
          "nextn_final_norm": "nextn.norm.weight"}
assert tuple(_ATTN) == reference.ATTN_LEAVES
assert all(tuple(_FFN[k]) == reference.FFN_LEAVES[k] for k in _FFN)


def names_of(config: dict, nextn: bool = False) -> dict:
    """reference leaf -> the program's parameter name, a layer at a time;
    with ``nextn`` the next-token module's too."""
    names = {"embed": "model.embed.weight", "final_norm": "model.norm.weight",
             "head_w": "lm_head.weight"}
    for i, kind in enumerate(reference.layer_kinds(config)):
        names.update({f"l{i}_{leaf}": f"model.layers.{i}.{target}"
                      for leaf, target in {**_ATTN, **_FFN[kind]}.items()})
    if nextn:
        names.update(_NEXTN)
        names.update({f"nextn_{leaf}": f"nextn.block.{target}"
                      for leaf, target in {**_ATTN, **_FFN["moe"]}.items()})
    return names


def param_specs(config: dict, nextn: bool = False) -> dict:
    return reference.param_specs(config, nextn)
