"""The LongCat-Flash family: ``text.models.longcat_flash.
LongcatFlashForCausalLM`` holding this chip's share (the configuration's
``n_routed_experts`` routed experts of the
``deployment.n_routed_experts_published``, every identity expert, its
``num_layers`` double layers, its ``vocab_size`` rows of the vocabulary).
The model is served, not trained (no cut of it fits a chip's train state),
so what a cell needs of it is in ``longcat_flash_serve.py``; here are the
names: reference leaf -> the program's parameter.
"""
from __future__ import annotations

# at import, so that a checkout whose program lacks the model fails here,
# before a weight is made or a chip is asked for anything
from paddle_tpu.text.models.longcat_flash import (  # noqa: F401
    LongcatFlashConfig, LongcatFlashForCausalLM)
from benchmark.reference import longcat_flash as reference

_ATTN = {
    "attn_norm": "input_layernorm.{j}.weight",
    "q_a_w": "self_attn.{j}.q_a_proj.weight",
    "q_a_norm": "self_attn.{j}.q_a_norm.weight",
    "q_b_w": "self_attn.{j}.q_b_proj.weight",
    "kv_a_w": "self_attn.{j}.kv_a_proj.weight",
    "kv_a_norm": "self_attn.{j}.kv_a_norm.weight",
    "kv_b_w": "self_attn.{j}.kv_b_proj.weight",
    "o_w": "self_attn.{j}.o_proj.weight",
    "post_attn_norm": "post_attention_layernorm.{j}.weight",
}
_FFN = {"gate_w": "mlps.{j}.gate_proj.weight",
        "up_w": "mlps.{j}.up_proj.weight",
        "down_w": "mlps.{j}.down_proj.weight"}
_MOE = {"router_w": "mlp.router.weight", "e_gate_w": "mlp.w_gate",
        "e_up_w": "mlp.w_up", "e_down_w": "mlp.w_down"}
assert tuple(_ATTN) == reference.ATTN_LEAVES
assert tuple(_FFN) == reference.FFN_LEAVES
assert tuple(_MOE) == reference.MOE_LEAVES


def names_of(config: dict) -> dict:
    """reference leaf -> the program's parameter name, a double layer at a
    time."""
    names = {"embed": "model.embed.weight", "final_norm": "model.norm.weight",
             "head_w": "lm_head.weight"}
    for i in range(config["num_layers"]):
        at = f"model.layers.{i}."
        for j in (0, 1):
            names.update({f"l{i}_attn{j}_{leaf}": at + target.format(j=j)
                          for leaf, target in _ATTN.items()})
            names.update({f"l{i}_ffn{j}_{leaf}": at + target.format(j=j)
                          for leaf, target in _FFN.items()})
        names.update({f"l{i}_{leaf}": at + target
                      for leaf, target in _MOE.items()})
    return names


def param_specs(config: dict) -> dict:
    return reference.param_specs(config)
