"""The Falcon-H1 family: ``text.models.falcon_h1.FalconH1ForCausalLM``
holding this chip's share (the configuration's ``num_hidden_layers`` first
layers of the ``deployment.num_hidden_layers_published`` and its
``vocab_size`` rows of the vocabulary). The model is served, not trained
(no cut of it fits a chip's train state), so what a cell needs of it is in
``falcon_h1_serve.py``; here are the names: reference leaf -> the program's
parameter, through the reference's own ``param_specs``.
"""
from __future__ import annotations

import json
import os

# at import, so that a checkout whose program lacks the model fails here,
# before a weight is made or a chip is asked for anything
from paddle_tpu.text.models.falcon_h1 import (FalconH1Config,  # noqa: F401
                                              FalconH1ForCausalLM)
from benchmark.reference import falcon_h1 as reference

_LAYER = {
    "in_norm": "input_norm.weight", "ssm_in_w": "mamba.in_proj.weight",
    "ssm_conv_w": "mamba.conv_weight", "ssm_conv_b": "mamba.conv_bias",
    "ssm_dt_bias": "mamba.dt_bias", "ssm_a_log": "mamba.A_log",
    "ssm_d": "mamba.D", "ssm_norm": "mamba.norm.weight",
    "ssm_out_w": "mamba.out_proj.weight", "attn_q_w": "attn.q_proj.weight",
    "attn_k_w": "attn.k_proj.weight", "attn_v_w": "attn.v_proj.weight",
    "attn_o_w": "attn.o_proj.weight", "ffn_norm": "ffn_norm.weight",
    "mlp_gate_w": "ffn.gate_proj.weight", "mlp_up_w": "ffn.up_proj.weight",
    "mlp_down_w": "ffn.down_proj.weight",
}
assert tuple(_LAYER) == reference.LAYER_LEAVES


def names_of(config: dict) -> dict:
    """reference leaf -> the program's parameter name, a layer at a time."""
    names = {"embed": "model.embed.weight", "final_norm": "model.norm.weight",
             "head_w": "lm_head.weight"}
    for i in range(config["num_hidden_layers"]):
        names.update({f"l{i}_{leaf}": f"model.layers.{i}.{target}"
                      for leaf, target in _LAYER.items()})
    return names


def param_specs(config: dict) -> dict:
    return reference.param_specs(config)


with open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "configs", "falcon-h1-34b.json")) as _f:
    NAMES = names_of(json.load(_f))
