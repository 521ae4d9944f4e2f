"""The serve side of the GPT family: ``text.models.gpt.GPTForCausalLM``
with the harness's weights resident in bfloat16, under
``inference.serving.TokenServingEngine`` as the cell's file sets it up.
Found by ``drivers/serve.py`` as ``<family>_serve``; no train cell reads it.

Also here, because they belong to the yardstick: the bytes a decode step
has to read and the operations a served token costs, from shapes alone,
whatever kernel or tier does the work.
"""
from __future__ import annotations

import jax

from benchmark.families.gpt import NAMES
from benchmark.lib import layout
from benchmark.reference import common
from benchmark.reference import gpt2 as reference
from benchmark.reference.gpt2_logits import margins as reference_margins  # noqa: F401,E501 (the entry)

KV_BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


def weights(config: dict, seed: int, dtype: str) -> dict:
    """The seed's weights under the program's names, in the type they are
    served in, made on the device in one jitted call."""
    specs = reference.param_specs(config)

    def make(key):
        params = {n: common.init_leaf(specs, n, key).astype(dtype)
                  for n in specs}
        return layout.to_program(NAMES, specs, params)

    return jax.jit(make)(common.seed_key(seed))


def build_model(config: dict, named_weights: dict):
    from paddle_tpu.jit.functionalize import set_params
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

    model = GPTForCausalLM(GPTConfig(
        vocab_size=config["assumed"]["vocab_rows_held"],
        hidden_size=config["n_embd"], num_layers=config["n_layer"],
        num_heads=config["n_head"],
        max_position_embeddings=config["n_positions"],
        hidden_dropout=config["resid_pdrop"],
        attention_dropout=config["attn_pdrop"],
        initializer_range=config["initializer_range"],
        layer_norm_epsilon=config["layer_norm_epsilon"]))
    set_params(model, named_weights)
    model.eval()
    return model


def build_engine(model, engine: dict):
    """``TokenServingEngine`` as the cell's ``engine`` group states it: no
    deadline, no speculation, nothing the group does not name."""
    from paddle_tpu.inference.serving import (TokenServeConfig,
                                              TokenServingEngine)

    return TokenServingEngine(model, TokenServeConfig(
        capacity=engine["capacity"],
        decode_buckets=tuple(engine["decode_buckets"]),
        max_running=engine["max_running"],
        prefill_chunk=engine["prefill_chunk"],
        kv_blocks=engine["kv_blocks"],
        kv_block_size=engine["kv_block_size"],
        kv_dtype=engine["kv_dtype"], default_deadline_s=None,
        spec_k=0, drain_grace_s=engine.get("drain_grace_s", 5.0)))


# -- counts from shapes ------------------------------------------------------

def _matmul_params(config: dict) -> int:
    """Parameters of the per-token matmuls of the trunk (no embeddings)."""
    h = config["n_embd"]
    inner = config.get("n_inner") or 4 * h
    return config["n_layer"] * (4 * h * h + 2 * h * inner)


def weight_bytes(config: dict, dtype_bytes: int = 2) -> int:
    """What one forward reads of the weights: every matmul and its bias,
    the LayerNorms and the tied embedding once (as the head)."""
    h, layers = config["n_embd"], config["n_layer"]
    inner = config.get("n_inner") or 4 * h
    small = layers * (3 * h + h + inner + h + 4 * h) + 2 * h
    head = h * config["assumed"]["vocab_rows_held"]
    return dtype_bytes * (_matmul_params(config) + small + head)


def kv_bytes_per_token(config: dict, kv_dtype: str) -> int:
    """K and V of one position over every layer."""
    return 2 * config["n_layer"] * config["n_embd"] * KV_BYTES[kv_dtype]


def decode_step_bytes(config: dict, kv_dtype: str, live_tokens: int) -> int:
    """The least a decode step must read: the weights once and the cached
    K and V of every position its sequences attend to (``live_tokens``,
    summed over the step's sequences)."""
    return weight_bytes(config) + live_tokens * kv_bytes_per_token(
        config, kv_dtype)


def forward_flops(config: dict, tokens: int, attended: int,
                  emitted: int) -> float:
    """Operations the served work needs: 2 a parameter of the trunk's
    matmuls for each of ``tokens`` positions forwarded (prompt or output),
    4 h a layer for each pair of a query and a cached key (``attended``
    pairs in all: QK^T and PV), and the head, 2 h a row (padded rows too:
    the chip multiplies them), for the ``emitted`` tokens alone: a prompt's
    other positions need no logits."""
    h = config["n_embd"]
    head = h * config["assumed"]["vocab_rows_held"]
    return (2.0 * tokens * _matmul_params(config)
            + 4.0 * h * config["n_layer"] * attended
            + 2.0 * emitted * head)
