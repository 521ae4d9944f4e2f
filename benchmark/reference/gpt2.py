"""GPT-2 as Radford et al. (2019) and the released ``gpt2`` code describe
it, in plain jax.numpy: learned token and position embeddings, pre-LN
blocks of causal self-attention and a 4x GELU (tanh form) MLP, a final
LayerNorm, and the token embedding again as the output head.

Departures, each because the timed program does the same: the embedding
holds ``vocab_rows_held`` rows, the published vocabulary padded up, and the
softmax runs over all of them; no dropout (the configuration sets it to 0).
Initialisation is the published one: normal(0, 0.02), the two residual
projections scaled by 1/sqrt(2 * layers), biases 0, LayerNorm 1 and 0.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import common

_ONE = ("wte", "wpe", "ln_f_w", "ln_f_b")


def param_specs(config: dict) -> dict:
    h, layers = config["n_embd"], config["n_layer"]
    inner = config.get("n_inner") or 4 * h
    std = config["initializer_range"]
    resid = std / math.sqrt(2 * layers)
    rows = config["assumed"]["vocab_rows_held"]
    one = {"wte": ((rows, h), ("normal", std)),
           "wpe": ((config["n_positions"], h), ("normal", std)),
           "ln_f_w": ((h,), ("ones",)), "ln_f_b": ((h,), ("zeros",))}
    per_layer = {
        "ln_1_w": ((h,), ("ones",)), "ln_1_b": ((h,), ("zeros",)),
        "qkv_w": ((h, 3 * h), ("normal", std)),
        "qkv_b": ((3 * h,), ("zeros",)),
        "attn_proj_w": ((h, h), ("normal", resid)),
        "attn_proj_b": ((h,), ("zeros",)),
        "ln_2_w": ((h,), ("ones",)), "ln_2_b": ((h,), ("zeros",)),
        "fc_w": ((h, inner), ("normal", std)),
        "fc_b": ((inner,), ("zeros",)),
        "mlp_proj_w": ((inner, h), ("normal", resid)),
        "mlp_proj_b": ((h,), ("zeros",)),
    }
    specs = {n: (shape, how, False) for n, (shape, how) in one.items()}
    specs.update({n: ((layers,) + shape, how, True)
                  for n, (shape, how) in per_layer.items()})
    return specs


def denominators(batch: dict) -> dict:
    return {"tokens": jnp.sum(batch["labels"] >= 0).astype(common.F32)}


def block_loss(params, block, denoms, config, einsum):
    """This block of rows' part of the batch's mean next-token loss."""
    ids, labels = block["ids"], block["labels"]
    b, l = ids.shape
    heads, eps = config["n_head"], config["layer_norm_epsilon"]
    causal = jnp.where(jnp.tril(jnp.ones((l, l), bool)), 0.0, -jnp.inf)
    x = params["wte"][ids] + params["wpe"][:l]

    def layer(x, p):
        a = common.layer_norm(x, p["ln_1_w"], p["ln_1_b"], eps)
        qkv = einsum("blh,hk->blk", a, p["qkv_w"]) + p["qkv_b"]
        q, k, v = (t.reshape(b, l, heads, -1)
                   for t in jnp.split(qkv, 3, axis=-1))
        o = common.attention(einsum, q, k, v, causal).reshape(b, l, -1)
        x = x + einsum("blh,hk->blk", o, p["attn_proj_w"]) + p["attn_proj_b"]
        a = common.layer_norm(x, p["ln_2_w"], p["ln_2_b"], eps)
        f = common.gelu_tanh(einsum("blh,hk->blk", a, p["fc_w"]) + p["fc_b"])
        x = x + einsum("blk,kh->blh", f, p["mlp_proj_w"]) + p["mlp_proj_b"]
        return x, None

    stacked = {n: v for n, v in params.items() if n not in _ONE}
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, stacked)
    x = common.layer_norm(x, params["ln_f_w"], params["ln_f_b"], eps)
    logits = einsum("blh,vh->blv", x, params["wte"])
    return common.cross_entropy_sum(logits, labels) / denoms["tokens"]
