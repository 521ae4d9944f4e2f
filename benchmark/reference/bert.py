"""BERT as Devlin et al. (2018) and ``google-research/bert`` describe it,
in plain jax.numpy: token, position and segment embeddings under a
LayerNorm, post-LN blocks of bidirectional self-attention and a 4x GELU
MLP (the tanh form, as ``modeling.py`` writes it), a tanh pooler over the
first token, and the two pre-training heads: masked LM (dense, GELU,
LayerNorm, then the token embedding again) and next-sentence.

Departures, each because the timed program does the same: the embedding
holds ``vocab_rows_held`` rows and the softmax runs over all of them; the
masked-LM head computes logits at every position and has no output bias;
no dropout (the configuration sets it to 0). Initialisation: normal(0,
0.02) for every weight (the published one truncates it at two standard
deviations; listed under ``assumed``), biases 0, LayerNorm 1 and 0.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common

_ONE = ("word", "position", "token_type", "emb_ln_w", "emb_ln_b",
        "pooler_w", "pooler_b", "mlm_w", "mlm_b", "mlm_ln_w", "mlm_ln_b",
        "nsp_w", "nsp_b")


def param_specs(config: dict) -> dict:
    h, layers = config["hidden_size"], config["num_hidden_layers"]
    inner = config["intermediate_size"]
    w = ("normal", config["initializer_range"])
    ones, zeros = ("ones",), ("zeros",)
    one = {"word": ((config["assumed"]["vocab_rows_held"], h), w),
           "position": ((config["max_position_embeddings"], h), w),
           "token_type": ((config["type_vocab_size"], h), w),
           "emb_ln_w": ((h,), ones), "emb_ln_b": ((h,), zeros),
           "pooler_w": ((h, h), w), "pooler_b": ((h,), zeros),
           "mlm_w": ((h, h), w), "mlm_b": ((h,), zeros),
           "mlm_ln_w": ((h,), ones), "mlm_ln_b": ((h,), zeros),
           "nsp_w": ((h, 2), w), "nsp_b": ((2,), zeros)}
    per_layer = {
        "qkv_w": ((h, 3 * h), w), "qkv_b": ((3 * h,), zeros),
        "attn_out_w": ((h, h), w), "attn_out_b": ((h,), zeros),
        "ln1_w": ((h,), ones), "ln1_b": ((h,), zeros),
        "fc1_w": ((h, inner), w), "fc1_b": ((inner,), zeros),
        "fc2_w": ((inner, h), w), "fc2_b": ((h,), zeros),
        "ln2_w": ((h,), ones), "ln2_b": ((h,), zeros),
    }
    specs = {n: (shape, how, False) for n, (shape, how) in one.items()}
    specs.update({n: ((layers,) + shape, how, True)
                  for n, (shape, how) in per_layer.items()})
    return specs


def denominators(batch: dict) -> dict:
    return {"masked": jnp.maximum(
                jnp.sum(batch["mlm_labels"] >= 0), 1).astype(common.F32),
            "rows": float(batch["ids"].shape[0])}


def block_loss(params, block, denoms, config, einsum):
    """This block of rows' part of the batch's loss: the mean masked-LM
    loss over the masked positions plus the mean next-sentence loss."""
    ids = block["ids"]
    b, l = ids.shape
    heads, eps = config["num_attention_heads"], config["layer_norm_eps"]
    bias = ((1.0 - block["attention_mask"].astype(common.F32)) * -1e9
            )[:, None, None, :]
    x = (params["word"][ids] + params["position"][:l]
         + params["token_type"][block["token_type_ids"]])
    x = common.layer_norm(x, params["emb_ln_w"], params["emb_ln_b"], eps)

    def layer(x, p):
        qkv = einsum("blh,hk->blk", x, p["qkv_w"]) + p["qkv_b"]
        q, k, v = (t.reshape(b, l, heads, -1)
                   for t in jnp.split(qkv, 3, axis=-1))
        o = common.attention(einsum, q, k, v, bias).reshape(b, l, -1)
        o = einsum("blh,hk->blk", o, p["attn_out_w"]) + p["attn_out_b"]
        x = common.layer_norm(x + o, p["ln1_w"], p["ln1_b"], eps)
        f = common.gelu_tanh(einsum("blh,hk->blk", x, p["fc1_w"])
                             + p["fc1_b"])
        f = einsum("blk,kh->blh", f, p["fc2_w"]) + p["fc2_b"]
        return common.layer_norm(x + f, p["ln2_w"], p["ln2_b"], eps), None

    stacked = {n: v for n, v in params.items() if n not in _ONE}
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, stacked)
    pooled = jnp.tanh(einsum("bh,hk->bk", x[:, 0], params["pooler_w"])
                      + params["pooler_b"])
    t = common.gelu_tanh(einsum("blh,hk->blk", x, params["mlm_w"])
                         + params["mlm_b"])
    t = common.layer_norm(t, params["mlm_ln_w"], params["mlm_ln_b"], eps)
    logits = einsum("blh,vh->blv", t, params["word"])
    nsp = einsum("bh,hk->bk", pooled, params["nsp_w"]) + params["nsp_b"]
    return (common.cross_entropy_sum(logits, block["mlm_labels"])
            / denoms["masked"]
            + common.cross_entropy_sum(nsp, block["nsp_labels"])
            / denoms["rows"])
