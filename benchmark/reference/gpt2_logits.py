"""GPT-2's logits at every position of whole sequences, in plain jax.numpy:
one full forward, no cache, no program. The serve cells' plain reference.

The model is ``gpt2.py``'s, equation for equation (pre-LN blocks, tanh GELU,
the token embedding again as the head over all ``vocab_rows_held`` rows),
and its weights are that file's ``param_specs`` made from the seed by
``common.init_params``: nothing the program made. The block body is written
out again here because ``gpt2.py`` gives only a loss and a file a train cell
reads is not edited by a PR that adds cells (PERF.md, Open questions).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import common
from .gpt2 import _ONE, param_specs  # noqa: F401 (param_specs: the entry)


def logits(params, ids, config, einsum):
    """``[rows, length, vocab_rows_held]`` float32 logits of ``ids``
    ``[rows, length]``; a position sees itself and what comes before it."""
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
    x, _ = jax.lax.scan(layer, x, stacked)
    x = common.layer_norm(x, params["ln_f_w"], params["ln_f_b"], eps)
    return einsum("blh,vh->blv", x, params["wte"])


@functools.partial(jax.jit, static_argnums=(0, 1))
def _margins(frozen_config, precisions, params, ids, served):
    """For every position: how far the reference's logit of the token that
    was served next lies below the reference's best, in units of that
    position's standard deviation over the vocabulary; and the same for the
    token each lower precision of ``precisions[1:]`` puts first."""
    config = dict(frozen_config)
    ref = logits(params, ids, config, common.make_einsum(precisions[0]))
    best = jnp.max(ref, axis=-1)
    std = jnp.std(ref, axis=-1)

    def below(tokens):
        at = jnp.take_along_axis(ref, tokens[..., None], axis=-1)[..., 0]
        return (best - at) / std

    out = [below(served)]
    for precision in precisions[1:]:
        low = logits(params, ids, config, common.make_einsum(precision))
        out.append(below(jnp.argmax(low, axis=-1)))
    return tuple(out)


def margins(config: dict, seed: int, ids, served, precisions=("float32",),
            rows_per_block: int = 1):
    """``ids``, ``served``: int32 ``[rows, length]``; ``served[r, p]`` is
    the token that followed position ``p`` of row ``r`` (any id where none
    did: the caller masks). Returns one ``[rows, length]`` numpy array of
    gaps a precision: the served tokens' first, then each control's own."""
    import numpy as np

    frozen = tuple(sorted((k, v) for k, v in config.items()
                          if isinstance(v, (int, float, str, bool))))
    params = common.init_params(param_specs(config), seed)
    outs = [[] for _ in precisions]
    for lo in range(0, ids.shape[0], rows_per_block):
        got = _margins(frozen, tuple(precisions), params,
                       jnp.asarray(ids[lo:lo + rows_per_block]),
                       jnp.asarray(served[lo:lo + rows_per_block]))
        for o, g in zip(outs, got):
            o.append(np.asarray(g))
    return [np.concatenate(o) for o in outs]
