"""Kimi-Linear (``model_type`` ``kimi_linear``, Moonshot AI 2025) as its
``config.json`` and the Kimi Linear report describe it, in plain jax.numpy:
pre-norm residual blocks ``x + Mix(RMSNorm(x))``, ``x + FFN(RMSNorm(x))``,
no position embedding anywhere, a final RMSNorm and an untied head.

- **KDA** (``linear_attn_config``): q, k, v = SiLU(conv4(W x)), a
  depthwise causal convolution along the sequence; q and k L2-normalised a
  head, q scaled by d^-1/2; log-decay a channel g = -exp(A_log) *
  softplus(W_f2 W_f1 x + dt_bias); beta = sigmoid(W_b x) a head; the gated
  delta rule, **token by token**: S~ = Diag(exp g_t) S, S = S~ + beta_t
  k_t (v_t - S~^T k_t)^T, o_t = S^T q_t; output W_o (RMSNorm_head(o) *
  sigmoid(W_g2 W_g1 x)).
- **MLA, NoPE**: q = W_q x a head of 128 + 64; [c, k_s] = W_kva x (512 +
  64, k_s shared by the heads); [k_n, v] = W_kvb RMSNorm(c); causal softmax
  of q.[k_n, k_s] / sqrt(192); W_o. The 64 "rope" columns keep their width
  and are not rotated (``mla_use_nope``).
- **Experts** (layers past ``first_k_dense_replace``): s = sigmoid(W_r x)
  over all routed experts; the ``num_experts_per_token`` largest of s + b
  are chosen; w = routed_scaling_factor * s / sum of the chosen s; y = sum
  w_i E_i(x) + E_shared(x); E(x) = W_down(SiLU(W_gate x) * W_up x).

Departures, each because the timed program does the same (the
configuration file's ``deployment`` and ``assumed``):

- this chip's share: ``num_experts`` experts are held (the first of the
  routed ones), the router still scores all of them, and what the absent
  experts would add is left out; the vocabulary is the slice held here:
  ids, logits and the loss are over it; the layers are the first
  ``num_hidden_layers`` published ones;
- the selection bias b starts at zero and is moved by a rule outside the
  gradient that no training step here runs: it is no leaf (``uncut_moe``
  takes one, for the test that ties the share to the model);
- the sizes the published config lacks: ``assumed``.

Memory: half a layer at a time under ``jax.checkpoint``, a KDA layer's heads
in groups, the recurrence in
blocks of time steps, the score square in blocks of query rows, the
feed-forwards, the head and the loss in blocks of tokens, so that a row of
8192 tokens fits beside the float32 train state. ``einsum`` is the
harness's, in the precision asked for; the recurrence itself is elementwise
float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import common

F32 = jnp.float32
TIME_BLOCK = 64      # time steps of the recurrence a checkpoint
QUERY_BLOCK = 256    # query rows of the score square a checkpoint
TOKEN_BLOCK = 2048   # tokens of a feed-forward, of the head and the loss
HEAD_GROUPS = 4      # groups of a KDA layer's heads, one at a time


def layer_kinds(config: dict) -> list:
    """(mixer, ffn) of every layer held, by the published 1-based lists."""
    kda = set(config["linear_attn_config"]["kda_layers"])
    return [("kda" if i in kda else "mla",
             "dense" if i <= config["first_k_dense_replace"] else "moe")
            for i in range(1, config["num_hidden_layers"] + 1)]


def sizes(config: dict) -> dict:
    lin, a = config["linear_attn_config"], config["assumed"]
    return {
        "h": config["hidden_size"], "rows": config["vocab_size"],
        "kda_heads": lin["num_heads"], "kda_dim": lin["head_dim"],
        "conv": lin["short_conv_kernel_size"], "gate_rank": a["gate_rank"],
        "heads": config["num_attention_heads"],
        "nope": config["qk_nope_head_dim"], "rope": config["qk_rope_head_dim"],
        "v_dim": config["v_head_dim"], "latent": config["kv_lora_rank"],
        "dense": config["intermediate_size"],
        "expert": config["moe_intermediate_size"],
        "held": config["num_experts"],
        "routed": config["deployment"]["num_experts_routed"],
        "top_k": config["num_experts_per_token"],
        "scale": config["routed_scaling_factor"],
        "eps": config["rms_norm_eps"], "l2_eps": a["l2_norm_eps"],
    }


def param_specs(config: dict) -> dict:
    z, a = sizes(config), config["assumed"]
    std = a["initializer_range"]
    h, kd = z["h"], z["kda_heads"] * z["kda_dim"]
    normal = lambda *shape: (shape, ("normal", std))  # noqa: E731
    ones = lambda *shape: (shape, ("ones",))  # noqa: E731
    mixers = {
        "kda": {
            "q_w": normal(h, kd), "k_w": normal(h, kd), "v_w": normal(h, kd),
            **{c: ((kd, z["conv"]), ("normal", a["conv_std"]))
               for c in ("q_conv", "k_conv", "v_conv")},
            "f1_w": normal(h, z["gate_rank"]), "f2_w": normal(z["gate_rank"], kd),
            "a_log": ((z["kda_heads"],), ("zeros",)),
            "dt_bias": ((kd,), ("normal", a["dt_bias_std"])),
            "b_w": normal(h, z["kda_heads"]),
            "g1_w": normal(h, z["gate_rank"]), "g2_w": normal(z["gate_rank"], kd),
            "o_norm": ones(z["kda_dim"]), "o_w": normal(kd, h)},
        "mla": {
            "q_w": normal(h, z["heads"] * (z["nope"] + z["rope"])),
            "kva_w": normal(h, z["latent"] + z["rope"]),
            "kv_norm": ones(z["latent"]),
            "kvb_w": normal(z["latent"], z["heads"] * (z["nope"] + z["v_dim"])),
            "o_w": normal(z["heads"] * z["v_dim"], h)},
    }
    gated = lambda w, *e: {"gate_w": normal(*e, h, w),  # noqa: E731
                           "up_w": normal(*e, h, w),
                           "down_w": normal(*e, w, h)}
    ffns = {
        "dense": gated(z["dense"]),
        "moe": {"router_w": normal(h, z["routed"]),
                **{"e_" + n: s for n, s in gated(z["expert"],
                                                 z["held"]).items()},
                **{"s_" + n: s for n, s in gated(z["expert"]).items()}},
    }
    one = {"embed": normal(z["rows"], h), "final_norm": ones(h),
           "head_w": normal(h, z["rows"])}
    for i, (mixer, ffn) in enumerate(layer_kinds(config)):
        one[f"l{i}_attn_norm"] = ones(h)
        one.update({f"l{i}_{mixer}_{n}": s for n, s in mixers[mixer].items()})
        one[f"l{i}_ffn_norm"] = ones(h)
        one.update({f"l{i}_{ffn}_{n}": s for n, s in ffns[ffn].items()})
    # no leaf is a stack of layers: the kinds differ from layer to layer
    return {n: (shape, how, False) for n, (shape, how) in one.items()}


def denominators(batch: dict) -> dict:
    return {"tokens": jnp.sum(batch["labels"] >= 0).astype(F32)}


# -- the layers ----------------------------------------------------------------

def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def short_conv(x, w):
    """y_t = sum_j w[:, j] x_{t-(k-1)+j} on [b, l, c], zeros before 0."""
    k, l = w.shape[-1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j:j + l] * w[:, j] for j in range(k))


def delta_rule(q, k, v, g, beta):
    """The recurrence on [b, l, heads, d] (beta [b, l, heads]), a token at
    a time, checkpointed every ``TIME_BLOCK`` steps."""
    b, l, heads, d = k.shape
    pad = -l % TIME_BLOCK
    if pad:  # tokens that write nothing and decay nothing
        q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, pad))
                                    + ((0, 0),) * (t.ndim - 2))
                            for t in (q, k, v, g, beta))

    def token(S, x):
        q_t, k_t, v_t, g_t, beta_t = x              # [b, heads, d], beta [b, heads]
        S = S * jnp.exp(g_t)[..., None]
        u = v_t - jnp.sum(S * k_t[..., None], axis=-2)
        S = S + (beta_t[..., None] * k_t)[..., None] * u[..., None, :]
        return S, jnp.sum(S * q_t[..., None], axis=-2)

    @jax.checkpoint
    def block(S, xs):
        return jax.lax.scan(token, S, xs)

    blocks = tuple(jnp.moveaxis(t, 1, 0).reshape(
        (-1, TIME_BLOCK) + t.shape[:1] + t.shape[2:])
        for t in (q, k, v, g, beta))
    _, o = jax.lax.scan(block, jnp.zeros((b, heads, d, v.shape[-1]), F32),
                        blocks)
    return jnp.moveaxis(o.reshape((l + pad,) + o.shape[2:]), 0, 1)[:, :l]


def kda(x, p, z, einsum):
    """The heads are independent up to the output projection, which sums
    over them: ``HEAD_GROUPS`` groups of heads, one after the other, each
    under its own checkpoint, so that a quarter of the layer's float32
    activations exists at a time."""
    b, l, h = x.shape
    heads, d = z["kda_heads"], z["kda_dim"]
    groups = math.gcd(heads, HEAD_GROUPS)
    per = heads // groups
    columns = lambda w: jnp.moveaxis(  # noqa: E731  [.., heads * d] by group
        w.reshape(w.shape[:-1] + (groups, per * d)), -2, 0)
    by_group = {
        **{n: columns(p[n]) for n in ("q_w", "k_w", "v_w", "f2_w", "g2_w",
                                      "dt_bias")},
        **{n: p[n].reshape(groups, per * d, -1)
           for n in ("q_conv", "k_conv", "v_conv", "o_w")},
        "a_log": p["a_log"].reshape(groups, per),
        "b_w": jnp.moveaxis(p["b_w"].reshape(h, groups, per), 1, 0),
    }

    @jax.checkpoint
    def some(w):
        proj = lambda m: einsum("blh,hk->blk", x, m)  # noqa: E731
        q, k, v = (jax.nn.silu(short_conv(proj(w[n + "_w"]), w[n + "_conv"]))
                   .reshape(b, l, per, d) for n in "qkv")
        unit = lambda t: t * jax.lax.rsqrt(  # noqa: E731
            jnp.sum(jnp.square(t), -1, keepdims=True) + z["l2_eps"])
        q, k = unit(q) * d ** -0.5, unit(k)
        low = lambda n: einsum("blr,rk->blk", proj(p[n + "1_w"]),  # noqa: E731
                               w[n + "2_w"])
        g = -jnp.exp(w["a_log"])[:, None] * jax.nn.softplus(
            low("f") + w["dt_bias"]).reshape(b, l, per, d)
        beta = jax.nn.sigmoid(proj(w["b_w"]))
        o = rms_norm(delta_rule(q, k, v, g, beta), p["o_norm"], z["eps"])
        o = o.reshape(b, l, per * d) * jax.nn.sigmoid(low("g"))
        return einsum("blk,kh->blh", o, w["o_w"])

    return jnp.sum(jax.lax.map(some, by_group), axis=0)


def mla(x, p, z, einsum):
    b, l, _ = x.shape
    heads, nope, rope = z["heads"], z["nope"], z["rope"]
    q = einsum("blh,hk->blk", x, p["q_w"]).reshape(b, l, heads, nope + rope)
    kva = einsum("blh,hk->blk", x, p["kva_w"])
    c, k_shared = kva[..., :z["latent"]], kva[..., z["latent"]:]
    kvb = einsum("blc,ck->blk", rms_norm(c, p["kv_norm"], z["eps"]),
                 p["kvb_w"]).reshape(b, l, heads, nope + z["v_dim"])
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
        k_shared[:, :, None, :], (b, l, heads, rope))], axis=-1)
    v = kvb[..., nope:]
    rows = min(QUERY_BLOCK, l)
    at = jnp.arange(l, dtype=jnp.int32)

    @jax.checkpoint
    def attend(lo):
        q_rows = jax.lax.dynamic_slice_in_dim(q, lo, rows, axis=1)
        s = einsum("bqnd,bknd->bnqk", q_rows, k) / math.sqrt(nope + rope)
        seen = at[None, :] <= (lo + jnp.arange(rows, dtype=jnp.int32))[:, None]
        s = jnp.where(seen, s, -jnp.inf)
        return einsum("bnqk,bknd->bqnd", jax.nn.softmax(s, axis=-1), v)

    # int32 whatever jax_enable_x64 says: the TPU emulates 64-bit integers
    o = jax.lax.map(attend, jnp.arange(0, l, rows, dtype=jnp.int32))
    o = jnp.moveaxis(o, 0, 1).reshape(b, l, heads * z["v_dim"])
    return einsum("blk,kh->blh", o, p["o_w"])


def gated_mlp(x, gate_w, up_w, down_w, einsum):
    a = jax.nn.silu(einsum("blh,hk->blk", x, gate_w)) \
        * einsum("blh,hk->blk", x, up_w)
    return einsum("blk,kh->blh", a, down_w)


def route(x, router_w, bias, z, einsum):
    """(chosen experts [.., k], their weights [.., k])."""
    s = jax.nn.sigmoid(einsum("blh,he->ble", x, router_w))
    _, chosen = jax.lax.top_k(s if bias is None else s + bias, z["top_k"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, z["scale"] * picked / jnp.sum(picked, -1, keepdims=True)


def routed_part(x, chosen, weights, experts, first, einsum):
    """What the experts ``first``, ``first`` + 1, ... give: a dense masked
    sum, every expert over every token (one product over the stack)."""
    count = experts["gate_w"].shape[0]
    a = jax.nn.silu(einsum("blh,ehk->eblk", x, experts["gate_w"])) \
        * einsum("blh,ehk->eblk", x, experts["up_w"])
    mine = chosen[None] == (first + jnp.arange(count, dtype=chosen.dtype)
                            )[:, None, None, None]
    w = jnp.sum(jnp.where(mine, weights[None], 0.0), axis=-1)    # [e, b, l]
    # sum_e w_e (a_e W_e) = sum_e (w_e a_e) W_e: one product over e and k
    return einsum("eblk,ekh->blh", w[..., None] * a, experts["down_w"])


def moe(x, p, z, einsum):
    chosen, weights = route(x, p["router_w"], None, z, einsum)
    held = {n: p["e_" + n] for n in ("gate_w", "up_w", "down_w")}
    return (routed_part(x, chosen, weights, held, 0, einsum)
            + gated_mlp(x, p["s_gate_w"], p["s_up_w"], p["s_down_w"], einsum))


def uncut_moe(x, router_w, bias, experts, shared, z, einsum):
    """The whole layer, every routed expert present (``experts`` holds
    all of them): what the shares have to add up to."""
    chosen, weights = route(x, router_w, bias, z, einsum)
    return (routed_part(x, chosen, weights, experts, 0, einsum)
            + gated_mlp(x, shared["gate_w"], shared["up_w"],
                        shared["down_w"], einsum))


MIXERS = {"kda": kda, "mla": mla}


def ffn(kind, x, p, z, einsum):
    if kind == "moe":
        return moe(x, p, z, einsum)
    return gated_mlp(x, p["gate_w"], p["up_w"], p["down_w"], einsum)


def of_layer(params: dict, i: int, part: str) -> dict:
    prefix = f"l{i}_{part}_"
    return {n[len(prefix):]: v for n, v in params.items()
            if n.startswith(prefix)}


def hidden_states(params, ids, config, einsum):
    """The trunk: embedding through the last block, before the final norm."""
    z = sizes(config)
    x = params["embed"][ids]
    for i, (mixer, kind) in enumerate(layer_kinds(config)):
        # each half of a block under its own checkpoint: the backward
        # holds one half's activations at a time
        @jax.checkpoint
        def mix(x, norm, p, mixer=mixer):
            return x + MIXERS[mixer](rms_norm(x, norm, z["eps"]), p, z,
                                     einsum)

        @jax.checkpoint
        def feed(x, norm, p, kind=kind):
            # a feed-forward works a token at a time: in blocks of tokens
            @jax.checkpoint
            def some(x_block):
                return x_block + ffn(kind, rms_norm(x_block, norm, z["eps"]),
                                     p, z, einsum)

            b, l, h = x.shape
            tokens = min(TOKEN_BLOCK, l)
            if l % tokens:
                return some(x)
            blocks = jax.lax.map(some, jnp.moveaxis(
                x.reshape(b, l // tokens, tokens, h), 1, 0))
            return jnp.moveaxis(blocks, 0, 1).reshape(b, l, h)

        x = mix(x, params[f"l{i}_attn_norm"], of_layer(params, i, mixer))
        x = feed(x, params[f"l{i}_ffn_norm"], of_layer(params, i, kind))
    return x


def block_loss(params, block, denoms, config, einsum):
    """This block of rows' part of the batch's mean next-token loss."""
    ids, labels = block["ids"], block["labels"]
    z = sizes(config)
    x = hidden_states(params, ids, config, einsum)
    x = rms_norm(x, params["final_norm"], z["eps"]).reshape(-1, z["h"])
    labels = labels.reshape(-1)
    tokens = min(TOKEN_BLOCK, x.shape[0])
    pad = -x.shape[0] % tokens
    x = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, tokens, z["h"])
    labels = jnp.pad(labels, (0, pad), constant_values=-1).reshape(-1, tokens)

    @jax.checkpoint
    def part(x_block, label_block):
        logits = einsum("th,hv->tv", x_block, params["head_w"])
        return common.cross_entropy_sum(logits, label_block)

    total = jnp.sum(jax.lax.map(lambda xs: part(*xs), (x, labels)))
    return total / denoms["tokens"]
