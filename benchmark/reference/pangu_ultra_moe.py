"""openPangu-Ultra-MoE (``model_type`` ``pangu_ultra_moe``) as its
``config.json`` describes it, in plain jax.numpy: multi-head latent
attention with a compressed query and decoupled rotary columns in every
layer, a gated SiLU MLP in the leading ``first_k_dense_replace`` layers and
routed experts beside one shared expert in the rest, a norm after each
sub-layer as well as before it (``sandwich_norm``), and one next-token
prediction module. The serve cell's plain reference: one full forward over
whole rows, no cache, no absorption, no sorting of pairs, no program.

Every norm is an RMSNorm (eps ``rms_norm_eps``) with its own gain::

    a  = RMSNorm_post_attn(attn(RMSNorm_in(x)));      x = x + a
    m  = RMSNorm_post_mlp(mlp(RMSNorm_pre_mlp(x)));   x = x + m

    attn(u): cq = RMSNorm(u W_qa)                     (q_lora_rank)
             q  = cq W_qb            a head of qk_nope + qk_rope columns
             [c | k_r] = u W_kva     (kv_lora_rank + qk_rope, k_r one for all heads)
             [k_n | v] = RMSNorm(c) W_kvb             a head of qk_nope + v_head
             q's last qk_rope columns a head and k_r rotated at the token's
             position (theta ``rope_theta``, no scaling, the rotate-half
             pairing over those columns)
             softmax_causal(q . [k_n | k_r] / sqrt(qk_nope + qk_rope)) v, W_o
    mlp(g):  W_down(silu(W_gate g) * W_up g)          below first_k_dense_replace
             else  s = sigmoid(W_r g) in float32 over all routed experts;
             the num_experts_per_tok largest chosen (no groups; the
             selection bias is zero); w = routed_scaling_factor * s / sum of
             the chosen s;  sum_i w_i E_i(g) + E_shared(g)
    logits = RMSNorm_f(x) W_head

    next-token module (one): h'_i = [RMSNorm_e(E[t_{i+1}]) | RMSNorm_h(x_i)] W_p
             with x_i the trunk's output before its final norm; one block as
             above with an expert layer; logits = RMSNorm_n(.) W_head with
             the trunk's embedding and head: position i guesses t_{i+2}

Departures, each because the timed program does the same (the
configuration file's ``deployment`` and ``assumed``): this chip's share is
``n_routed_experts`` experts (the first of the routed ones; the router still
scores all ``deployment.n_routed_experts_published`` and what the absent
experts would add is left out), the ``num_hidden_layers`` layers held (the
first ``first_k_dense_replace`` of them dense) and the slice of
``vocab_size`` rows; what the published config lacks is ``assumed``.

Memory: ``margins`` goes layer by layer (an expert layer's float32 leaves
are 4 GB), a layer's leaves made from the seed when it is reached and
dropped after it, the rows ``rows_per_block`` at a time, the score square
``QUERY_BLOCK`` query rows at a time. ``einsum`` is the harness's, in the
precision asked for; norms, softmax, the router's sigmoid and the gates are
elementwise float32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import common

F32 = jnp.float32
QUERY_BLOCK = 512    # query rows of the score square at a time


def sizes(config: dict) -> dict:
    return {
        "h": config["hidden_size"], "rows": config["vocab_size"],
        "layers": config["num_hidden_layers"],
        "dense_layers": config["first_k_dense_replace"],
        "heads": config["num_attention_heads"],
        "q_rank": config["q_lora_rank"], "latent": config["kv_lora_rank"],
        "nope": config["qk_nope_head_dim"], "rope": config["qk_rope_head_dim"],
        "v_dim": config["v_head_dim"], "dense": config["intermediate_size"],
        "expert": config["moe_intermediate_size"],
        "shared": config["n_shared_experts"],
        "held": config["n_routed_experts"],
        "routed": config["deployment"]["n_routed_experts_published"],
        "top_k": config["num_experts_per_tok"],
        "scale": config["routed_scaling_factor"],
        "eps": config["rms_norm_eps"], "theta": config["rope_theta"],
    }


def layer_kinds(config: dict) -> list:
    """'dense' or 'moe' for every layer held."""
    return ["dense" if i < config["first_k_dense_replace"] else "moe"
            for i in range(config["num_hidden_layers"])]


ATTN_LEAVES = ("attn_norm", "q_a_w", "q_a_norm", "q_b_w", "kv_a_w",
               "kv_a_norm", "kv_b_w", "o_w", "post_attn_norm",
               "pre_mlp_norm", "post_mlp_norm")
FFN_LEAVES = {
    "dense": ("gate_w", "up_w", "down_w"),
    "moe": ("router_w", "e_gate_w", "e_up_w", "e_down_w", "s_gate_w",
            "s_up_w", "s_down_w"),
}
NEXTN_LEAVES = ("enorm", "hnorm", "proj_w", "final_norm")


def layer_leaves(kind: str) -> tuple:
    return ATTN_LEAVES + FFN_LEAVES[kind]


def param_specs(config: dict, nextn: bool = False) -> dict:
    """Every leaf by its own name (``l3_q_b_w``, ``nextn_proj_w``), none a
    stack of layers, so that a layer's leaves can be made alone. With
    ``nextn`` the next-token module's leaves too (a deployment that does
    not speculate does not load them)."""
    z = sizes(config)
    std = config["assumed"]["initializer_range"]
    h, heads = z["h"], z["heads"]
    normal = lambda *shape: (shape, ("normal", std))  # noqa: E731
    ones = lambda *shape: (shape, ("ones",))  # noqa: E731
    attn = {
        "attn_norm": ones(h), "q_a_w": normal(h, z["q_rank"]),
        "q_a_norm": ones(z["q_rank"]),
        "q_b_w": normal(z["q_rank"], heads * (z["nope"] + z["rope"])),
        "kv_a_w": normal(h, z["latent"] + z["rope"]),
        "kv_a_norm": ones(z["latent"]),
        "kv_b_w": normal(z["latent"], heads * (z["nope"] + z["v_dim"])),
        "o_w": normal(heads * z["v_dim"], h),
        "post_attn_norm": ones(h), "pre_mlp_norm": ones(h),
        "post_mlp_norm": ones(h),
    }
    assert tuple(attn) == ATTN_LEAVES
    gated = lambda w, *e: {"gate_w": normal(*e, h, w),  # noqa: E731
                           "up_w": normal(*e, h, w),
                           "down_w": normal(*e, w, h)}
    ffn = {
        "dense": gated(z["dense"]),
        "moe": {"router_w": normal(h, z["routed"]),
                **{"e_" + n: s for n, s in gated(z["expert"],
                                                 z["held"]).items()},
                **{"s_" + n: s for n, s in gated(
                    z["expert"] * z["shared"]).items()}},
    }
    one = {"embed": normal(z["rows"], h), "final_norm": ones(h),
           "head_w": normal(h, z["rows"])}
    for i, kind in enumerate(layer_kinds(config)):
        one.update({f"l{i}_{n}": s for n, s in {**attn, **ffn[kind]}.items()})
    if nextn:
        one.update({"nextn_enorm": ones(h), "nextn_hnorm": ones(h),
                    "nextn_proj_w": normal(2 * h, h),
                    "nextn_final_norm": ones(h)})
        one.update({f"nextn_{n}": s
                    for n, s in {**attn, **ffn["moe"]}.items()})
    return {n: (shape, how, False) for n, (shape, how) in one.items()}


# -- the layers ----------------------------------------------------------------

def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def rope(t, positions, theta):
    """``t`` [rows, l, heads, d] rotated at ``positions`` [l] (whole
    numbers), the rotate-half pairing over the d columns given."""
    d = t.shape[-1]
    inv_freq = 1.0 / (float(theta) ** (jnp.arange(0, d, 2, dtype=F32) / d))
    freqs = positions.astype(F32)[:, None] * inv_freq
    emb = jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    rotated = jnp.concatenate([-t[..., d // 2:], t[..., :d // 2]], axis=-1)
    return t * jnp.cos(emb) + rotated * jnp.sin(emb)


def attention(p, u, z, einsum):
    """Keys and values a head from the latent, every time: no cache, no
    absorption."""
    rows, l, _ = u.shape
    heads, nope, rot = z["heads"], z["nope"], z["rope"]
    at = jnp.arange(l, dtype=jnp.int32)
    cq = rms_norm(einsum("blh,hk->blk", u, p["q_a_w"]), p["q_a_norm"],
                  z["eps"])
    q = einsum("blr,rk->blk", cq, p["q_b_w"]).reshape(rows, l, heads,
                                                      nope + rot)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], at, z["theta"])],
                        axis=-1)
    kva = einsum("blh,hk->blk", u, p["kv_a_w"])
    c = rms_norm(kva[..., :z["latent"]], p["kv_a_norm"], z["eps"])
    k_r = rope(kva[:, :, None, z["latent"]:], at, z["theta"])
    kvb = einsum("blc,ck->blk", c, p["kv_b_w"]).reshape(
        rows, l, heads, nope + z["v_dim"])
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
        k_r, (rows, l, heads, rot))], axis=-1)
    v = kvb[..., nope:]
    block = QUERY_BLOCK if l % QUERY_BLOCK == 0 else l

    def attend(lo):
        q_rows = jax.lax.dynamic_slice_in_dim(q, lo, block, axis=1)
        s = einsum("bqnd,bknd->bnqk", q_rows, k) / math.sqrt(nope + rot)
        seen = at[None, :] <= (lo + jnp.arange(block, dtype=jnp.int32))[:, None]
        s = jnp.where(seen, s, -jnp.inf)
        return einsum("bnqk,bknd->bqnd", jax.nn.softmax(s, axis=-1), v)

    # int32 whatever jax_enable_x64 says: the TPU emulates 64-bit integers
    o = jax.lax.map(attend, jnp.arange(0, l, block, dtype=jnp.int32))
    o = jnp.moveaxis(o, 0, 1).reshape(rows, l, heads * z["v_dim"])
    return einsum("blk,kh->blh", o, p["o_w"])


def gated_mlp(x, gate_w, up_w, down_w, einsum):
    a = jax.nn.silu(einsum("blh,hk->blk", x, gate_w)) \
        * einsum("blh,hk->blk", x, up_w)
    return einsum("blk,kh->blh", a, down_w)


def route(x, router_w, z, einsum):
    """(chosen experts [.., k], their weights [.., k])."""
    s = jax.nn.sigmoid(einsum("blh,he->ble", x, router_w))
    picked, chosen = jax.lax.top_k(s, z["top_k"])
    return chosen, z["scale"] * picked / jnp.sum(picked, -1, keepdims=True)


def routed_part(x, chosen, weights, experts, first, einsum):
    """What the experts ``first``, ``first`` + 1, ... give: a dense masked
    sum, every expert over every token, an expert at a time."""
    count = experts["gate_w"].shape[0]

    def one(e):
        mine = chosen == (first + e).astype(chosen.dtype)
        w = jnp.sum(jnp.where(mine, weights, 0.0), axis=-1)      # [b, l]
        return w[..., None] * gated_mlp(
            x, experts["gate_w"][e], experts["up_w"][e],
            experts["down_w"][e], einsum)

    return jnp.sum(jax.lax.map(one, jnp.arange(count, dtype=jnp.int32)),
                   axis=0)


def moe(x, p, z, einsum):
    chosen, weights = route(x, p["router_w"], z, einsum)
    held = {n: p["e_" + n] for n in ("gate_w", "up_w", "down_w")}
    return (routed_part(x, chosen, weights, held, 0, einsum)
            + gated_mlp(x, p["s_gate_w"], p["s_up_w"], p["s_down_w"], einsum))


def uncut_moe(x, router_w, experts, shared, z, einsum):
    """The whole layer, every routed expert present (``experts`` holds all
    of them): what the shares have to add up to."""
    chosen, weights = route(x, router_w, z, einsum)
    return (routed_part(x, chosen, weights, experts, 0, einsum)
            + gated_mlp(x, shared["gate_w"], shared["up_w"],
                        shared["down_w"], einsum))


def block(p, x, kind, config, einsum):
    """One layer on [rows, l, h] float32; ``p`` its leaves by their short
    names (``layer_leaves(kind)``)."""
    z = sizes(config)
    a = attention(p, rms_norm(x, p["attn_norm"], z["eps"]), z, einsum)
    x = x + rms_norm(a, p["post_attn_norm"], z["eps"])
    g = rms_norm(x, p["pre_mlp_norm"], z["eps"])
    m = (moe(g, p, z, einsum) if kind == "moe" else
         gated_mlp(g, p["gate_w"], p["up_w"], p["down_w"], einsum))
    return x + rms_norm(m, p["post_mlp_norm"], z["eps"])


def head(final_norm, head_w, x, config, einsum):
    return einsum("blh,hv->blv",
                  rms_norm(x, final_norm, config["rms_norm_eps"]), head_w)


def _of(params: dict, prefix: str, kind: str) -> dict:
    return {n: params[prefix + n] for n in layer_leaves(kind)}


def hidden_states(params, ids, config, einsum):
    """The trunk: embedding through the last block, before the final norm."""
    x = params["embed"][ids]
    for i, kind in enumerate(layer_kinds(config)):
        x = block(_of(params, f"l{i}_", kind), x, kind, config, einsum)
    return x


def logits(params: dict, ids, config: dict, einsum):
    """``[rows, l, vocab_size]`` float32 logits of ``ids`` [rows, l], all
    leaves in ``params`` (the tests' entry, at a size that fits whole)."""
    return head(params["final_norm"], params["head_w"],
                hidden_states(params, ids, config, einsum), config, einsum)


def nextn_logits(params: dict, ids, config: dict, einsum):
    """The next-token module's logits ``[rows, l - 1, vocab_size]``:
    position i, from the trunk's x_i and the embedding of ``ids[:, i + 1]``,
    scores the token at i + 2. ``params`` from ``param_specs(config,
    nextn=True)``."""
    eps = config["rms_norm_eps"]
    x = hidden_states(params, ids, config, einsum)[:, :-1]
    e = params["embed"][ids[:, 1:]]
    both = jnp.concatenate([rms_norm(e, params["nextn_enorm"], eps),
                            rms_norm(x, params["nextn_hnorm"], eps)], axis=-1)
    y = block(_of(params, "nextn_", "moe"),
              einsum("blk,kh->blh", both, params["nextn_proj_w"]), "moe",
              config, einsum)
    return head(params["nextn_final_norm"], params["head_w"], y, config,
                einsum)


# -- the serve cell's entry ----------------------------------------------------

def margins(config: dict, seed: int, ids, served, precisions=("float32",),
            rows_per_block: int = 1):
    """``ids``, ``served``: int32 ``[rows, length]``; ``served[r, p]`` is
    the token that followed position ``p`` of row ``r`` (any id where none
    did: the caller masks). Returns one ``[rows, length]`` numpy array of
    gaps a precision: for the first (the reference itself), how far its
    logit of the served token lies below its best at every position, in
    standard deviations of that position's logits; for each further one (a
    control put in the program's place), the same for the token that
    precision's own forward puts first."""
    specs = param_specs(config)
    key = common.seed_key(seed)
    leaf = lambda name: common.init_leaf(specs, name, key)  # noqa: E731
    einsums = [common.make_einsum(p) for p in precisions]
    n = ids.shape[0]
    blocks = [slice(lo, lo + rows_per_block)
              for lo in range(0, n, rows_per_block)]

    table = leaf("embed")
    lookup = jax.jit(lambda table, ids: table[ids])
    # one residual stream a precision (a layer's call donates its input)
    streams = [[lookup(table, jnp.asarray(ids[b])) for b in blocks]
               for _ in precisions]
    del table
    layer_fns = {kind: [jax.jit(functools.partial(
        block, kind=kind, config=config, einsum=e), donate_argnums=(1,))
        for e in einsums] for kind in set(layer_kinds(config))}
    for i, kind in enumerate(layer_kinds(config)):
        p = {name: leaf(f"l{i}_{name}") for name in layer_leaves(kind)}
        for fn, stream in zip(layer_fns[kind], streams):
            for j, x in enumerate(stream):
                stream[j] = fn(p, x)
        del p
    final_norm, head_w = leaf("final_norm"), leaf("head_w")

    @jax.jit
    def gaps(final_norm, head_w, xs, served_block):
        ref = head(final_norm, head_w, xs[0], config, einsums[0])
        best, std = jnp.max(ref, axis=-1), jnp.std(ref, axis=-1)

        def below(tokens):
            at = jnp.take_along_axis(ref, tokens[..., None], axis=-1)[..., 0]
            return (best - at) / std

        out = [below(served_block)]
        for x, e in zip(xs[1:], einsums[1:]):
            low = head(final_norm, head_w, x, config, e)
            out.append(below(jnp.argmax(low, axis=-1)))
        return tuple(out)

    outs = [[] for _ in precisions]
    for j, b in enumerate(blocks):
        got = gaps(final_norm, head_w, tuple(s[j] for s in streams),
                   jnp.asarray(served[b]))
        for o, g in zip(outs, got):
            o.append(np.asarray(g))
    return [np.concatenate(o) for o in outs]
