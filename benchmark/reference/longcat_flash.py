"""LongCat-Flash-Chat (``model_type`` ``longcat_flash``) as its
``config.json`` describes it, in plain jax.numpy: ``num_layers`` double
layers, each two latent attentions and two dense gated SiLU feed-forwards,
with one expert layer connected by a shortcut. The serve cell's plain
reference: one full forward over whole rows, no cache, no absorption, no
sorting of pairs, no program.

Every norm is an RMSNorm (eps ``rms_norm_eps``) with its own gain::

    a1 = x  + attn_0(RMSNorm_in0(x))
    g1 = RMSNorm_post0(a1)
    m  = moe(g1)                                   the shortcut branch
    b1 = a1 + mlp_0(g1)
    a2 = b1 + attn_1(RMSNorm_in1(b1))
    y  = a2 + mlp_1(RMSNorm_post1(a2)) + m

    attn(u): cq = RMSNorm(u W_qa)                  (q_lora_rank)
             q  = cq W_qb * (h / q_lora_rank)^0.5  a head of nope + rope
             [c | k_r] = u W_kva
             [k_n | v] = (RMSNorm(c) * (h / kv_lora_rank)^0.5) W_kvb
             q's rope columns a head and k_r rotated at the position
             (theta ``rope_theta``, no scaling, the rotate-half pairing)
             softmax_causal(q . [k_n | k_r] / sqrt(nope + rope)) v, W_o
             (each scale where ``mla_scale_q_lora`` / ``mla_scale_kv_lora``)
    mlp(g):  W_down(silu(W_gate g) * W_up g)       ``ffn_hidden_size`` wide
    moe(g):  p = softmax(g W_r) in float32 over the routed and the
             ``zero_expert_num`` identity experts (routed first); the
             ``moe_topk`` largest of p chosen; w = routed_scaling_factor
             * p (no renormalisation); sum over chosen routed e of w_e
             E_e(g) (``expert_ffn_hidden_size`` wide) + sum over chosen
             identity e of w_e g
    logits = RMSNorm_f(x) W_head

Departures from the published modelling code (``modeling_longcat_flash.
py``), each because the timed program does the same (the configuration
file's ``deployment`` and ``assumed``): this chip's share is
``n_routed_experts`` routed experts (the first; the router still scores all
``deployment.n_routed_experts_published`` + ``zero_expert_num`` columns,
and what the absent routed experts would add is left out), the
``num_layers`` double layers held and the slice of ``vocab_size`` rows;
``e_score_correction_bias`` is zero (the published value is trained, moved
by a controller outside the gradient); the rotary columns pair by halves.
The published router casts its input and weight to float32 before the
product; here that product is the harness's ``einsum`` in the precision
asked, as every other (float32 at ``highest`` for the reference itself).
The held experts are computed densely, every one over every position, and
masked by the routing (``pangu_ultra_moe.routed_part``), not dispatched.

Memory: ``margins`` goes a double layer at a time (its float32 leaves are
5 GB at the served size), made from the seed when it is reached and
dropped after it, the rows ``rows_per_block`` at a time, the score square
``QUERY_BLOCK`` query rows at a time.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import common
from .pangu_ultra_moe import gated_mlp, head, rms_norm, rope, routed_part

F32 = jnp.float32
QUERY_BLOCK = 512    # query rows of the score square at a time


def sizes(config: dict) -> dict:
    h = config["hidden_size"]
    return {
        "h": h, "rows": config["vocab_size"], "layers": config["num_layers"],
        "heads": config["num_attention_heads"],
        "q_rank": config["q_lora_rank"], "latent": config["kv_lora_rank"],
        "nope": config["qk_nope_head_dim"], "rope": config["qk_rope_head_dim"],
        "v_dim": config["v_head_dim"], "ffn": config["ffn_hidden_size"],
        "expert": config["expert_ffn_hidden_size"],
        "held": config["n_routed_experts"],
        "routed": config["deployment"]["n_routed_experts_published"],
        "zero": config["zero_expert_num"], "top_k": config["moe_topk"],
        "scale": config["routed_scaling_factor"],
        "eps": config["rms_norm_eps"], "theta": config["rope_theta"],
        "q_scale": ((h / config["q_lora_rank"]) ** 0.5
                    if config["mla_scale_q_lora"] else 1.0),
        "kv_scale": ((h / config["kv_lora_rank"]) ** 0.5
                     if config["mla_scale_kv_lora"] else 1.0),
    }


ATTN_LEAVES = ("attn_norm", "q_a_w", "q_a_norm", "q_b_w", "kv_a_w",
               "kv_a_norm", "kv_b_w", "o_w", "post_attn_norm")
FFN_LEAVES = ("gate_w", "up_w", "down_w")
MOE_LEAVES = ("router_w", "e_gate_w", "e_up_w", "e_down_w")
# a double layer's leaves: attention j's and feed-forward j's under the
# prefixes ``attn{j}_`` and ``ffn{j}_``, then the expert layer's
LAYER_LEAVES = tuple(f"{kind}{j}_{n}" for j in (0, 1)
                     for kind, names in (("attn", ATTN_LEAVES),
                                         ("ffn", FFN_LEAVES))
                     for n in names) + MOE_LEAVES


def param_specs(config: dict) -> dict:
    """Every leaf by its own name (``l2_attn1_q_b_w``, ``l0_router_w``),
    none a stack of layers, so that a layer's leaves can be made alone."""
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
        "o_w": normal(heads * z["v_dim"], h), "post_attn_norm": ones(h),
    }
    ffn = {"gate_w": normal(h, z["ffn"]), "up_w": normal(h, z["ffn"]),
           "down_w": normal(z["ffn"], h)}
    layer = {f"attn{j}_{n}": s for j in (0, 1) for n, s in attn.items()}
    layer.update({f"ffn{j}_{n}": s for j in (0, 1) for n, s in ffn.items()})
    layer.update({
        "router_w": normal(h, z["routed"] + z["zero"]),
        "e_gate_w": normal(z["held"], h, z["expert"]),
        "e_up_w": normal(z["held"], h, z["expert"]),
        "e_down_w": normal(z["held"], z["expert"], h)})
    assert set(layer) == set(LAYER_LEAVES)
    one = {"embed": normal(z["rows"], h), "final_norm": ones(h),
           "head_w": normal(h, z["rows"])}
    for i in range(z["layers"]):
        one.update({f"l{i}_{n}": layer[n] for n in LAYER_LEAVES})
    return {n: (shape, how, False) for n, (shape, how) in one.items()}


# -- the layers ----------------------------------------------------------------

def attention(p, u, z, einsum):
    """Keys and values a head from the (scaled) latent, every time: no
    cache, no absorption. ``p`` one attention's leaves by ``ATTN_LEAVES``."""
    rows, l, _ = u.shape
    heads, nope, rot = z["heads"], z["nope"], z["rope"]
    at = jnp.arange(l, dtype=jnp.int32)
    cq = rms_norm(einsum("blh,hk->blk", u, p["q_a_w"]), p["q_a_norm"],
                  z["eps"])
    q = (einsum("blr,rk->blk", cq, p["q_b_w"]) * z["q_scale"]).reshape(
        rows, l, heads, nope + rot)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], at, z["theta"])],
                        axis=-1)
    kva = einsum("blh,hk->blk", u, p["kv_a_w"])
    c = rms_norm(kva[..., :z["latent"]], p["kv_a_norm"], z["eps"]) \
        * z["kv_scale"]
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


def route(x, router_w, z, einsum):
    """(chosen experts [.., k], their weights [.., k]): softmax over every
    column, routed and identity, no renormalisation."""
    p = jax.nn.softmax(einsum("blh,he->ble", x, router_w).astype(F32),
                       axis=-1)
    picked, chosen = jax.lax.top_k(p, z["top_k"])
    return chosen, z["scale"] * picked


def identity_part(x, chosen, weights, z):
    """What the identity experts give: their weights' sum times x."""
    w = jnp.sum(jnp.where(chosen >= z["routed"], weights, 0.0), axis=-1)
    return w[..., None] * x


def moe(x, p, z, einsum, experts=None):
    """The held experts' part (``experts``: a stack of every routed expert
    instead, for the uncut layer) and the identity experts'."""
    chosen, weights = route(x, p["router_w"], z, einsum)
    experts = experts or {n: p["e_" + n] for n in FFN_LEAVES}
    return (routed_part(x, chosen, weights, experts, 0, einsum)
            + identity_part(x, chosen, weights, z))


def ffn(p, j, x, einsum):
    return gated_mlp(x, p[f"ffn{j}_gate_w"], p[f"ffn{j}_up_w"],
                     p[f"ffn{j}_down_w"], einsum)


def sub(p: dict, prefix: str) -> dict:
    return {n[len(prefix):]: v for n, v in p.items() if n.startswith(prefix)}


def block(p, x, config, einsum):
    """One double layer on [rows, l, h] float32; ``p`` its leaves by their
    short names (``LAYER_LEAVES``)."""
    z = sizes(config)
    eps = z["eps"]
    a0, a1 = sub(p, "attn0_"), sub(p, "attn1_")
    x = x + attention(a0, rms_norm(x, a0["attn_norm"], eps), z, einsum)
    g = rms_norm(x, a0["post_attn_norm"], eps)
    m = moe(g, p, z, einsum)
    x = x + ffn(p, 0, g, einsum)
    x = x + attention(a1, rms_norm(x, a1["attn_norm"], eps), z, einsum)
    return x + ffn(p, 1, rms_norm(x, a1["post_attn_norm"], eps), einsum) + m


def _of(params: dict, i: int) -> dict:
    return {n: params[f"l{i}_{n}"] for n in LAYER_LEAVES}


def logits(params: dict, ids, config: dict, einsum):
    """``[rows, l, vocab_size]`` float32 logits of ``ids`` [rows, l], all
    leaves in ``params`` (the tests' entry, at a size that fits whole)."""
    x = params["embed"][ids]
    for i in range(config["num_layers"]):
        x = block(_of(params, i), x, config, einsum)
    return head(params["final_norm"], params["head_w"], x, config, einsum)


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
    layer_fns = [jax.jit(functools.partial(block, config=config, einsum=e),
                         donate_argnums=(1,)) for e in einsums]
    for i in range(config["num_layers"]):
        p = {name: leaf(f"l{i}_{name}") for name in LAYER_LEAVES}
        for fn, stream in zip(layer_fns, streams):
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
