"""Falcon-H1 (``model_type`` ``falcon_h1``, TII 2025) as its ``config.json``
describes it, in plain jax.numpy: every block runs a Mamba-2 mixer and
grouped-query rotary attention side by side on one normed input and adds
both to the residual, then a gated MLP; fixed multipliers stand where the
published modelling code puts them. The serve cells' plain reference: one
full forward over whole rows, no cache, no chunks, no program.

With h the hidden size and the multipliers of ``config`` by name::

    x  = E[ids] * embedding_multiplier
    u  = RMSNorm_in(x)
    p  = ((u * ssm_in_multiplier) @ W_in) * mup        [z | xBC | dt]
         mup = ssm_multipliers[0..4] over [z | x | B | C | dt]
    xBC = silu(conv4(xBC) + b_conv)                    depthwise, causal
    x_s [heads, d_head], B [groups, d_state], C [groups, d_state] = xBC
         (head i reads group i // (heads / groups))
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_s,t (x) B_t     **token by token**
    y_t = S_t C_t + D x_s,t
    y  = GroupRMSNorm(y * silu(z)) * w_norm            gate first
    m  = (y @ W_out) * ssm_out_multiplier
    q = (u * attention_in_multiplier) @ W_q;  v likewise
    k = ((u * attention_in_multiplier) @ W_k) * key_multiplier
    q, k = rope(q, k; theta, rotate-half over the whole head)
    a  = (softmax_causal(q k^T / sqrt(d)) v) @ W_o * attention_out_multiplier
    x  = x + m + a
    g  = RMSNorm_ff(x)
    x  = x + (silu((g @ W_gate) * mlp_multipliers[0]) * (g @ W_up)) @ W_down
             * mlp_multipliers[1]
    logits = (RMSNorm_f(x) @ W_head) * lm_head_multiplier

``mamba_use_mlp``, ``attn_layer_indices``, ``num_logits_to_keep`` and
``mamba_expand`` (overridden by ``mamba_d_ssm``) enter no equation.

Departures, each because the timed program does the same (the
configuration file's ``deployment`` and ``assumed``): the layers are the
first ``num_hidden_layers`` published ones and the vocabulary is the slice
of ``vocab_size`` rows held here (ids, logits and ``argmax`` over the
slice); the initial values the published config lacks are ``assumed``.

Memory: the float32 weights of the cell's nine layers are 15.5 GB and fit
beside nothing, so ``margins`` goes **layer by layer**: a layer's leaves
are made from the seed when the layer is reached and dropped after it, the
rows pass through it ``rows_per_block`` at a time, and only the residual
stream of all rows lives from layer to layer. ``einsum`` is the harness's,
in the precision asked for; norms, softmax, gates and the recurrence are
elementwise float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import common

F32 = jnp.float32


def sizes(config: dict) -> dict:
    groups, state = config["mamba_n_groups"], config["mamba_d_state"]
    return {
        "h": config["hidden_size"], "rows": config["vocab_size"],
        "layers": config["num_hidden_layers"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"], "d": config["head_dim"],
        "inner": config["intermediate_size"],
        "d_ssm": config["mamba_d_ssm"], "ssm_heads": config["mamba_n_heads"],
        "d_head": config["mamba_d_head"], "groups": groups, "state": state,
        "conv": config["mamba_d_conv"],
        "conv_dim": config["mamba_d_ssm"] + 2 * groups * state,
        "proj": 2 * config["mamba_d_ssm"] + 2 * groups * state
        + config["mamba_n_heads"],
        "eps": config["rms_norm_eps"], "theta": config["rope_theta"],
    }


LAYER_LEAVES = ("in_norm", "ssm_in_w", "ssm_conv_w", "ssm_conv_b",
                "ssm_dt_bias", "ssm_a_log", "ssm_d", "ssm_norm", "ssm_out_w",
                "attn_q_w", "attn_k_w", "attn_v_w", "attn_o_w", "ffn_norm",
                "mlp_gate_w", "mlp_up_w", "mlp_down_w")


def param_specs(config: dict) -> dict:
    """Every leaf by its own name (``l3_attn_q_w``), none a stack of
    layers, so that a layer's leaves can be made alone."""
    z, a = sizes(config), config["assumed"]
    std = a["initializer_range"]
    h = z["h"]
    normal = lambda *shape: (shape, ("normal", std))  # noqa: E731
    ones = lambda *shape: (shape, ("ones",))  # noqa: E731
    zeros = lambda *shape: (shape, ("zeros",))  # noqa: E731
    layer = {
        "in_norm": ones(h), "ssm_in_w": normal(h, z["proj"]),
        "ssm_conv_w": ((z["conv_dim"], z["conv"]),
                       ("normal", a["conv_std"])),
        "ssm_conv_b": zeros(z["conv_dim"]),
        "ssm_dt_bias": ((z["ssm_heads"],), ("normal", a["dt_bias_std"])),
        "ssm_a_log": zeros(z["ssm_heads"]), "ssm_d": ones(z["ssm_heads"]),
        "ssm_norm": ones(z["d_ssm"]), "ssm_out_w": normal(z["d_ssm"], h),
        "attn_q_w": normal(h, z["heads"] * z["d"]),
        "attn_k_w": normal(h, z["kv_heads"] * z["d"]),
        "attn_v_w": normal(h, z["kv_heads"] * z["d"]),
        "attn_o_w": normal(z["heads"] * z["d"], h),
        "ffn_norm": ones(h), "mlp_gate_w": normal(h, z["inner"]),
        "mlp_up_w": normal(h, z["inner"]),
        "mlp_down_w": normal(z["inner"], h),
    }
    assert tuple(layer) == LAYER_LEAVES
    one = {"embed": normal(z["rows"], h), "final_norm": ones(h),
           "head_w": normal(h, z["rows"])}
    for i in range(z["layers"]):
        one.update({f"l{i}_{n}": s for n, s in layer.items()})
    return {n: (shape, how, False) for n, (shape, how) in one.items()}


# -- the layers ----------------------------------------------------------------

def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def causal_conv(x, w, b):
    """Depthwise along the sequence: ``x`` [rows, l, c], ``w`` [c, k];
    y_t = sum_j w[:, j] x_{t-(k-1)+j} + b, zeros before the start."""
    k = w.shape[-1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j:j + x.shape[1]] * w[:, j] for j in range(k)) + b


def recurrence(x, dt, A, B, C, D):
    """The state-space recurrence, one token at a time. ``x`` [rows, l,
    heads, p], ``dt`` [rows, l, heads], ``A``, ``D`` [heads], ``B``, ``C``
    [rows, l, heads, n] (each head's group's, repeated). S starts at 0."""
    rows, _, heads, p = x.shape

    def step(S, t):
        x_t, dt_t, B_t, C_t = t
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :])
        return S, jnp.sum(S * C_t[:, :, None, :], axis=-1) + D[:, None] * x_t

    S0 = jnp.zeros((rows, heads, p, B.shape[-1]), F32)
    _, y = jax.lax.scan(step, S0, tuple(jnp.moveaxis(t, 1, 0)
                                        for t in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1)


def mamba(p, u, config, z, einsum):
    rows, l, _ = u.shape
    mup = jnp.concatenate([jnp.full((w,), m, F32) for w, m in zip(
        (z["d_ssm"], z["d_ssm"], z["groups"] * z["state"],
         z["groups"] * z["state"], z["ssm_heads"]),
        config["ssm_multipliers"])])
    proj = einsum("blh,hk->blk", u * config["ssm_in_multiplier"],
                  p["ssm_in_w"]) * mup
    gate, xbc, dt = jnp.split(proj, [z["d_ssm"], z["d_ssm"] + z["conv_dim"]],
                              axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, p["ssm_conv_w"], p["ssm_conv_b"]))
    gn = z["groups"] * z["state"]
    x, B, C = jnp.split(xbc, [z["d_ssm"], z["d_ssm"] + gn], axis=-1)
    per_group = z["ssm_heads"] // z["groups"]
    B, C = (jnp.repeat(t.reshape(rows, l, z["groups"], z["state"]),
                       per_group, axis=2) for t in (B, C))
    x = x.reshape(rows, l, z["ssm_heads"], z["d_head"])
    dt = jax.nn.softplus(dt + p["ssm_dt_bias"])
    y = recurrence(x, dt, -jnp.exp(p["ssm_a_log"]), B, C, p["ssm_d"])
    y = y.reshape(rows, l, z["d_ssm"]) * jax.nn.silu(gate)
    grouped = y.reshape(rows, l, z["groups"], -1)
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(jnp.square(grouped), -1, keepdims=True) + z["eps"])
    y = grouped.reshape(rows, l, z["d_ssm"]) * p["ssm_norm"]
    return einsum("blk,kh->blh", y, p["ssm_out_w"]) \
        * config["ssm_out_multiplier"]


def rope(t, theta):
    """``t`` [rows, l, heads, d] by the positions 0 .. l-1, the rotate-half
    pairing over the whole head width."""
    l, d = t.shape[1], t.shape[-1]
    inv_freq = 1.0 / (float(theta) ** (jnp.arange(0, d, 2, dtype=F32) / d))
    freqs = jnp.arange(l, dtype=F32)[:, None] * inv_freq
    emb = jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    rotated = jnp.concatenate([-t[..., d // 2:], t[..., :d // 2]], axis=-1)
    return t * jnp.cos(emb) + rotated * jnp.sin(emb)


def attention(p, u, config, z, einsum):
    rows, l, _ = u.shape
    u = u * config["attention_in_multiplier"]
    q = einsum("blh,hk->blk", u, p["attn_q_w"]).reshape(
        rows, l, z["heads"], z["d"])
    k = (einsum("blh,hk->blk", u, p["attn_k_w"])
         * config["key_multiplier"]).reshape(rows, l, z["kv_heads"], z["d"])
    v = einsum("blh,hk->blk", u, p["attn_v_w"]).reshape(
        rows, l, z["kv_heads"], z["d"])
    q, k = rope(q, z["theta"]), rope(k, z["theta"])
    rep = z["heads"] // z["kv_heads"]  # query head i reads key head i // rep
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    causal = jnp.where(jnp.tril(jnp.ones((l, l), bool)), 0.0, -jnp.inf)
    o = common.attention(einsum, q, k, v, causal).reshape(rows, l, -1)
    return einsum("blk,kh->blh", o, p["attn_o_w"]) \
        * config["attention_out_multiplier"]


def mlp(p, g, config, einsum):
    gate = einsum("blh,hk->blk", g, p["mlp_gate_w"]) \
        * config["mlp_multipliers"][0]
    up = einsum("blh,hk->blk", g, p["mlp_up_w"])
    return einsum("blk,kh->blh", jax.nn.silu(gate) * up, p["mlp_down_w"]) \
        * config["mlp_multipliers"][1]


def block(p, x, config, einsum):
    """One layer on [rows, l, h] float32; ``p`` its leaves by their short
    names (``LAYER_LEAVES``)."""
    z = sizes(config)
    u = rms_norm(x, p["in_norm"], z["eps"])
    x = x + mamba(p, u, config, z, einsum) + attention(p, u, config, z, einsum)
    return x + mlp(p, rms_norm(x, p["ffn_norm"], z["eps"]), config, einsum)


def embed(table, ids, config):
    return table[ids] * config["embedding_multiplier"]


def head(final_norm, head_w, x, config, einsum):
    x = rms_norm(x, final_norm, config["rms_norm_eps"])
    return einsum("blh,hv->blv", x, head_w) * config["lm_head_multiplier"]


def logits(params: dict, ids, config: dict, einsum):
    """``[rows, l, vocab_size]`` float32 logits of ``ids`` [rows, l], all
    leaves in ``params`` (the tests' entry, at a size that fits whole)."""
    x = embed(params["embed"], ids, config)
    for i in range(config["num_hidden_layers"]):
        x = block({n: params[f"l{i}_{n}"] for n in LAYER_LEAVES}, x, config,
                  einsum)
    return head(params["final_norm"], params["head_w"], x, config, einsum)


# -- the serve cells' entry ------------------------------------------------------

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
    lookup = jax.jit(functools.partial(embed, config=config))
    # one residual stream a precision (a layer's call donates its input)
    streams = [[lookup(table, jnp.asarray(ids[b])) for b in blocks]
               for _ in precisions]
    del table
    layer_fns = [jax.jit(functools.partial(block, config=config, einsum=e),
                         donate_argnums=(1,)) for e in einsums]
    for i in range(config["num_hidden_layers"]):
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
