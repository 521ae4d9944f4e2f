"""openPangu-Ultra-MoE (``model_type`` ``pangu_ultra_moe``): a decoder
with multi-head latent attention in every layer (a compressed query, a
compressed key-value latent, rotary columns kept apart from the ones the
latent carries), a gated SiLU MLP in the leading layers and routed experts
beside a shared expert in the rest, a norm after each sub-layer as well as
before it, and one next-token prediction module.

    a = post_attention_layernorm(attn(input_layernorm(x)));  x = x + a
    m = post_mlp_layernorm(mlp(pre_mlp_layernorm(x)));       x = x + m

    attn(u): cq = RMSNorm(u W_qa);  q = cq W_qb             a head of nope + rope
             [c | k_r] = u W_kva;  c = RMSNorm(c)           512 + 64, k_r one for all heads
             [k_n | v] = c W_kvb                            a head of nope + v
             q's rope columns a head and k_r rotated at the position
             (``nn.functional.rotary_embedding`` on that slice)
             softmax_causal(q . [k_n | k_r] / sqrt(nope + rope)) v, W_o
    mlp(g):  W_down(silu(W_gate g) * W_up g)   in the first_k_dense_replace leading layers
             else sigmoid router over all routed experts, the 8 largest,
             weights over their sum times 2.5 (``incubate.moe.route_top_k``),
             the held experts' part (``held_experts_part``) + the shared expert
    next-token module: [RMSNorm(E[t_{i+1}]) | RMSNorm(x_i)] W_p, one such
             block with an expert layer, a final norm of its own, the
             trunk's embedding and head: position i guesses t_{i+2}

A chip of an expert-parallel deployment holds a run of the routed experts
(``experts_held``), the first ``layers_held`` layers of which the first
``dense_layers_held`` are dense (further pipeline stages hold the rest), a
slice of the vocabulary (``vocab_rows_held``) and the next-token module or
not (``nextn_held``: a deployment that does not speculate loads none). The
defaults are the published openPangu-Ultra-MoE-718B whole.

Two forms of one model, as Falcon-H1's. ``PanguUltraMoEForCausalLM`` is the
``nn.Layer``: it holds the parameters and its forward gives a whole
sequence's logits. ``pangu_decode_fns`` is the cached forward that
``inference.serving.TokenServingEngine`` serves with, over a **latent**
cache: a token and layer one row ``[c | rotated k_r]`` of 576 numbers,
stored 640 wide (``PanguUltraMoEConfig.cache_width``;
``inference/serving/kv_cache.py``, layout ``latent``), read by
``ops.attention.mla_paged_attention`` in its absorbed form (a decode or
verify step) or its expanded one (a prefill chunk). The module drafts for
the scheduler from the trunk's hidden state (``decode_spec()['draft']``),
its one layer's latent rows in the same pool and under the same blocks.
Training this model is not built: no cut of it fits a chip's train state.

Scopes of the compiled step: ``embed``; ``self_attn`` around a layer's
attention and, inside it, ``mla`` (the absorbed or expanded walk over the
latent pages); ``mlp`` around the feed-forward and, inside it, ``moe``
(router, top-k, sort, grouped products, combine, shared expert);
``head_loss``.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import nn
from paddle_tpu.core.tensor import apply_op
from paddle_tpu.incubate.moe import (DroplessMoE, held_experts_part,
                                     held_load, route_top_k)
from paddle_tpu.nn import initializer as I
from paddle_tpu.nn.functional.common import rotary_embedding
from paddle_tpu.nn.functional.norm import group_rms
from paddle_tpu.ops.attention import dot_product_attention

__all__ = ["PanguUltraMoEConfig", "PanguUltraMoEModel",
           "PanguUltraMoEForCausalLM", "pangu_decode_fns",
           "pangu_ultra_moe_tiny"]

F32 = jnp.float32
# rows of the served step's counts (``cache['moe_counts']``): a decode step
# (one query a row) and everything else (a prefill chunk, a verify step)
KINDS = ("decode", "chunk")
COUNTS = ("layer_steps", "experts_hit", "pairs_here")


@dataclass
class PanguUltraMoEConfig:
    # the published keys
    vocab_size: int = 153600
    hidden_size: int = 7680
    num_hidden_layers: int = 61
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 25600000.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    num_nextn_predict_layers: int = 1
    # what the published config lacks
    initializer_range: float = 0.02
    # this chip's share: None holds everything
    experts_held: range = None
    vocab_rows_held: int = None
    layers_held: int = None
    dense_layers_held: int = None
    nextn_held: int = None

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = range(self.n_routed_experts)
        if self.vocab_rows_held is None:
            self.vocab_rows_held = self.vocab_size
        if self.layers_held is None:
            self.layers_held = self.num_hidden_layers
        if self.dense_layers_held is None:
            self.dense_layers_held = min(self.first_k_dense_replace,
                                         self.layers_held)
        if self.nextn_held is None:
            self.nextn_held = self.num_nextn_predict_layers
        if self.nextn_held not in (0, 1):
            raise ValueError("one next-token module is built, or none")

    @property
    def layer_kinds(self) -> list:
        """'dense' or 'moe' for every layer held."""
        return ["dense" if i < self.dense_layers_held else "moe"
                for i in range(self.layers_held)]

    @property
    def latent_width(self) -> int:
        """A cached token's row a layer: the latent and the rotated key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_width(self) -> int:
        """The row as the pool stores it: padded with zeros to whole
        128-lane groups (576 -> 640). A minor axis of 576 is 4.5 groups,
        and the TPU runtime then lays ``[blocks, block, 576]`` out with
        the *blocks* along the lanes to avoid the padding: every compiled
        step copied each layer's whole array into the row-major layout its
        scatter and gathers want and back (ten copies of 302 MB a step at
        the served size, by the v5e's compiler; PERF.md section 6, PR 37).
        The price is a ninth more pool and a ninth more bytes a walk."""
        return -(-self.latent_width // 128) * 128


def _linear(n_in, n_out, std):
    return nn.Linear(n_in, n_out, nn.ParamAttr(initializer=I.Normal(0.0, std)),
                     bias_attr=False)


# -- the Layer form -------------------------------------------------------------

class PanguMLAttention(nn.Layer):
    """Latent attention with a compressed query and decoupled rotary
    columns; keys and values made a head from the latent (the whole-row
    form: nothing is cached)."""

    def __init__(self, config: PanguUltraMoEConfig):
        super().__init__()
        c, h, std = config, config.hidden_size, config.initializer_range
        self.config = c
        heads = c.num_attention_heads
        self.q_a_proj = _linear(h, c.q_lora_rank, std)
        self.q_a_norm = nn.RMSNorm(c.q_lora_rank, c.rms_norm_eps)
        self.q_b_proj = _linear(c.q_lora_rank, heads * (
            c.qk_nope_head_dim + c.qk_rope_head_dim), std)
        self.kv_a_proj = _linear(h, c.latent_width, std)
        self.kv_a_norm = nn.RMSNorm(c.kv_lora_rank, c.rms_norm_eps)
        self.kv_b_proj = _linear(c.kv_lora_rank, heads * (
            c.qk_nope_head_dim + c.v_head_dim), std)
        self.o_proj = _linear(heads * c.v_head_dim, h, std)

    def forward(self, u):
        c = self.config
        b, l = u.shape[:2]
        heads, nope, rope, latent = (c.num_attention_heads,
                                     c.qk_nope_head_dim, c.qk_rope_head_dim,
                                     c.kv_lora_rank)

        def attend(q, kva, kvb):
            at = jnp.broadcast_to(jnp.arange(l, dtype=jnp.int32), (b, l))
            q = q.reshape(b, l, heads, nope + rope)
            q = jnp.concatenate([q[..., :nope], rotary_embedding(
                q[..., nope:], at, c.rope_theta)], axis=-1)
            k_r = rotary_embedding(kva[:, :, None, latent:], at, c.rope_theta)
            kvb = kvb.reshape(b, l, heads, -1)
            k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
                k_r, (b, l, heads, rope))], axis=-1)
            o = dot_product_attention(q, k, kvb[..., nope:], causal=True,
                                      layout="blhd")
            return o.reshape(b, l, -1)

        kva = self.kv_a_proj(u)
        kvb = self.kv_b_proj(self.kv_a_norm(kva[:, :, :latent]))
        q = self.q_b_proj(self.q_a_norm(self.q_a_proj(u)))
        return self.o_proj(apply_op(attend, q, kva, kvb, op_name="mla"))


class PanguBlock(nn.Layer):
    """Sandwich norms: one before and one after each sub-layer."""

    def __init__(self, config: PanguUltraMoEConfig, kind: str):
        super().__init__()
        c, h, eps = config, config.hidden_size, config.rms_norm_eps
        attr = nn.ParamAttr(initializer=I.Normal(0.0, c.initializer_range))
        self.input_layernorm = nn.RMSNorm(h, eps)
        self.attn = PanguMLAttention(c)
        self.post_attention_layernorm = nn.RMSNorm(h, eps)
        self.pre_mlp_layernorm = nn.RMSNorm(h, eps)
        if kind == "dense":
            self.mlp = nn.SwiGLU(h, c.intermediate_size, attr)
        else:
            self.mlp = DroplessMoE(
                h, c.moe_intermediate_size, c.n_routed_experts,
                experts_held=c.experts_held, top_k=c.num_experts_per_tok,
                scale=c.routed_scaling_factor, renormalize=c.norm_topk_prob,
                shared_experts=c.n_shared_experts, weight_attr=attr)
        self.post_mlp_layernorm = nn.RMSNorm(h, eps)

    def forward(self, x):
        with jax.named_scope("self_attn"):
            x = x + self.post_attention_layernorm(
                self.attn(self.input_layernorm(x)))
        with jax.named_scope("mlp"):
            return x + self.post_mlp_layernorm(
                self.mlp(self.pre_mlp_layernorm(x)))


class PanguUltraMoEModel(nn.Layer):
    def __init__(self, config: PanguUltraMoEConfig):
        super().__init__()
        self.config = config
        self.embed = nn.Embedding(
            config.vocab_rows_held, config.hidden_size,
            weight_attr=nn.ParamAttr(initializer=I.Normal(
                0.0, config.initializer_range)))
        self.layers = nn.LayerList([PanguBlock(config, kind)
                                    for kind in config.layer_kinds])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids):
        """The trunk's output before its final norm (the next-token module
        reads it there)."""
        with jax.named_scope("embed"):
            x = self.embed(input_ids)
        for block in self.layers:
            x = block(x)
        return x


class PanguNextN(nn.Layer):
    """The next-token prediction module: the next token's embedding and
    the trunk's hidden state, each normed, joined and projected, through
    one block with an expert layer and a final norm of its own."""

    def __init__(self, config: PanguUltraMoEConfig):
        super().__init__()
        h, eps = config.hidden_size, config.rms_norm_eps
        self.enorm = nn.RMSNorm(h, eps)
        self.hnorm = nn.RMSNorm(h, eps)
        self.eh_proj = _linear(2 * h, h, config.initializer_range)
        self.block = PanguBlock(config, "moe")
        self.norm = nn.RMSNorm(h, eps)

    def forward(self, next_embedded, hidden):
        import paddle_tpu as paddle

        both = paddle.concat([self.enorm(next_embedded), self.hnorm(hidden)],
                             axis=-1)
        return self.norm(self.block(self.eh_proj(both)))


class PanguUltraMoEForCausalLM(nn.Layer):
    """Untied head over the rows of the vocabulary held here. ``forward``
    gives the logits of whole sequences; ``nextn_logits`` the next-token
    module's, position i of ``l - 1`` scoring the token at i + 2."""

    def __init__(self, config: PanguUltraMoEConfig):
        super().__init__()
        self.config = config
        self.model = PanguUltraMoEModel(config)
        self.lm_head = _linear(config.hidden_size, config.vocab_rows_held,
                               config.initializer_range)
        if config.nextn_held:
            self.nextn = PanguNextN(config)

    def forward(self, input_ids):
        h = self.model(input_ids)
        with jax.named_scope("head_loss"):
            return self.lm_head(self.model.norm(h))

    def nextn_logits(self, input_ids):
        if not self.config.nextn_held:
            raise ValueError("this share holds no next-token module")
        h = self.model(input_ids)[:, :-1]
        y = self.nextn(self.model.embed(input_ids[:, 1:]), h)
        return self.lm_head(y)

    def decode_spec(self, kv_dtype: str = "float32") -> dict:
        """What ``inference.serving.TokenServingEngine`` asks a model. The
        cache is latent: a layer's pages ``[blocks, block, 640]`` (576 and
        the padding to whole lanes), one shared key a token whose first
        512 columns are also the value.
        Where the next-token module is held it takes one more such layer
        of the same pool and is offered as the scheduler's draft. The
        expert layers' counts ride in the cache pytree (``moe_counts``)
        and are read when the engine asks, never a step."""
        c = self.config
        fns = pangu_decode_fns(c, kv_dtype)
        spec = {"forward_chunk": fns["forward_chunk"],
                "num_layers": c.layers_held + c.nextn_held,
                "num_heads": c.num_attention_heads, "num_kv_heads": 1,
                "head_dim": c.cache_width,
                "max_positions": c.max_position_embeddings,
                "kv_layout": "latent", "state": {},
                "counters": {"moe_counts": ((len(KINDS), len(COUNTS)),
                                            "int32")},
                "publish_counters": publish_moe_counts}
        if c.nextn_held:
            spec["draft"] = {"forward_hidden": fns["forward_hidden"],
                             "forward_draft": fns["forward_draft"],
                             "max_k": 1}
        return spec


def publish_moe_counts(counters: dict, telemetry=None) -> dict:
    """The served steps' counts since the pool was made, as counters
    ``moe/<layer_steps|experts_hit|pairs_here>.<decode|chunk>``: expert
    layers run, held experts that received at least one pair, pairs routed
    to held experts. The caller's fetch, made when it asks."""
    from paddle_tpu.profiler.telemetry import get_telemetry

    tel = telemetry or get_telemetry()
    values = np.asarray(counters["moe_counts"])
    out = {}
    for kind, row in zip(KINDS, values):
        for name, v in zip(COUNTS, row):
            key = f"moe/{name}.{kind}"
            # counters only rise: publish what was added since the last look
            tel.counter(key, max(0, int(v) - tel.counter_value(key)))
            out[key] = int(v)
    return out


# -- the cached form ------------------------------------------------------------

def pangu_decode_fns(config: PanguUltraMoEConfig, kv_dtype: str = "float32"):
    """Pure cached forwards for token-level serving, the twins of
    ``falcon_h1_decode_fns``. ``forward_chunk(params, tokens, q_positions,
    cache, block_tables, kv_lens, slots) -> (logits [B, T, rows], cache)``
    advances every row's cache by a T-token chunk; ``forward_hidden`` is
    the same and returns the trunk's output before its final norm as well;
    ``forward_draft(params, hidden, tokens, ...)`` is the next-token
    module's: position i takes the trunk's ``hidden`` of position i and
    ``tokens`` = the token at i + 1, writes its own layer's latent row and
    scores the token at i + 2.

    ``cache['latent'][i]`` is layer i's pages ``[blocks, block, 640]``
    (``[c | rotated k_r | zeros]``, the module's the last);
    ``cache['moe_counts']`` int32 ``[2, 3]`` (``KINDS`` by ``COUNTS``). A
    position is there where ``q_position < kv_len``; the others write to
    the scratch page and are routed to no expert."""
    from paddle_tpu.ops.attention import mla_paged_attention

    c = config
    if kv_dtype == "int8":
        raise ValueError("pangu_decode_fns: latent pages have no int8 form")
    store = jnp.dtype(kv_dtype)
    eps = c.rms_norm_eps
    heads, nope, rope, latent = (c.num_attention_heads, c.qk_nope_head_dim,
                                 c.qk_rope_head_dim, c.kv_lora_rank)
    first, held = c.experts_held.start, len(c.experts_held)
    no_bias = jnp.zeros((c.n_routed_experts,), F32)

    def rms(x, w):
        return group_rms(x, w, eps, 1)

    def attend(p, u, pages, where):
        q_positions, block_tables, kv_lens, page_idx, slot_in_page = where
        B, T, _ = u.shape
        # the barrier keeps the split by heads on the activation: without
        # it the compiler lays W_qb out anew (a head's 192 columns apart)
        # in every step, 75 MB a layer at the served size
        q = jax.lax.optimization_barrier(
            rms(u @ p("attn.q_a_proj.weight"), p("attn.q_a_norm.weight"))
            @ p("attn.q_b_proj.weight")).reshape(B, T, heads, nope + rope)
        kva = u @ p("attn.kv_a_proj.weight")
        row = jnp.concatenate([
            rms(kva[..., :latent], p("attn.kv_a_norm.weight")),
            rotary_embedding(kva[:, :, None, latent:], q_positions,
                    c.rope_theta)[:, :, 0],
            jnp.zeros((B, T, c.cache_width - c.latent_width), kva.dtype)],
            axis=-1)
        pages = pages.at[page_idx, slot_in_page].set(row.astype(store))
        with jax.named_scope("mla"):
            o = mla_paged_attention(
                q[..., :nope], rotary_embedding(q[..., nope:], q_positions,
                                       c.rope_theta),
                p("attn.kv_b_proj.weight"), pages, block_tables, q_positions,
                kv_lens, v_dim=c.v_head_dim)
        return o.reshape(B, T, heads * c.v_head_dim) \
            @ p("attn.o_proj.weight"), pages

    def gated(g, p, at):
        return (jax.nn.silu(g @ p(at + "gate_proj.weight"))
                * (g @ p(at + "up_proj.weight"))) @ p(at + "down_proj.weight")

    def experts(p, g, valid):
        """The held experts' part and the shared expert's, and int32[2]:
        held experts hit, pairs routed here. Positions that are not there
        are routed nowhere."""
        B, T, h = g.shape
        flat = g.reshape(B * T, h)
        with jax.named_scope("moe"):
            scores = jax.nn.sigmoid(jnp.dot(
                flat, p("mlp.gate.weight"), preferred_element_type=F32))
            chosen, weights = route_top_k(
                scores, no_bias, c.num_experts_per_tok,
                c.routed_scaling_factor, c.norm_topk_prob)
            chosen = jnp.where(valid.reshape(-1, 1), chosen, -1)
            y, _ = held_experts_part(flat, chosen, weights, p("mlp.w_gate"),
                                     p("mlp.w_up"), p("mlp.w_down"), first)
            y = y.astype(flat.dtype)
            if c.n_shared_experts:
                y = y + gated(flat, p, "mlp.shared.")
            load = held_load(chosen, first, held)
        return y.reshape(B, T, h), load

    def layer(params, at, kind, x, pages, where, valid):
        p = lambda name: params[at + name]  # noqa: E731
        with jax.named_scope("self_attn"):
            a, pages = attend(p, rms(x, p("input_layernorm.weight")), pages,
                              where)
            x = x + rms(a, p("post_attention_layernorm.weight"))
        with jax.named_scope("mlp"):
            g = rms(x, p("pre_mlp_layernorm.weight"))
            if kind == "dense":
                m, load = gated(g, p, "mlp."), None
            else:
                m, load = experts(p, g, valid)
            x = x + rms(m, p("post_mlp_layernorm.weight"))
        return x, pages, load

    def placed(cache, q_positions, block_tables, kv_lens):
        bs = cache["latent"][0].shape[1]
        valid = q_positions < kv_lens[:, None]
        page_idx = jnp.take_along_axis(
            block_tables, jnp.clip(q_positions // bs, 0,
                                   block_tables.shape[1] - 1), axis=1)
        where = (q_positions, block_tables, kv_lens,
                 jnp.where(valid, page_idx, 0), q_positions % bs)
        return where, valid

    def trunk(params, tokens, q_positions, cache, block_tables, kv_lens):
        cache = dict(cache)
        pages = list(cache["latent"])
        where, valid = placed(cache, q_positions, block_tables, kv_lens)
        with jax.named_scope("embed"):
            x = params["model.embed.weight"][tokens]
        loads = []
        for i, kind in enumerate(c.layer_kinds):
            x, pages[i], load = layer(params, f"model.layers.{i}.", kind, x,
                                      pages[i], where, valid)
            if load is not None:
                loads.append(load)
        cache["latent"] = tuple(pages)
        if loads and "moe_counts" in cache:
            ran = jnp.any(valid).astype(jnp.int32)
            step = jnp.concatenate([ran[None] * len(loads),
                                    sum(loads)]).astype(jnp.int32)
            cache["moe_counts"] = cache["moe_counts"].at[
                0 if tokens.shape[1] == 1 else 1].add(step)
        return x, cache

    def head(params, x, norm="model.norm.weight"):
        with jax.named_scope("head_loss"):
            return rms(x, params[norm]) @ params["lm_head.weight"]

    def forward_hidden(params, tokens, q_positions, cache, block_tables,
                       kv_lens, slots):
        x, cache = trunk(params, tokens, q_positions, cache, block_tables,
                         kv_lens)
        return head(params, x), cache, x

    def forward_chunk(params, tokens, q_positions, cache, block_tables,
                      kv_lens, slots):
        return forward_hidden(params, tokens, q_positions, cache,
                              block_tables, kv_lens, slots)[:2]

    def forward_draft(params, hidden, tokens, q_positions, cache,
                      block_tables, kv_lens, slots):
        cache = dict(cache)
        pages = list(cache["latent"])
        where, valid = placed(cache, q_positions, block_tables, kv_lens)
        e = params["model.embed.weight"][tokens]
        both = jnp.concatenate([rms(e, params["nextn.enorm.weight"]),
                                rms(hidden, params["nextn.hnorm.weight"])],
                               axis=-1)
        x, pages[c.layers_held], _ = layer(
            params, "nextn.block.", "moe",
            both @ params["nextn.eh_proj.weight"], pages[c.layers_held],
            where, valid)
        cache["latent"] = tuple(pages)
        return head(params, x, "nextn.norm.weight"), cache

    return {"forward_chunk": forward_chunk, "forward_hidden": forward_hidden,
            "forward_draft": forward_draft}


def pangu_ultra_moe_tiny(**kw):
    """Every part at a width a CPU test can afford: 4 heads, a compressed
    query, 8 routed experts of which 2 a token, one dense layer of three,
    the next-token module."""
    base = dict(
        vocab_size=256, hidden_size=64, num_hidden_layers=3,
        intermediate_size=128, moe_intermediate_size=32, n_routed_experts=8,
        num_experts_per_tok=2, first_k_dense_replace=1,
        num_attention_heads=4, q_lora_rank=24, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        max_position_embeddings=512)
    base.update(kw)
    return PanguUltraMoEConfig(**base)
