"""LongCat-Flash (``model_type`` ``longcat_flash``): a decoder of *double*
layers, each two latent attentions and two dense gated SiLU feed-forwards,
with one expert layer connected by a shortcut: the experts read the first
half's normed residual and their output joins the residual at the double
layer's end, a block later. Every ``RMS_*`` is its own RMSNorm.

    a1 = x  + MLA_1(RMS_in1(x))
    g1 = RMS_post1(a1)
    m  = MoE(g1)                                   the shortcut branch
    b1 = a1 + FFN_1(g1)
    a2 = b1 + MLA_2(RMS_in2(b1))
    y  = a2 + FFN_2(RMS_post2(a2)) + m             m joins here

    MLA:    openPangu's latent attention (a compressed query, a latent with
            decoupled rotary columns; ``pangu_ultra_moe.cached_mla``) with
            two LoRA scales: q times (h / q_lora_rank)^0.5 after W_qb, the
            normed latent times (h / kv_lora_rank)^0.5 before W_kvb
    MoE(g): p = softmax(W_r g) in float32 over the n_routed_experts routed
            and the zero_expert_num identity experts; the moe_topk largest
            of p + e_score_correction_bias chosen (the bias steers the
            choice only); a chosen expert weighs routed_scaling_factor * p,
            not renormalised;  sum over routed e of w_e SwiGLU_e(g) + sum
            over identity e of w_e g  (``incubate.moe.held_experts_part``,
            ``identity_experts_part``); no shared expert

So the work a token costs varies: a token whose chosen experts are mostly
identity ones costs little more than its weighted add.

A chip of an expert-parallel deployment holds a run of the routed experts
(``experts_held``), the first ``layers_held`` double layers (further
pipeline stages hold the rest) and a slice of the vocabulary
(``vocab_rows_held``); attention, the two dense feed-forwards and the
router whole, and every identity expert, which needs no exchange. The
defaults are the published LongCat-Flash-Chat whole.

Two forms of one model, as openPangu's. ``LongcatFlashForCausalLM`` is the
``nn.Layer``; ``longcat_decode_fns`` is the cached forward
``inference.serving.TokenServingEngine`` serves with, over a latent cache of
two entries a double layer (``kv_layout`` ``latent``). Training is not
built: no cut of it fits a chip's train state.

Scopes of the compiled step: ``embed``; ``self_attn`` around each
attention and, inside it, ``mla`` (a decode step's rows walk the pages in
groups of ``WALK_ROWS`` sorted by length); ``mlp`` around the feed-forward
half of each block and, inside it, ``moe`` (router, top-k, the held
experts' grouped products, the identity experts' add) and ``ffn`` (a dense
feed-forward's three products); ``head_loss``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from paddle_tpu import nn
from paddle_tpu.core.tensor import apply_op, wrap_raw
from paddle_tpu.incubate.moe import (held_experts_part, held_load,
                                     identity_experts_part, route_top_k)
from paddle_tpu.nn import initializer as I
from paddle_tpu.nn.functional.norm import group_rms
from paddle_tpu.text.models.pangu_ultra_moe import (KINDS, PanguMLAttention,
                                                    cached_mla, latent_where,
                                                    publish_moe_counts)

__all__ = ["LongcatFlashConfig", "LongcatFlashModel",
           "LongcatFlashForCausalLM", "longcat_decode_fns",
           "shortcut_moe", "longcat_flash_tiny"]

F32 = jnp.float32
# columns of the served step's counts (``cache['moe_counts']``, a row a
# kind of ``KINDS``): expert layers run, held experts that received a pair,
# pairs routed to held experts, pairs routed to identity experts, and the
# positions routed (each takes ``moe_topk`` pairs a layer)
COUNTS = ("layer_steps", "experts_hit", "pairs_here", "pairs_zero",
          "positions")
# rows of a decode step that walk the latent pages together: sorted by
# length, each group of this many walks only to its own longest row, so a
# step's walk follows its rows' lengths, not its one longest row's
WALK_ROWS = 32


@dataclass
class LongcatFlashConfig:
    # the published keys
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_rope_head_dim: int = 64
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    routed_scaling_factor: float = 6.0
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    moe_topk: int = 12
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000000.0
    # what the published config lacks
    initializer_range: float = 0.02
    # this chip's share: None holds everything
    experts_held: range = None
    vocab_rows_held: int = None
    layers_held: int = None

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = range(self.n_routed_experts)
        held = self.experts_held
        if (held.step != 1 or held.start < 0 or not len(held)
                or held.stop > self.n_routed_experts):
            raise ValueError(f"experts_held {held!r} is no run of the "
                             f"{self.n_routed_experts} routed experts")
        if self.vocab_rows_held is None:
            self.vocab_rows_held = self.vocab_size
        if self.layers_held is None:
            self.layers_held = self.num_layers

    @property
    def router_width(self) -> int:
        """The router's columns: the routed experts, then the identity
        ones."""
        return self.n_routed_experts + self.zero_expert_num

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_width(self) -> int:
        """A cached row padded to whole 128-lane groups, as openPangu's
        (``PanguUltraMoEConfig.cache_width``)."""
        return -(-self.latent_width // 128) * 128

    @property
    def q_scale(self):
        return ((self.hidden_size / self.q_lora_rank) ** 0.5
                if self.mla_scale_q_lora else None)

    @property
    def kv_scale(self):
        return ((self.hidden_size / self.kv_lora_rank) ** 0.5
                if self.mla_scale_kv_lora else None)


def shortcut_moe(c: LongcatFlashConfig, g, router_w, bias, w_gate, w_up,
                 w_down, valid=None):
    """The expert layer on ``g`` [T, h]: (y [T, h] in g's type, int32[4] =
    held experts hit, pairs routed to them, pairs routed to identity
    experts, positions routed). ``valid`` [T] (or None: all): positions
    that are not there are routed nowhere."""
    p = jax.nn.softmax(jnp.dot(g, router_w, preferred_element_type=F32),
                       axis=-1)
    chosen, weights = route_top_k(p, bias, c.moe_topk,
                                  c.routed_scaling_factor, renormalize=False)
    if valid is not None:
        chosen = jnp.where(valid.reshape(-1, 1), chosen, -1)
    first, held = c.experts_held.start, len(c.experts_held)
    y, _ = held_experts_part(g, chosen, weights, w_gate, w_up, w_down, first)
    z, zero_pairs = identity_experts_part(g, chosen, weights,
                                          c.n_routed_experts)
    positions = (jnp.sum(valid) if valid is not None
                 else jnp.asarray(g.shape[0]))
    counts = jnp.concatenate([held_load(chosen, first, held),
                              jnp.stack([zero_pairs, positions.astype(
                                  jnp.int32)])])
    return (y + z).astype(g.dtype), counts


# -- the Layer form -------------------------------------------------------------

def _linear(n_in, n_out, std):
    return nn.Linear(n_in, n_out, nn.ParamAttr(initializer=I.Normal(0.0, std)),
                     bias_attr=False)


class LongcatMoE(nn.Layer):
    """This chip's share of the shortcut expert layer: the router over all
    routed and identity experts, the held experts' stacks, every identity
    expert. What the absent routed experts would add is another chip's."""

    def __init__(self, config: LongcatFlashConfig):
        super().__init__()
        from paddle_tpu.profiler.telemetry import get_telemetry

        c, h, std = config, config.hidden_size, config.initializer_range
        self.config = c
        self.router = _linear(h, c.router_width, std)
        n, f = len(c.experts_held), c.expert_ffn_hidden_size
        init = I.Normal(0.0, std)
        self.w_gate = self.create_parameter([n, h, f],
                                            default_initializer=init)
        self.w_up = self.create_parameter([n, h, f], default_initializer=init)
        self.w_down = self.create_parameter([n, f, h],
                                            default_initializer=init)
        for p in (self.w_gate, self.w_up, self.w_down):
            p.tp_spec = ("ep", None, None)
        self.register_buffer("e_score_correction_bias", wrap_raw(
            jnp.zeros([c.router_width], F32)))
        tel = get_telemetry()
        tel.gauge("moe/experts_held", n)
        tel.gauge("moe/zero_experts", c.zero_expert_num)

    def forward(self, x):
        shape, c = x.shape, self.config
        flat = x.reshape([-1, shape[-1]])
        with jax.named_scope("moe"):
            y = apply_op(lambda *a: shortcut_moe(c, *a)[0], flat,
                         self.router.weight,
                         self.e_score_correction_bias.detach(), self.w_gate,
                         self.w_up, self.w_down, op_name="shortcut_moe")
        return y.reshape(list(shape))


class LongcatDoubleLayer(nn.Layer):
    """Two attention blocks and two dense feed-forwards, the expert layer
    beside the first feed-forward and added after the second."""

    def __init__(self, config: LongcatFlashConfig):
        super().__init__()
        c, h, eps = config, config.hidden_size, config.rms_norm_eps
        attr = nn.ParamAttr(initializer=I.Normal(0.0, c.initializer_range))
        self.input_layernorm = nn.LayerList(
            [nn.RMSNorm(h, eps) for _ in range(2)])
        self.self_attn = nn.LayerList(
            [PanguMLAttention(c, c.q_scale, c.kv_scale) for _ in range(2)])
        self.post_attention_layernorm = nn.LayerList(
            [nn.RMSNorm(h, eps) for _ in range(2)])
        self.mlps = nn.LayerList(
            [nn.SwiGLU(h, c.ffn_hidden_size, attr) for _ in range(2)])
        self.mlp = LongcatMoE(c)

    def forward(self, x):
        with jax.named_scope("self_attn"):
            x = x + self.self_attn[0](self.input_layernorm[0](x))
        with jax.named_scope("mlp"):
            g = self.post_attention_layernorm[0](x)
            m = self.mlp(g)
            with jax.named_scope("ffn"):
                x = x + self.mlps[0](g)
        with jax.named_scope("self_attn"):
            x = x + self.self_attn[1](self.input_layernorm[1](x))
        with jax.named_scope("mlp"):
            with jax.named_scope("ffn"):
                f = self.mlps[1](self.post_attention_layernorm[1](x))
            return x + f + m


class LongcatFlashModel(nn.Layer):
    def __init__(self, config: LongcatFlashConfig):
        super().__init__()
        self.config = config
        self.embed = nn.Embedding(
            config.vocab_rows_held, config.hidden_size,
            weight_attr=nn.ParamAttr(initializer=I.Normal(
                0.0, config.initializer_range)))
        self.layers = nn.LayerList([LongcatDoubleLayer(config)
                                    for _ in range(config.layers_held)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids):
        with jax.named_scope("embed"):
            x = self.embed(input_ids)
        for layer in self.layers:
            x = layer(x)
        return x


class LongcatFlashForCausalLM(nn.Layer):
    """Untied head over the rows of the vocabulary held here; ``forward``
    gives the logits of whole sequences."""

    def __init__(self, config: LongcatFlashConfig):
        super().__init__()
        self.config = config
        self.model = LongcatFlashModel(config)
        self.lm_head = _linear(config.hidden_size, config.vocab_rows_held,
                               config.initializer_range)

    def forward(self, input_ids):
        h = self.model(input_ids)
        with jax.named_scope("head_loss"):
            return self.lm_head(self.model.norm(h))

    def decode_spec(self, kv_dtype: str = "float32") -> dict:
        """What ``inference.serving.TokenServingEngine`` asks a model: a
        latent cache of two entries a double layer, each ``[blocks, block,
        640]``; the expert layers' counts ride in the cache pytree
        (``moe_counts``, ``KINDS`` by ``COUNTS``) and are read when the
        engine asks, never a step. No draft: the published model has no
        next-token module."""
        c = self.config
        return {"forward_chunk": longcat_decode_fns(c, kv_dtype),
                "num_layers": 2 * c.layers_held,
                "num_heads": c.num_attention_heads, "num_kv_heads": 1,
                "head_dim": c.cache_width,
                "max_positions": c.max_position_embeddings,
                "kv_layout": "latent", "state": {},
                "counters": {"moe_counts": ((len(KINDS), len(COUNTS)),
                                            "int32")},
                "publish_counters": functools.partial(publish_moe_counts,
                                                      names=COUNTS)}


# -- the cached form ------------------------------------------------------------

def longcat_decode_fns(config: LongcatFlashConfig, kv_dtype: str = "float32"):
    """The cached forward for token-level serving, ``forward_chunk(params,
    tokens, q_positions, cache, block_tables, kv_lens, slots) -> (logits
    [B, T, rows], cache)``, which advances every row's cache by a T-token
    chunk. ``cache['latent'][2 i + j]`` is double layer i's attention j's
    pages ``[blocks, block, 640]`` (``[scaled c | rotated k_r | zeros]``);
    ``cache['moe_counts']`` int32 ``[2, 5]``. A position is there where
    ``q_position < kv_len``; the others write to the scratch page and are
    routed to no expert."""
    c = config
    if kv_dtype == "int8":
        raise ValueError("longcat_decode_fns: latent pages have no int8 form")
    eps = c.rms_norm_eps
    no_bias = jnp.zeros((c.router_width,), F32)

    def rms(x, w):
        return group_rms(x, w, eps, 1)

    def ffn(g, p, at):
        with jax.named_scope("ffn"):
            return (jax.nn.silu(g @ p(at + "gate_proj.weight"))
                    * (g @ p(at + "up_proj.weight"))) \
                @ p(at + "down_proj.weight")

    def attend(p, j, x, pages, where):
        with jax.named_scope("self_attn"):
            a, pages = cached_mla(
                c, lambda n: p(f"self_attn.{j}." + n),
                rms(x, p(f"input_layernorm.{j}.weight")), pages, where,
                c.q_scale, c.kv_scale, rows_per_walk=WALK_ROWS)
        return x + a, pages

    def double_layer(params, at, x, pages, where, valid):
        p = lambda name: params[at + name]  # noqa: E731
        B, T, h = x.shape
        first, second = pages
        x, first = attend(p, 0, x, first, where)
        with jax.named_scope("mlp"):
            g = rms(x, p("post_attention_layernorm.0.weight"))
            with jax.named_scope("moe"):
                m, counts = shortcut_moe(
                    c, g.reshape(B * T, h), p("mlp.router.weight"), no_bias,
                    p("mlp.w_gate"), p("mlp.w_up"), p("mlp.w_down"), valid)
            x = x + ffn(g, p, "mlps.0.")
        x, second = attend(p, 1, x, second, where)
        with jax.named_scope("mlp"):
            x = x + ffn(rms(x, p("post_attention_layernorm.1.weight")), p,
                        "mlps.1.") + m.reshape(B, T, h)
        return x, (first, second), counts

    def forward_chunk(params, tokens, q_positions, cache, block_tables,
                      kv_lens, slots):
        cache = dict(cache)
        pages = list(cache["latent"])
        where, valid = latent_where(cache, q_positions, block_tables,
                                    kv_lens)
        with jax.named_scope("embed"):
            x = params["model.embed.weight"][tokens]
        counts = []
        for i in range(c.layers_held):
            x, pages[2 * i:2 * i + 2], got = double_layer(
                params, f"model.layers.{i}.", x, pages[2 * i:2 * i + 2],
                where, valid)
            counts.append(got)
        cache["latent"] = tuple(pages)
        if counts and "moe_counts" in cache:
            ran = jnp.any(valid).astype(jnp.int32)
            step = jnp.concatenate([ran[None] * len(counts),
                                    sum(counts)]).astype(jnp.int32)
            cache["moe_counts"] = cache["moe_counts"].at[
                0 if tokens.shape[1] == 1 else 1].add(step)
        with jax.named_scope("head_loss"):
            logits = rms(x, params["model.norm.weight"]) \
                @ params["lm_head.weight"]
        return logits, cache

    return forward_chunk


def longcat_flash_tiny(**kw):
    """Every part at a width a CPU test can afford: 4 heads, a compressed
    query, 8 routed experts and 4 identity ones, 3 a token, two double
    layers."""
    base = dict(
        vocab_size=256, hidden_size=64, ffn_hidden_size=128,
        expert_ffn_hidden_size=32, num_layers=2, num_attention_heads=4,
        kv_lora_rank=32, q_lora_rank=24, qk_rope_head_dim=8,
        qk_nope_head_dim=16, v_head_dim=16, n_routed_experts=8,
        zero_expert_num=4, moe_topk=3, max_position_embeddings=512)
    base.update(kw)
    return LongcatFlashConfig(**base)
