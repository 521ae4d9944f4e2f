"""Kimi-Linear (``model_type`` ``kimi_linear``): a decoder whose layers are
of three kinds by a per-layer pattern. Token mixing is Kimi Delta
Attention (a gated delta rule with a per-channel decay, linear in the
length; ``ops.linear_attention``) in three layers of four and latent
attention without a position embedding (MLA, NoPE) in the fourth; the
feed-forward is a dense gated MLP in the leading layers and a routed
expert layer with a shared expert (``incubate.moe.DroplessMoE``) in the
rest. Pre-norm residual blocks with RMSNorm, no position embedding
anywhere, an untied head.

A chip of an expert-parallel job holds a run of the routed experts
(``experts_held``) and a slice of the vocabulary (``vocab_rows_held``);
the layers it does not hold are another pipeline stage's
(``num_hidden_layers`` counts the ones here, from the first published
one). The defaults are the published Kimi-Linear-48B-A3B whole.

Scopes of the compiled step: ``self_attn`` around a mixer and, inside it,
``kda`` (convolutions, decay and write gates, ``chunk_kda``) or
``attention`` (MLA's score space, from ``dot_product_attention``); ``mlp``
around the feed-forward and, inside it, ``moe`` (router, top-k, sort,
grouped products, scatter); ``embed``; ``head_loss``.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from paddle_tpu import nn
from paddle_tpu.core.tensor import apply_op
from paddle_tpu.incubate.moe import DroplessMoE
from paddle_tpu.jit.functionalize import checkpointed_call
from paddle_tpu.nn import functional as F
from paddle_tpu.nn import initializer as I
from paddle_tpu.ops import remat_policy
from paddle_tpu.ops.attention import dot_product_attention
from paddle_tpu.ops.linear_attention import chunk_kda, short_conv

__all__ = ["KimiLinearConfig", "KimiLinearModel", "KimiLinearForCausalLM",
           "kimi_linear_tiny"]

_KDA_LAYERS = tuple(i for i in range(1, 28) if i % 4 and i != 27)
_FULL_ATTN_LAYERS = (4, 8, 12, 16, 20, 24, 27)


@dataclass
class KimiLinearConfig:
    # the published keys (``linear_attn_config``'s under kda_*)
    vocab_size: int = 163840
    hidden_size: int = 2304
    num_hidden_layers: int = 27
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_experts: int = 256
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 2.446
    moe_renormalize: bool = True
    num_attention_heads: int = 32
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64   # kept wide, never rotated (mla_use_nope)
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    rms_norm_eps: float = 1e-5
    kda_layers: tuple = _KDA_LAYERS              # 1-based, as published
    full_attn_layers: tuple = _FULL_ATTN_LAYERS
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    # what the published config lacks
    gate_rank: int = 0           # of the two low-rank pairs; 0: kda_head_dim
    initializer_range: float = 0.02
    l2_norm_eps: float = 1e-6
    kda_chunk: int = 64
    # this chip's share: None holds everything
    experts_held: range = None
    vocab_rows_held: int = None

    def __post_init__(self):
        self.gate_rank = self.gate_rank or self.kda_head_dim
        if self.experts_held is None:
            self.experts_held = range(self.num_experts)
        if self.vocab_rows_held is None:
            self.vocab_rows_held = self.vocab_size
        listed = set(self.kda_layers) | set(self.full_attn_layers)
        missing = set(range(1, self.num_hidden_layers + 1)) - listed
        if missing:
            raise ValueError(f"layers {sorted(missing)} are in neither "
                             "kda_layers nor full_attn_layers")

    @property
    def layer_types(self) -> list:
        """(mixer, feed-forward) of every layer held: 'kda' or 'mla',
        'dense' or 'moe'."""
        return [("kda" if i in self.kda_layers else "mla",
                 "dense" if i <= self.first_k_dense_replace else "moe")
                for i in range(1, self.num_hidden_layers + 1)]


class _LogOfUniform(I.Initializer):
    """log of U(low, high): ``A_log``'s published initial range."""

    def __init__(self, low, high):
        self.low, self.high = low, high

    def _generate(self, shape, dtype, key):
        return jnp.log(jax.random.uniform(key, shape, dtype, self.low,
                                          self.high))


class _InverseSoftplusOfLogUniform(I.Initializer):
    """b with softplus(b) log-uniform in [low, high]: ``dt_bias``."""

    def __init__(self, low, high):
        self.low, self.high = low, high

    def _generate(self, shape, dtype, key):
        dt = jnp.exp(jax.random.uniform(key, shape, dtype, jnp.log(self.low),
                                        jnp.log(self.high)))
        return dt + jnp.log(-jnp.expm1(-dt))


def _linear(n_in, n_out, std):
    return nn.Linear(n_in, n_out, nn.ParamAttr(initializer=I.Normal(0.0, std)),
                     bias_attr=False)


class KimiDeltaAttention(nn.Layer):
    """q, k, v = SiLU(conv(W x)); a decay a channel g = -exp(A_log)
    softplus(W_f2 W_f1 x + dt_bias); beta = sigmoid(W_b x) a head; the
    gated delta rule on q and k L2-normalised a head (``chunk_kda``, which
    takes them raw with the norm's epsilon); output W_o (RMSNorm_head(o) *
    sigmoid(W_g2 W_g1 x)).

    Between the projections every tensor of a token stays [b, l, heads d]:
    what is a head's own is either ``chunk_kda``'s (the L2 norms, which on
    the kernels' lowering are sums by products on that shape) or written
    on the flat shape here (the decay's rate repeated over a head's
    channels; the output norm, ``F.rms_norm`` with ``gate``). An operation
    that computes on [b, l, heads, d] costs the TPU a relayout of the whole
    tensor, there and back (``tests/test_kda_tpu_compile.py`` reads the
    compiled layer for them). q and k are float32 from their convolutions
    to their norm, as the compiler kept them when the norm was this
    layer's: rounding them before it moves the step away from the float32
    reference (PERF.md section 6, PR 33)."""

    def __init__(self, config: KimiLinearConfig):
        super().__init__()
        h, std = config.hidden_size, config.initializer_range
        self.heads, self.head_dim = config.kda_num_heads, config.kda_head_dim
        self.chunk, self.l2_eps = config.kda_chunk, config.l2_norm_eps
        width, k = self.heads * self.head_dim, config.short_conv_kernel_size
        self.q_proj, self.k_proj, self.v_proj = (
            _linear(h, width, std) for _ in range(3))
        conv = I.Uniform(-k ** -0.5, k ** -0.5)
        self.q_conv, self.k_conv, self.v_conv = (
            self.create_parameter([width, k], default_initializer=conv)
            for _ in range(3))
        self.f_a, self.f_b = (_linear(h, config.gate_rank, std),
                              _linear(config.gate_rank, width, std))
        self.A_log = self.create_parameter(
            [self.heads], default_initializer=_LogOfUniform(1.0, 16.0))
        self.dt_bias = self.create_parameter(
            [width],
            default_initializer=_InverseSoftplusOfLogUniform(1e-3, 1e-1))
        self.b_proj = _linear(h, self.heads, std)
        self.g_a, self.g_b = (_linear(h, config.gate_rank, std),
                              _linear(config.gate_rank, width, std))
        self.o_norm = nn.GatedRMSNorm(self.head_dim, config.rms_norm_eps)
        self.o_proj = _linear(width, h, std)
        for proj in (self.q_proj, self.k_proj, self.v_proj, self.f_b,
                     self.g_b):
            proj.weight.tp_spec = (None, "mp")   # heads split
        self.o_proj.weight.tp_spec = ("mp", None)

    def forward(self, x):
        heads, d, chunk, eps = (self.heads, self.head_dim, self.chunk,
                                self.l2_eps)
        # a layer that is made again whole keeps nothing of its own
        keep_inputs = not remat_policy.layers_checkpointed()

        def mix(q, k, v, decay_in, write_in, q_conv, k_conv, v_conv, a_log,
                dt_bias):
            # everything here computes on [b, l, h d], the convolutions'
            # and the kernels' layout; the heads' axis is only named
            by_head = lambda t: t.reshape(*t.shape[:2], heads, d)  # noqa: E731
            # q and k stay float32 up to their norm (the class docstring)
            f32 = jnp.float32
            q, k, v = (jax.nn.silu(short_conv(t.astype(dtype), w))
                       for t, w, dtype in ((q, q_conv, f32), (k, k_conv, f32),
                                           (v, v_conv, v.dtype)))
            rate = jnp.repeat(-jnp.exp(a_log.astype(jnp.float32)), d)
            g = rate * jax.nn.softplus(
                decay_in.astype(jnp.float32) + dt_bias.astype(jnp.float32))
            beta = jax.nn.sigmoid(write_in.astype(jnp.float32))
            return chunk_kda(by_head(q), by_head(k), by_head(v), by_head(g),
                             beta, eps, chunk=chunk, checkpoint=keep_inputs)

        q, k, v = self.q_proj(x), self.k_proj(x), self.v_proj(x)
        with jax.named_scope("kda"):
            o = apply_op(mix, q, k, v, self.f_b(self.f_a(x)), self.b_proj(x),
                         self.q_conv, self.k_conv, self.v_conv, self.A_log,
                         self.dt_bias, op_name="kda")
        o = self.o_norm(o, self.g_b(self.g_a(x)))
        b, l = x.shape[:2]
        return self.o_proj(o.reshape([b, l, heads * d]))


class KimiMLAttention(nn.Layer):
    """Latent attention without rotation: q = W_q x a head of nope + rope
    columns; [c, k_s] = W_kva x, k_s shared by the heads; [k_n, v] = W_kvb
    RMSNorm(c); causal softmax of q.[k_n, k_s] / sqrt(nope + rope)."""

    def __init__(self, config: KimiLinearConfig):
        super().__init__()
        h, std = config.hidden_size, config.initializer_range
        self.heads = config.num_attention_heads
        self.nope, self.rope = config.qk_nope_head_dim, config.qk_rope_head_dim
        self.v_dim, self.latent = config.v_head_dim, config.kv_lora_rank
        self.q_proj = _linear(h, self.heads * (self.nope + self.rope), std)
        self.kv_a_proj = _linear(h, self.latent + self.rope, std)
        self.kv_a_norm = nn.RMSNorm(self.latent, config.rms_norm_eps)
        self.kv_b_proj = _linear(self.latent,
                                 self.heads * (self.nope + self.v_dim), std)
        self.o_proj = _linear(self.heads * self.v_dim, h, std)
        self.q_proj.weight.tp_spec = (None, "mp")
        self.kv_b_proj.weight.tp_spec = (None, "mp")
        self.o_proj.weight.tp_spec = ("mp", None)

    def forward(self, x):
        b, l = x.shape[:2]
        heads, nope, rope, latent = self.heads, self.nope, self.rope, self.latent

        def attend(q, kva, kvb):
            q = q.reshape(b, l, heads, nope + rope)
            kvb = kvb.reshape(b, l, heads, -1)
            shared = jnp.broadcast_to(kva[:, :, None, latent:],
                                      (b, l, heads, rope))
            k = jnp.concatenate([kvb[..., :nope], shared], axis=-1)
            o = dot_product_attention(q, k, kvb[..., nope:], causal=True,
                                      layout="blhd")
            return o.reshape(b, l, -1)

        kva = self.kv_a_proj(x)
        kvb = self.kv_b_proj(self.kv_a_norm(kva[:, :, :latent]))
        return self.o_proj(apply_op(attend, self.q_proj(x), kva, kvb,
                                    op_name="mla"))


class KimiBlock(nn.Layer):
    def __init__(self, config: KimiLinearConfig, mixer: str, ffn: str):
        super().__init__()
        h, eps = config.hidden_size, config.rms_norm_eps
        attr = nn.ParamAttr(initializer=I.Normal(0.0, config.initializer_range))
        self.attn_norm = nn.RMSNorm(h, eps)
        self.mixer = (KimiDeltaAttention if mixer == "kda"
                      else KimiMLAttention)(config)
        self.ffn_norm = nn.RMSNorm(h, eps)
        if ffn == "dense":
            self.ffn = nn.SwiGLU(h, config.intermediate_size, attr)
        else:
            self.ffn = DroplessMoE(
                h, config.moe_intermediate_size, config.num_experts,
                experts_held=config.experts_held,
                top_k=config.num_experts_per_token,
                scale=config.routed_scaling_factor,
                renormalize=config.moe_renormalize,
                shared_experts=config.num_shared_experts, weight_attr=attr)

    def _mix(self, x):
        with jax.named_scope("self_attn"):
            return x + self.mixer(self.attn_norm(x))

    def _feed(self, x):
        with jax.named_scope("mlp"):
            return x + self.ffn(self.ffn_norm(x))

    def forward(self, x):
        # under remat='layer' each half is made again on its own in the
        # backward: the step then holds the larger half's activations, not
        # both halves'
        x = checkpointed_call(self._mix, (self.attn_norm, self.mixer), x)
        return checkpointed_call(self._feed, (self.ffn_norm, self.ffn), x)


class KimiLinearModel(nn.Layer):
    def __init__(self, config: KimiLinearConfig):
        super().__init__()
        self.config = config
        self.embed = nn.Embedding(
            config.vocab_rows_held, config.hidden_size,
            weight_attr=nn.ParamAttr(initializer=I.Normal(
                0.0, config.initializer_range)))
        self.embed.weight.tp_spec = ("mp", None)
        self.layers = nn.LayerList([KimiBlock(config, mixer, ffn)
                                    for mixer, ffn in config.layer_types])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids):
        with jax.named_scope("embed"):
            x = self.embed(input_ids)
        for block in self.layers:
            x = block(x)
        # the final norm belongs to the head, as GPT's does
        with jax.named_scope("head_loss"):
            return self.norm(x)


class KimiLinearForCausalLM(nn.Layer):
    """Untied head over the rows of the vocabulary held here; with
    ``labels`` the forward returns the mean next-token loss over them."""

    def __init__(self, config: KimiLinearConfig):
        super().__init__()
        self.config = config
        self.model = KimiLinearModel(config)
        self.lm_head = _linear(config.hidden_size, config.vocab_rows_held,
                               config.initializer_range)
        self.lm_head.weight.tp_spec = (None, "mp")

    def forward(self, input_ids, labels=None):
        h = self.model(input_ids)
        with jax.named_scope("head_loss"):
            logits = self.lm_head(h)
            if labels is None:
                return logits
            return F.cross_entropy(
                logits.reshape([-1, self.config.vocab_rows_held]),
                labels.reshape([-1]))


def kimi_linear_tiny(**kw):
    """Every kind of layer at a width a CPU test can afford: KDA + dense,
    KDA + experts, MLA + experts; 8 routed experts, 2 a token."""
    base = dict(
        vocab_size=256, hidden_size=64, num_hidden_layers=3,
        intermediate_size=128, moe_intermediate_size=32, num_experts=8,
        num_experts_per_token=2, num_attention_heads=2, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
        kda_layers=(1, 2), full_attn_layers=(3,), kda_num_heads=2,
        kda_head_dim=16, gate_rank=8, kda_chunk=16)
    base.update(kw)
    return KimiLinearConfig(**base)
