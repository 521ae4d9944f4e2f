"""Falcon-H1 (``model_type`` ``falcon_h1``): a decoder whose every block
runs a Mamba-2 state-space mixer and grouped-query rotary attention side by
side on one normed input, adds both to the residual, and follows them with
a gated MLP. Fixed multipliers (a maximal-update parametrisation) stand at
the embedding, at both mixers' inputs and outputs, on the keys, on the five
segments of the mixer's input projection, inside and after the MLP, and on
the logits; each is applied where the published equations put it.

    u  = RMSNorm(x)
    [z | xBC | dt] = ((u * ssm_in) @ W_in) * mup          (segments z, x, B, C, dt)
    xBC = silu(conv4(xBC) + b);  [x_s | B | C] = xBC      (head i reads group i // (heads / groups))
    dt = softplus(dt + dt_bias);  S_t = exp(dt A) S_{t-1} + dt x_s B^T;  y = S C + D x_s
    m  = (RMSNorm_groups(y * silu(z)) * w) @ W_out * ssm_out
    q, k, v = (u * attn_in) @ W_{q,k,v};  k *= key_mult;  q, k = rope(q, k)
    a  = softmax_causal(q k^T / sqrt(d)) v @ W_o * attn_out
    x  = x + m + a
    x  = x + W_down(silu(W_gate g * mlp_0) * W_up g) * mlp_1,   g = RMSNorm(x)

A chip of a pipeline holds a run of the layers (``layers_held``, from the
first published one) and a slice of the vocabulary (``vocab_rows_held``:
ids, logits and ``argmax`` are over the slice). The defaults are the
published Falcon-H1-34B-Instruct whole.

Two forms of one model. ``FalconH1ForCausalLM`` is the ``nn.Layer``: it
holds the parameters (``jit.functionalize.get_params`` / ``set_params``
carry them, as GPT's) and its forward gives a whole sequence's logits from
an empty state. ``falcon_h1_decode_fns`` is the cached forward that
``inference.serving.TokenServingEngine`` serves with: one
``forward_chunk`` for a prefill chunk and a decode step alike, over a
cache pytree that holds K and V pages and, a sequence's slot, the
recurrent state and the convolution's tail
(``inference/serving/kv_cache.py``). What is a mixer's own arithmetic
(``_ssm``, ``_qkv``) is written once and both forms call it. Training this
model is not built: no cut of it fits a chip's train state.

Scopes of the compiled step: ``embed``; ``self_attn`` around both mixers
and, inside it, ``ssm`` (the state's and the tail's gather and scatter,
the convolution, the gates, ``chunk_ssd`` / ``ssd_step``, the gated norm;
the two projections stay ``self_attn``'s) and ``attention`` (the paged
call); ``mlp``; ``head_loss`` (final norm, head).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from paddle_tpu import nn
from paddle_tpu.core.tensor import apply_op
from paddle_tpu.nn import initializer as I
from paddle_tpu.nn.functional.common import rotary_embedding
from paddle_tpu.nn.functional.norm import group_rms
from paddle_tpu.ops.linear_attention import chunk_ssd, short_conv, ssd_step

__all__ = ["FalconH1Config", "FalconH1Model", "FalconH1ForCausalLM",
           "falcon_h1_decode_fns", "falcon_h1_tiny"]

F32 = jnp.float32


@dataclass
class FalconH1Config:
    # the published keys
    vocab_size: int = 261120
    hidden_size: int = 5120
    num_hidden_layers: int = 72
    intermediate_size: int = 21504
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e11
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    mamba_d_ssm: int = 4096
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_n_groups: int = 2
    mamba_d_state: int = 256
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    ssm_multipliers: tuple = (0.3535533905932738, 0.25, 0.1767766952966369,
                              0.5, 0.3535533905932738)
    mlp_multipliers: tuple = (0.1767766952966369, 0.011160714285714284)
    # what the published config lacks
    initializer_range: float = 0.02
    # this chip's share: None holds everything
    vocab_rows_held: int = None
    layers_held: int = None

    def __post_init__(self):
        if self.vocab_rows_held is None:
            self.vocab_rows_held = self.vocab_size
        if self.layers_held is None:
            self.layers_held = self.num_hidden_layers
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_d_ssm:
            raise ValueError("mamba_d_ssm is not mamba_n_heads heads of "
                             "mamba_d_head")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("the query heads are no multiple of the key "
                             "heads")

    @property
    def conv_dim(self) -> int:
        """Columns the convolution runs over: x, B and C."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def segments(self) -> tuple:
        """Widths of the input projection's five segments z, x, B, C, dt."""
        gn = self.mamba_n_groups * self.mamba_d_state
        return (self.mamba_d_ssm, self.mamba_d_ssm, gn, gn,
                self.mamba_n_heads)

    def mup_vector(self):
        """``ssm_multipliers`` laid over the projection's columns."""
        return jnp.concatenate([jnp.full((w,), m, F32) for w, m in
                                zip(self.segments, self.ssm_multipliers)])


# -- a mixer's own arithmetic, for both forms (raw arrays) ----------------------

def _ssm(c: FalconH1Config, proj, conv_w, conv_b, a_log, dt_bias, d_skip,
         tail=None, state=None, valid=None):
    """From the input projection's output (multipliers applied) to the
    recurrence's y [b, l, d_ssm] and the gate z beside it (the gated norm
    follows: ``nn.GatedRMSNorm`` in the Layer form, ``_gated_norm`` in the
    cached one). ``tail`` [b, conv - 1, conv_dim] and
    ``state`` [b, heads, d_head, d_state] are what a cached sequence
    carries (None: a sequence from its start), ``valid`` [b, l] marks the
    positions that are there: the others get dt = 0, which decays nothing
    and writes nothing, and are left out of the new tail. Returns ``(y, z,
    new tail, new state)``."""
    b, l, _ = proj.shape
    d, heads, gn = c.mamba_d_ssm, c.mamba_n_heads, c.segments[2]
    z, xbc, dt = (proj[..., :d], proj[..., d:d + c.conv_dim],
                  proj[..., d + c.conv_dim:])
    if tail is None:
        conv, new_tail = short_conv(xbc, conv_w), None
    else:
        conv, new_tail = short_conv(
            xbc, conv_w, tail,
            None if valid is None else jnp.sum(valid, axis=1, dtype=jnp.int32))
    xbc = jax.nn.silu(conv.astype(F32) + conv_b.astype(F32)).astype(proj.dtype)
    x = xbc[..., :d].reshape(b, l, heads, c.mamba_d_head)
    B = xbc[..., d:d + gn].reshape(b, l, c.mamba_n_groups, c.mamba_d_state)
    C = xbc[..., d + gn:].reshape(b, l, c.mamba_n_groups, c.mamba_d_state)
    dt = jax.nn.softplus(dt.astype(F32) + dt_bias.astype(F32))
    if valid is not None:
        dt = jnp.where(valid[..., None], dt, 0.0)
    A = -jnp.exp(a_log.astype(F32))
    if l == 1 and state is not None:
        y, state = ssd_step(x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], d_skip,
                            state)
        y = y[:, None]
    else:
        y, state = chunk_ssd(x, dt, A, B, C, d_skip, c.mamba_chunk_size,
                             state)
    return y.reshape(b, l, d), z, new_tail, state


def _gated_norm(c: FalconH1Config, y, z, w):
    """RMSNorm_groups(y * silu(z)) * w: the gate comes before the norm
    (``mamba_norm_before_gate`` false)."""
    gated = y.astype(F32) * jax.nn.silu(z.astype(F32))
    return group_rms(gated.astype(y.dtype), w, c.rms_norm_eps,
                     c.mamba_n_groups)


def _qkv(c: FalconH1Config, q, k, v, positions):
    """The three projections' outputs [b, l, heads * d] to rotated q
    [b, l, Hq, d], rotated and scaled k and v [b, l, Hkv, d]."""
    b, l, _ = q.shape
    q = q.reshape(b, l, c.num_attention_heads, c.head_dim)
    k = (k * jnp.asarray(c.key_multiplier, k.dtype)).reshape(
        b, l, c.num_key_value_heads, c.head_dim)
    v = v.reshape(b, l, c.num_key_value_heads, c.head_dim)
    return (rotary_embedding(q, positions, c.rope_theta),
            rotary_embedding(k, positions, c.rope_theta), v)


def _dense_attention(q, k, v):
    """Causal softmax attention of whole rows, the grouped keys read as
    they are: [b, l, Hq, d] against [b, l, Hkv, d]."""
    b, l, hq, d = q.shape
    g = hq // k.shape[2]
    s = jnp.einsum("bqkgd,bskd->bkgqs", q.reshape(b, l, -1, g, d).astype(F32),
                   k.astype(F32)) / jnp.sqrt(jnp.asarray(d, F32))
    s = jnp.where(jnp.tril(jnp.ones((l, l), bool)), s, -jnp.inf)
    o = jnp.einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(s, axis=-1),
                   v.astype(F32))
    return o.reshape(b, l, hq * d).astype(q.dtype)


# -- the Layer form -------------------------------------------------------------

def _linear(n_in, n_out, std):
    return nn.Linear(n_in, n_out, nn.ParamAttr(initializer=I.Normal(0.0, std)),
                     bias_attr=False)


class FalconH1Mamba(nn.Layer):
    """The Mamba-2 mixer: ``in_proj`` to [z | xBC | dt], a depthwise causal
    convolution of 4 with bias and SiLU over xBC, the scalar-decay
    recurrence a head (``ops.linear_attention.chunk_ssd``), a grouped RMS
    norm gated by silu(z) beforehand, ``out_proj``."""

    def __init__(self, config: FalconH1Config):
        super().__init__()
        c, std = config, config.initializer_range
        self.config = c
        self.in_proj = _linear(c.hidden_size, sum(c.segments), std)
        self.conv_weight = self.create_parameter(
            [c.conv_dim, c.mamba_d_conv], default_initializer=I.Uniform(
                -c.mamba_d_conv ** -0.5, c.mamba_d_conv ** -0.5))
        self.conv_bias = self.create_parameter(
            [c.conv_dim], default_initializer=I.Constant(0.0))
        self.dt_bias = self.create_parameter(
            [c.mamba_n_heads], default_initializer=I.Normal(0.0, 1.0))
        self.A_log = self.create_parameter(
            [c.mamba_n_heads], default_initializer=I.Constant(0.0))
        self.D = self.create_parameter(
            [c.mamba_n_heads], default_initializer=I.Constant(1.0))
        self.norm = nn.GatedRMSNorm(c.mamba_d_ssm, c.rms_norm_eps,
                                    groups=c.mamba_n_groups)
        self.out_proj = _linear(c.mamba_d_ssm, c.hidden_size, std)

    def forward(self, u):
        c = self.config
        proj = self.in_proj(u * c.ssm_in_multiplier)

        def mix(proj, *leaves):
            return _ssm(c, proj * c.mup_vector().astype(proj.dtype),
                        *leaves)[:2]

        with jax.named_scope("ssm"):
            y, z = apply_op(mix, proj, self.conv_weight, self.conv_bias,
                            self.A_log, self.dt_bias, self.D, multi_out=True,
                            op_name="ssm")
            y = self.norm(y, z)
        return self.out_proj(y) * c.ssm_out_multiplier


class FalconH1Attention(nn.Layer):
    """Grouped-query attention with a rotary embedding over the whole head
    width, no bias."""

    def __init__(self, config: FalconH1Config):
        super().__init__()
        c, std = config, config.initializer_range
        self.config = c
        self.q_proj = _linear(c.hidden_size,
                              c.num_attention_heads * c.head_dim, std)
        self.k_proj, self.v_proj = (
            _linear(c.hidden_size, c.num_key_value_heads * c.head_dim, std)
            for _ in range(2))
        self.o_proj = _linear(c.num_attention_heads * c.head_dim,
                              c.hidden_size, std)

    def forward(self, u):
        c = self.config
        u = u * c.attention_in_multiplier

        def attend(q, k, v):
            b, l, _ = q.shape
            positions = jnp.broadcast_to(jnp.arange(l, dtype=jnp.int32),
                                         (b, l))
            with jax.named_scope("attention"):
                return _dense_attention(*_qkv(c, q, k, v, positions))

        o = apply_op(attend, self.q_proj(u), self.k_proj(u), self.v_proj(u),
                     op_name="gqa")
        return self.o_proj(o) * c.attention_out_multiplier


class FalconH1Block(nn.Layer):
    def __init__(self, config: FalconH1Config):
        super().__init__()
        c = config
        attr = nn.ParamAttr(initializer=I.Normal(0.0, c.initializer_range))
        self.input_norm = nn.RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.mamba = FalconH1Mamba(c)
        self.attn = FalconH1Attention(c)
        self.ffn_norm = nn.RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.ffn = nn.SwiGLU(c.hidden_size, c.intermediate_size, attr,
                             gate_scale=c.mlp_multipliers[0],
                             out_scale=c.mlp_multipliers[1])

    def forward(self, x):
        with jax.named_scope("self_attn"):
            u = self.input_norm(x)
            x = x + self.mamba(u) + self.attn(u)
        with jax.named_scope("mlp"):
            return x + self.ffn(self.ffn_norm(x))


class FalconH1Model(nn.Layer):
    def __init__(self, config: FalconH1Config):
        super().__init__()
        self.config = config
        self.embed = nn.Embedding(
            config.vocab_rows_held, config.hidden_size,
            weight_attr=nn.ParamAttr(initializer=I.Normal(
                0.0, config.initializer_range)))
        self.layers = nn.LayerList([FalconH1Block(config)
                                    for _ in range(config.layers_held)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids):
        with jax.named_scope("embed"):
            x = self.embed(input_ids) * self.config.embedding_multiplier
        for block in self.layers:
            x = block(x)
        with jax.named_scope("head_loss"):
            return self.norm(x)


class FalconH1ForCausalLM(nn.Layer):
    """Untied head over the rows of the vocabulary held here. The forward
    gives the logits of whole sequences, each from an empty state."""

    def __init__(self, config: FalconH1Config):
        super().__init__()
        self.config = config
        self.model = FalconH1Model(config)
        self.lm_head = _linear(config.hidden_size, config.vocab_rows_held,
                               config.initializer_range)

    def forward(self, input_ids):
        h = self.model(input_ids)
        with jax.named_scope("head_loss"):
            return self.lm_head(h) * self.config.lm_head_multiplier

    def decode_spec(self, kv_dtype: str = "float32") -> dict:
        """What ``inference.serving.TokenServingEngine`` asks a model: the
        cached forward and the cache it runs over. Pages of the key heads
        alone, a layer's array of its own with the heads flattened; a slot
        a sequence of the recurrent state in float32 (it is multiplied by
        a decay of up to 0.999... a token over hundreds of tokens) and of
        the convolution's tail in the activations' type."""
        c = self.config
        return {"forward_chunk": falcon_h1_decode_fns(c, kv_dtype),
                "num_layers": c.layers_held,
                "num_heads": c.num_attention_heads,
                "num_kv_heads": c.num_key_value_heads,
                "head_dim": c.head_dim,
                "max_positions": c.max_position_embeddings,
                "kv_layout": "per_layer",
                "state": {
                    "ssm": ((c.mamba_n_heads, c.mamba_d_head,
                             c.mamba_d_state), "float32"),
                    "conv": ((c.mamba_d_conv - 1, c.conv_dim),
                             jnp.dtype(
                                 self.model.embed.weight._value.dtype).name)}}


# -- the cached form ------------------------------------------------------------

def falcon_h1_decode_fns(config: FalconH1Config, kv_dtype: str = "float32"):
    """Pure cached forward for token-level serving, the twin of
    ``gpt_decode_fns``: ``forward_chunk(params, tokens, q_positions, cache,
    block_tables, kv_lens, slots) -> (logits [B, T, rows], cache)`` advances
    every row's cache by a T-token chunk (a prefill chunk, or T = 1: a
    decode step). ``params`` is ``get_params`` of a ``FalconH1ForCausalLM``;
    ``cache`` is a ``KVCachePool.pages`` pytree in the layout
    ``decode_spec`` names: ``cache['k'][i]`` / ``['v'][i]`` a layer's pages
    [blocks, block, Hkv * d] (k is cached after its rotation),
    ``cache['ssm'][i]`` [slots + 1, heads, d_head, d_state] float32 and
    ``cache['conv'][i]`` [slots + 1, conv - 1, conv_dim].

    A position is there where ``q_position < kv_len``; the others (a
    chunk's padded tail, a bucket's padded rows) write K and V to the
    scratch page, get dt = 0 (the state passes them unchanged) and stay out
    of the carried tail. A row whose chunk begins at position 0 starts from
    a zero state and a zero tail, whatever its slot held: a reused slot and
    a sequence evicted and admitted again carry nothing over, and nothing
    is cleared on the host. Padded rows name slot 0, the scratch slot."""
    from paddle_tpu.ops.attention import paged_attention

    c = config
    if kv_dtype == "int8":
        raise ValueError("falcon_h1_decode_fns: K and V pages with the heads "
                         "flattened have no int8 form")
    store = jnp.dtype(kv_dtype)
    eps = c.rms_norm_eps
    hq, hkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim

    def rms(x, w):
        return group_rms(x, w, eps, 1)

    def through_slots(tails, states, proj, valid, first, slots, mix, norm_w):
        """``mix(proj, tail, state, valid) -> (y, z, tail, state)`` and the
        gated norm under ``norm_w``, over the rows' slots of a layer's
        ``tails`` [slots + 1, ...] and ``states``; returns ``(y [B, T,
        d_ssm], tails, states)``.

        A decode step (T = 1) goes **by slots, in place**: every slot of
        the pool takes one step, fed by the row that names it; a slot that
        no row names gets a position that is not there (dt = 0), which
        leaves it as it was. The state is then read once and written once
        where it lies, and nothing gathers or scatters it: XLA's gather and
        scatter of [rows, heads, d_head, d_state] float32 copy the whole
        pool and the rows' state twice more (PERF.md, section 5). A chunk
        (T > 1) takes each row's slot out and puts it back, a dynamic
        slice a row: the scheduler prefills one sequence at a time."""
        B, T = valid.shape
        n_slots = states.shape[0]
        if T == 1:
            named = slots[None, :] == jnp.arange(n_slots,
                                                 dtype=slots.dtype)[:, None]
            fed, row = jnp.any(named, axis=1), jnp.argmax(named, axis=1)
            starts = first[row] & fed
            y, z, new_tails, states = mix(
                proj[row],
                jnp.where(starts[:, None, None], 0, tails),
                jnp.where(starts[:, None, None, None], 0.0, states),
                valid[row] & fed[:, None])
            return (_gated_norm(c, y, z, norm_w)[slots],
                    new_tails.astype(tails.dtype), states)
        ys = []
        for b in range(B):
            take = lambda pool: jax.lax.dynamic_index_in_dim(  # noqa: E731
                pool, slots[b], axis=0, keepdims=True)
            y, z, tail, state = mix(
                proj[b:b + 1], jnp.where(first[b], 0, take(tails)),
                jnp.where(first[b], 0.0, take(states)), valid[b:b + 1])
            ys.append(_gated_norm(c, y, z, norm_w))
            tails = jax.lax.dynamic_update_index_in_dim(
                tails, tail.astype(tails.dtype), slots[b], axis=0)
            states = jax.lax.dynamic_update_index_in_dim(
                states, state, slots[b], axis=0)
        return jnp.concatenate(ys, axis=0), tails, states

    def forward_chunk(params, tokens, q_positions, cache, block_tables,
                      kv_lens, slots):
        B, T = tokens.shape
        cache = {name: list(leaves) for name, leaves in cache.items()}
        bs = cache["k"][0].shape[1]
        valid = q_positions < kv_lens[:, None]
        width = block_tables.shape[1]
        page_idx = jnp.take_along_axis(
            block_tables, jnp.clip(q_positions // bs, 0, width - 1), axis=1)
        page_idx = jnp.where(valid, page_idx, 0)
        slot_in_page = q_positions % bs
        first = q_positions[:, 0] == 0  # the sequence starts in this chunk
        with jax.named_scope("embed"):
            x = params["model.embed.weight"][tokens]
            x = x * jnp.asarray(c.embedding_multiplier, x.dtype)
        mup = c.mup_vector().astype(x.dtype)
        for i in range(c.layers_held):
            at = f"model.layers.{i}."
            p = lambda name: params[at + name]  # noqa: E731
            with jax.named_scope("self_attn"):
                u = rms(x, p("input_norm.weight"))
                proj = (u * jnp.asarray(c.ssm_in_multiplier, u.dtype)) \
                    @ p("mamba.in_proj.weight") * mup
                with jax.named_scope("ssm"):
                    y, cache["conv"][i], cache["ssm"][i] = through_slots(
                        cache["conv"][i], cache["ssm"][i], proj, valid,
                        first, slots, lambda proj, tail, state, valid: _ssm(
                            c, proj, p("mamba.conv_weight"),
                            p("mamba.conv_bias"), p("mamba.A_log"),
                            p("mamba.dt_bias"), p("mamba.D"), tail, state,
                            valid), p("mamba.norm.weight"))
                m = y @ p("mamba.out_proj.weight") \
                    * jnp.asarray(c.ssm_out_multiplier, y.dtype)
                ua = u * jnp.asarray(c.attention_in_multiplier, u.dtype)
                q, k, v = _qkv(c, ua @ p("attn.q_proj.weight"),
                               ua @ p("attn.k_proj.weight"),
                               ua @ p("attn.v_proj.weight"), q_positions)
                cache["k"][i] = cache["k"][i].at[page_idx, slot_in_page].set(
                    k.reshape(B, T, hkv * hd).astype(store))
                cache["v"][i] = cache["v"][i].at[page_idx, slot_in_page].set(
                    v.reshape(B, T, hkv * hd).astype(store))
                with jax.named_scope("attention"):
                    o = paged_attention(q, cache["k"][i], cache["v"][i],
                                        block_tables, q_positions, kv_lens)
                a = o.reshape(B, T, hq * hd) @ p("attn.o_proj.weight") \
                    * jnp.asarray(c.attention_out_multiplier, o.dtype)
                x = x + m + a
            with jax.named_scope("mlp"):
                g = rms(x, p("ffn_norm.weight"))
                gate = g @ p("ffn.gate_proj.weight") \
                    * jnp.asarray(c.mlp_multipliers[0], g.dtype)
                f = (jax.nn.silu(gate) * (g @ p("ffn.up_proj.weight"))) \
                    @ p("ffn.down_proj.weight")
                x = x + f * jnp.asarray(c.mlp_multipliers[1], f.dtype)
        with jax.named_scope("head_loss"):
            x = rms(x, params["model.norm.weight"])
            logits = x @ params["lm_head.weight"] \
                * jnp.asarray(c.lm_head_multiplier, x.dtype)
        return logits, {name: tuple(leaves) for name, leaves in cache.items()}

    return forward_chunk


def falcon_h1_tiny(**kw):
    """Every part of the block at a width a CPU test can afford: 4 query
    heads over 2 key heads, 4 state-space heads in 2 groups, 3 layers."""
    base = dict(
        vocab_size=256, hidden_size=64, num_hidden_layers=3,
        intermediate_size=128, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16,
        mamba_n_groups=2, mamba_d_state=16, mamba_chunk_size=8,
        max_position_embeddings=512)
    base.update(kw)
    return FalconH1Config(**base)
