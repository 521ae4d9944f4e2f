"""GPT family — the flagship LM (driver configs #4/#5: GPT-2 345M sharding,
ERNIE-style pp+tp). API parity with the reference ecosystem's GPT
implementations built on fleet.meta_parallel (mp_layers.py usage pattern);
TPU-first internals: fused QKV projections (one MXU matmul), Pallas/blockwise
flash attention, params carry tp_spec so the fleet engine shards them over
the 'mp'/'sp' mesh axes, and the uniform block stack exposes a functional
form the pipeline engine can scan over stages.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import nn
from paddle_tpu.core.tensor import Tensor, apply_op
from paddle_tpu.nn import functional as F
from paddle_tpu.nn import initializer as I
from paddle_tpu.ops.attention import dot_product_attention

__all__ = ["GPTConfig", "GPT", "GPTForCausalLM", "gpt2_small", "gpt2_medium",
           "gpt2_tiny", "gpt_decode_fns"]


@dataclass
class GPTConfig:
    vocab_size: int = 50304  # padded to a multiple of 128 for the MXU
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_position_embeddings: int = 1024
    intermediate_size: int = 0  # 0 -> 4*hidden
    hidden_dropout: float = 0.1
    attention_dropout: float = 0.1
    initializer_range: float = 0.02
    layer_norm_epsilon: float = 1e-5
    use_flash_attention: bool = True
    # manual LayerNorm VJP scoped to THIS model's forward: +2.2% end-to-end
    # on GPT-2 345M on v5e (it regresses BERT-base 24%, so it is a
    # per-model config rather than a process-wide env default)
    manual_layer_norm: bool = True
    # joint lm_head+CE backward (loss.fused_linear_hard_ce): hands each of
    # the dW/dh dots its own fusable dlogits expression hoping the [N, V]
    # dlogits never materializes. MEASURED OFF: the v5e emitter materializes
    # both expressions instead of operand-fusing them (56.1k vs 56.4k tok/s
    # on the 345M headline), so the default stays on the split
    # linear+_hard_ce path; the knob is kept for rigs whose emitter does
    # operand-fuse dot inputs
    fused_head_ce: bool = False

    def __post_init__(self):
        if self.intermediate_size == 0:
            self.intermediate_size = 4 * self.hidden_size


class GPTAttention(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        h = config.hidden_size
        self.num_heads = config.num_heads
        self.head_dim = h // config.num_heads
        std = config.initializer_range
        # fused qkv: one [h, 3h] matmul feeds the MXU better than 3 separate
        self.qkv = nn.Linear(h, 3 * h, weight_attr=nn.ParamAttr(
            initializer=I.Normal(0.0, std)))
        self.proj = nn.Linear(h, h, weight_attr=nn.ParamAttr(
            initializer=I.Normal(0.0, std / math.sqrt(2 * config.num_layers))))
        # TP: qkv column-parallel (heads split), proj row-parallel
        self.qkv.weight.tp_spec = (None, "mp")
        self.qkv.bias.tp_spec = ("mp",)
        self.proj.weight.tp_spec = ("mp", None)
        self.attn_dropout_p = config.attention_dropout
        self.dropout = nn.Dropout(config.hidden_dropout)
        self.use_flash = config.use_flash_attention

    def forward(self, x, attn_mask=None):
        nh, hd = self.num_heads, self.head_dim
        use_flash = self.use_flash

        def qkv_attend(xr, w, bias):
            from paddle_tpu.amp.auto_cast import maybe_cast_inputs

            # 'linear': the projection must honor the same AMP white/black
            # list entry as every other nn.Linear in the model
            xr, w = maybe_cast_inputs("linear", xr, w)
            b, l, h = xr.shape
            # three separate projections from slices of the fused weight:
            # each of q/k/v is then BORN in the layout its attention einsum
            # wants — a fused [b,l,3h] output forces XLA to materialize
            # relayout copies at the split (measured 6 × 16MB/layer)
            outs = []
            for i in range(3):
                wi = jax.lax.slice_in_dim(w, i * h, (i + 1) * h, axis=1)
                bi = jax.lax.slice_in_dim(bias, i * h, (i + 1) * h, axis=0)
                o = xr @ wi
                outs.append((o + bi.astype(o.dtype)).reshape(b, l, nh, hd))
            q, k, v = outs
            o = dot_product_attention(q, k, v, causal=True,
                                      use_flash=use_flash, layout="blhd")
            return o.reshape(b, l, nh * hd)

        out = apply_op(qkv_attend, x, self.qkv.weight, self.qkv.bias)
        return self.dropout(self.proj(out))


class GPTMLP(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        std = config.initializer_range
        self.fc = nn.Linear(config.hidden_size, config.intermediate_size,
                            weight_attr=nn.ParamAttr(initializer=I.Normal(0.0, std)))
        self.proj = nn.Linear(config.intermediate_size, config.hidden_size,
                              weight_attr=nn.ParamAttr(
                                  initializer=I.Normal(
                                      0.0, std / math.sqrt(2 * config.num_layers))))
        self.fc.weight.tp_spec = (None, "mp")
        self.fc.bias.tp_spec = ("mp",)
        self.proj.weight.tp_spec = ("mp", None)
        self.dropout = nn.Dropout(config.hidden_dropout)

    def forward(self, x):
        return self.dropout(self.proj(F.gelu(self.fc(x), approximate=True)))


class GPTBlock(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.ln_1 = nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_epsilon)
        self.attn = GPTAttention(config)
        self.ln_2 = nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_epsilon)
        self.mlp = GPTMLP(config)

    def forward(self, x, attn_mask=None):
        with jax.named_scope("self_attn"):
            x = x + self.attn(self.ln_1(x), attn_mask)
        with jax.named_scope("mlp"):
            x = x + self.mlp(self.ln_2(x))
        return x


class GPT(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        std = config.initializer_range
        self.wte = nn.Embedding(config.vocab_size, config.hidden_size,
                                weight_attr=nn.ParamAttr(initializer=I.Normal(0.0, std)))
        self.wpe = nn.Embedding(config.max_position_embeddings, config.hidden_size,
                                weight_attr=nn.ParamAttr(initializer=I.Normal(0.0, std)))
        # vocab-parallel embedding rows over mp
        self.wte.weight.tp_spec = ("mp", None)
        self.drop = nn.Dropout(config.hidden_dropout)
        self.h = nn.LayerList([GPTBlock(config) for _ in range(config.num_layers)])
        self.ln_f = nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_epsilon)

    def forward(self, input_ids, attn_mask=None):
        b, l = input_ids.shape
        from paddle_tpu.nn.functional.norm import manual_ln_scope
        from paddle_tpu.tensor import arange

        with manual_ln_scope(self.config.manual_layer_norm):
            with jax.named_scope("embed"):
                pos = arange(l, dtype="int64")
                x = self.wte(input_ids) + self.wpe(pos)
                x = self.drop(x)
            for block in self.h:
                x = block(x, attn_mask)
            # the final LayerNorm belongs to the head: its backward is
            # fused with the head's dh matmul
            with jax.named_scope("head_loss"):
                return self.ln_f(x)


class GPTForCausalLM(nn.Layer):
    """LM head tied to wte (standard GPT-2 weight tying)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.gpt = GPT(config)

    def forward(self, input_ids, labels=None):
        h = self.gpt(input_ids)
        with jax.named_scope("head_loss"):
            return self._head(h, labels)

    def _head(self, h, labels):
        if labels is not None and self.config.fused_head_ce:
            from paddle_tpu.nn.functional.loss import fused_linear_hard_ce

            def head_ce(hr, w, lbl):
                from paddle_tpu.amp.auto_cast import maybe_cast_inputs

                hr2 = hr.reshape(-1, hr.shape[-1])
                hr2, wc = maybe_cast_inputs("linear", hr2, w)
                loss, mask = fused_linear_hard_ce(
                    hr2, wc.T, lbl.reshape(-1).astype(jnp.int32))
                return (jnp.sum(loss)
                        / jnp.maximum(jnp.sum(mask), 1.0)).astype(loss.dtype)

            return apply_op(head_ce, h, self.gpt.wte.weight,
                            labels.detach() if isinstance(labels, Tensor)
                            else labels)
        logits = F.linear(h, _transposed(self.gpt.wte.weight))
        if labels is not None:
            loss = F.cross_entropy(
                logits.reshape([-1, self.config.vocab_size]),
                labels.reshape([-1]),
            )
            return loss
        return logits

    def decode_spec(self, kv_dtype: str = "float32") -> dict:
        """What ``inference.serving.TokenServingEngine`` asks a model: the
        cached forward and the cache it runs over (here K and V pages of as
        many heads as the queries have, no recurrent state)."""
        c = self.config
        # float pages lie a layer to an array with the heads flat, so a
        # step touches the layer it is in and never the whole pool; int8
        # pages carry a scale a token-head and keep the stacked arrays
        # (one layout for all is ROADMAP D10, a simplicity issue's)
        layout = "stacked" if kv_dtype == "int8" else "per_layer"
        return {"forward_chunk": gpt_decode_fns(c, kv_dtype),
                "num_layers": c.num_layers, "num_heads": c.num_heads,
                "num_kv_heads": c.num_heads,
                "head_dim": c.hidden_size // c.num_heads,
                "max_positions": c.max_position_embeddings,
                "kv_layout": layout, "state": None}

    def loss_fn(self, logits, labels):
        with jax.named_scope("head_loss"):
            return F.cross_entropy(
                logits.reshape([-1, self.config.vocab_size]),
                labels.reshape([-1]))


def _transposed(w: Tensor) -> Tensor:
    return apply_op(lambda a: a.T, w)


# ---------------------------------------------------------------------------
# Pure functional forms for the pipeline / sp engines
# ---------------------------------------------------------------------------
def gpt_functional_fns(config: GPTConfig, sp_axis=None, mp_axis=None):
    """Pure-jnp (embed_fn, block_fn, head_loss_fn) matching the Layer math
    (dropout-free; use hidden_dropout=0 for exact parity). Used by
    fleet.pipeline_engine (pp over stacked blocks) and the sp ring-attention
    path (sp_axis set → attention rotates K/V around the 'sp' mesh axis).

    ``mp_axis`` set → Megatron-style tensor parallelism INSIDE shard_map
    (the 4D pp×mp×sharding×dp composition the reference builds in
    sharding_optimizer.py:120-138 + tensor_parallel_optimizer.py): the fns
    expect the mp param layout of ``gpt_split_params(..., mp=True)`` —
    head-split qkv [3, h, h/mp], row-parallel proj [h/mp, h], column/row
    mlp, vocab-parallel wte [V/mp, h] — and insert the explicit
    psum/pmax collectives (the reference's _mp_allreduce / vocab-parallel
    cross-entropy) that GSPMD would otherwise derive."""
    nh = config.num_heads
    hd = config.hidden_size // nh
    eps = config.layer_norm_epsilon

    def ln(x, w, b):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        return (x - mean) * jax.lax.rsqrt(var + eps) * w + b

    if mp_axis is not None:
        return _gpt_mp_fns(config, ln, sp_axis, mp_axis)

    def embed_fn(p, tokens):
        l = tokens.shape[-1]
        if sp_axis is not None:
            # tokens are sequence-sharded: positions offset by shard index
            off = jax.lax.axis_index(sp_axis) * l
        else:
            off = 0
        pos = off + jnp.arange(l)
        return p["wte"][tokens] + p["wpe"][pos]

    def block_fn(p, h):
        x = ln(h, p["ln_1.weight"], p["ln_1.bias"])
        qkv = x @ p["attn.qkv.weight"] + p["attn.qkv.bias"]
        b, l, _ = qkv.shape
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, l, nh, hd)
        k = k.reshape(b, l, nh, hd)
        v = v.reshape(b, l, nh, hd)
        o = dot_product_attention(q, k, v, causal=True, sp_axis=sp_axis,
                                  use_flash=config.use_flash_attention,
                                  layout="blhd")
        o = o.reshape(b, l, nh * hd)
        h = h + o @ p["attn.proj.weight"] + p["attn.proj.bias"]
        x = ln(h, p["ln_2.weight"], p["ln_2.bias"])
        x = jax.nn.gelu(x @ p["mlp.fc.weight"] + p["mlp.fc.bias"], approximate=True)
        h = h + x @ p["mlp.proj.weight"] + p["mlp.proj.bias"]
        return h

    def head_loss_fn(p, h, labels):
        x = ln(h, p["ln_f.weight"], p["ln_f.bias"])
        logits = x @ p["wte"].T
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
        loss = -picked.mean()
        if sp_axis is not None:
            loss = jax.lax.pmean(loss, sp_axis)
        return loss.astype(jnp.float32)

    return embed_fn, block_fn, head_loss_fn


def gpt_decode_fns(config: GPTConfig, kv_dtype: str = "float32"):
    """Pure KV-cached forward for token-level serving
    (``inference.serving.decode``): ONE function covers chunked prefill,
    single-token decode, and speculative verification — they are all
    "advance the cache by a T-token chunk and return the chunk's logits",
    differing only in T.

    Returns ``forward_chunk(params, tokens, q_positions, pages,
    block_tables, kv_lens, slots=None) -> (logits [B, T, V], pages)`` where
    ``params`` is the flat ``jit.functionalize.get_params`` dict of a
    ``GPTForCausalLM`` and ``pages`` is a ``KVCachePool.pages`` pytree in
    the layout ``GPTForCausalLM.decode_spec`` names for ``kv_dtype``
    (layouts + scratch-page convention documented in
    inference/serving/kv_cache.py). Float pages are ``per_layer``: K and V
    leave the qkv split ``hidden_size`` wide and are stored that way, a
    layer's scatter lands in that layer's own ``[blocks, block, heads *
    head_dim]`` array and ``ops.attention.paged_attention`` reads it as it
    lies, so no step slices, copies or relays out the pool (the stacked
    five-axis array cost 61 ms of every step of GPT-2 345M over 2,304
    blocks: PERF.md, section 6, PR 36). int8 pages quantize on write via
    ``quant.quantize_kv`` to a scale a token-head and stay ``stacked``,
    heads as an axis beside their scales. The tier of ``paged_attention``
    is ``ops.tier_policy.select_paged``'s.

    Numerics match the eval-mode Layer forward (dropout-free, gelu
    approximate, tied lm_head) up to the attention tier's accumulation
    order — the paged-vs-dense parity test pins the tolerance.
    """
    from paddle_tpu.ops.attention import paged_attention

    nh = config.num_heads
    hd = config.hidden_size // nh
    eps = config.layer_norm_epsilon
    nl = config.num_layers
    max_pos = config.max_position_embeddings
    quantized = kv_dtype == "int8"
    if quantized:
        from paddle_tpu.quant import quantize_kv
    store = jnp.int8 if quantized else jnp.dtype(kv_dtype)

    def ln(x, w, b):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        return (x - mean) * jax.lax.rsqrt(var + eps) * w + b

    def forward_chunk(params, tokens, q_positions, pages, block_tables,
                      kv_lens, slots=None):  # no recurrent state: no slots
        B, T = tokens.shape
        if not quantized:  # a layer's array is replaced, not the tuple
            pages = {name: list(leaves) for name, leaves in pages.items()}
        bs = pages["k"][0].shape[1]  # a layer's [blocks, block, ...]
        # scatter targets: token t of row b lands in table slot
        # pos // bs at offset pos % bs; masked-out tokens (padded rows,
        # padded chunk tails — q_position >= kv_len) are redirected to
        # the reserved scratch page 0, so the scatter needs no guard
        valid = q_positions < kv_lens[:, None]
        width = block_tables.shape[1]
        page_idx = jnp.take_along_axis(
            block_tables, jnp.clip(q_positions // bs, 0, width - 1), axis=1)
        page_idx = jnp.where(valid, page_idx, 0)
        slot = q_positions % bs
        pos = jnp.clip(q_positions, 0, max_pos - 1)
        x = params["gpt.wte.weight"][tokens] + params["gpt.wpe.weight"][pos]
        for i in range(nl):
            p = {n: params[f"gpt.h.{i}.{n}"] for n in (
                "ln_1.weight", "ln_1.bias", "attn.qkv.weight",
                "attn.qkv.bias", "attn.proj.weight", "attn.proj.bias",
                "ln_2.weight", "ln_2.bias", "mlp.fc.weight", "mlp.fc.bias",
                "mlp.proj.weight", "mlp.proj.bias")}
            h = ln(x, p["ln_1.weight"], p["ln_1.bias"])
            qkv = h @ p["attn.qkv.weight"] + p["attn.qkv.bias"]
            q3, k3, v3 = jnp.split(qkv, 3, axis=-1)
            q3 = q3.reshape(B, T, nh, hd)
            if quantized:
                k3 = k3.reshape(B, T, nh, hd)
                v3 = v3.reshape(B, T, nh, hd)
                kq, ks = quantize_kv(k3)
                vq, vs = quantize_kv(v3)
                pages["k"] = pages["k"].at[i, page_idx, slot].set(kq)
                pages["v"] = pages["v"].at[i, page_idx, slot].set(vq)
                pages["k_scale"] = \
                    pages["k_scale"].at[i, page_idx, slot].set(ks)
                pages["v_scale"] = \
                    pages["v_scale"].at[i, page_idx, slot].set(vs)
                k_sc, v_sc = pages["k_scale"][i], pages["v_scale"][i]
            else:
                pages["k"][i] = pages["k"][i].at[page_idx, slot].set(
                    k3.astype(store))
                pages["v"][i] = pages["v"][i].at[page_idx, slot].set(
                    v3.astype(store))
                k_sc = v_sc = None
            o = paged_attention(q3, pages["k"][i], pages["v"][i],
                                block_tables, q_positions, kv_lens,
                                k_sc, v_sc)
            x = x + o.reshape(B, T, nh * hd) @ p["attn.proj.weight"] \
                + p["attn.proj.bias"]
            h2 = ln(x, p["ln_2.weight"], p["ln_2.bias"])
            h2 = jax.nn.gelu(h2 @ p["mlp.fc.weight"] + p["mlp.fc.bias"],
                             approximate=True)
            x = x + h2 @ p["mlp.proj.weight"] + p["mlp.proj.bias"]
        x = ln(x, params["gpt.ln_f.weight"], params["gpt.ln_f.bias"])
        logits = x @ params["gpt.wte.weight"].T
        if not quantized:
            pages = {name: tuple(leaves) for name, leaves in pages.items()}
        return logits, pages

    return forward_chunk


def _gpt_mp_fns(config: GPTConfig, ln, sp_axis, mp_axis):
    """Tensor-parallel functional forms (see gpt_functional_fns)."""
    hd = config.hidden_size // config.num_heads
    V = config.vocab_size

    def embed_fn(p, tokens):
        size = jax.lax.psum(1, mp_axis)
        vloc = p["wte"].shape[0]
        off = jax.lax.axis_index(mp_axis) * vloc
        rel = tokens - off
        ok = (rel >= 0) & (rel < vloc)
        emb = p["wte"][jnp.clip(rel, 0, vloc - 1)] * ok[..., None]
        emb = jax.lax.psum(emb, mp_axis)  # vocab-parallel lookup
        l = tokens.shape[-1]
        seq_off = (jax.lax.axis_index(sp_axis) * l) if sp_axis is not None else 0
        return emb + p["wpe"][seq_off + jnp.arange(l)]

    def block_fn(p, h):
        x = ln(h, p["ln_1.weight"], p["ln_1.bias"])
        # column-parallel qkv: head-split [3, h, h/mp] + local bias
        q = x @ p["attn.qkv.w3"][0] + p["attn.qkv.b3"][0]
        k = x @ p["attn.qkv.w3"][1] + p["attn.qkv.b3"][1]
        v = x @ p["attn.qkv.w3"][2] + p["attn.qkv.b3"][2]
        b, l, hl = q.shape
        q = q.reshape(b, l, hl // hd, hd)
        k = k.reshape(b, l, hl // hd, hd)
        v = v.reshape(b, l, hl // hd, hd)
        o = dot_product_attention(q, k, v, causal=True, sp_axis=sp_axis,
                                  use_flash=config.use_flash_attention,
                                  layout="blhd")
        o = o.reshape(b, l, hl)
        # row-parallel out-projection: partial sums → one psum, bias once
        h = h + jax.lax.psum(o @ p["attn.proj.weight"], mp_axis) \
            + p["attn.proj.bias"]
        x = ln(h, p["ln_2.weight"], p["ln_2.bias"])
        x = jax.nn.gelu(x @ p["mlp.fc.weight"] + p["mlp.fc.bias"],
                        approximate=True)
        h = h + jax.lax.psum(x @ p["mlp.proj.weight"], mp_axis) \
            + p["mlp.proj.bias"]
        return h

    def head_loss_fn(p, h, labels):
        x = ln(h, p["ln_f.weight"], p["ln_f.bias"])
        logits = x @ p["wte"].T                       # [b, l, V/mp] local
        vloc = p["wte"].shape[0]
        off = jax.lax.axis_index(mp_axis) * vloc
        # vocab-parallel cross-entropy (reference
        # parallel_cross_entropy): global max via pmax, global sum-exp and
        # picked logit via psum
        m = jax.lax.pmax(jax.lax.stop_gradient(logits.max(axis=-1)), mp_axis)
        se = jax.lax.psum(
            jnp.sum(jnp.exp(logits - m[..., None]), axis=-1), mp_axis)
        lse = jnp.log(se) + m
        rel = labels - off
        ok = (rel >= 0) & (rel < vloc)
        picked = jnp.take_along_axis(
            logits, jnp.clip(rel, 0, vloc - 1)[..., None], axis=-1)[..., 0]
        picked = jax.lax.psum(picked * ok, mp_axis)
        loss = (lse - picked).mean()
        if sp_axis is not None:
            loss = jax.lax.pmean(loss, sp_axis)
        return loss.astype(jnp.float32)

    return embed_fn, block_fn, head_loss_fn


def gpt_split_params(model: "GPTForCausalLM", tied: bool = False,
                     mp: bool = False):
    """Split a GPTForCausalLM's params into (embed, stacked blocks, head)
    pytrees for the pipeline engine. Block params are stacked over layers.

    ``tied=True`` matches the Layer model's weight tying: the head gets NO
    wte copy — pass ``tie_keys=("wte",)`` to PipelineTrainStep, which
    injects the embedding matrix into the head and syncs its first↔last
    gradients (the reference's Megatron-style tied-embedding allreduce).
    ``tied=False`` unties the LM head (its own trainable copy).

    ``mp=True`` reshapes the attention projections into the
    tensor-parallel layout ``_gpt_mp_fns`` expects: the fused qkv weight
    [h, 3h] becomes head-split "attn.qkv.w3" [L, 3, h, h] (so sharding the
    LAST dim over 'mp' splits each of q/k/v by heads, never mixing them),
    and its bias "attn.qkv.b3" [L, 3, h]. Use with
    ``gpt_mp_param_specs`` as the pipeline engine's param specs."""
    from paddle_tpu.jit.functionalize import get_params

    params = get_params(model)
    n_layers = model.config.num_layers
    embed = {"wte": params["gpt.wte.weight"], "wpe": params["gpt.wpe.weight"]}
    keys = sorted(
        {k.split(".", 3)[3] for k in params if k.startswith("gpt.h.0.")}
    )
    blocks = {
        key: jnp.stack([params[f"gpt.h.{i}.{key}"] for i in range(n_layers)])
        for key in keys
    }
    if mp:
        h = model.config.hidden_size
        w = blocks.pop("attn.qkv.weight")          # [L, h, 3h]
        blocks["attn.qkv.w3"] = w.reshape(
            n_layers, h, 3, h).transpose(0, 2, 1, 3)  # [L, 3, h, h]
        b = blocks.pop("attn.qkv.bias")            # [L, 3h]
        blocks["attn.qkv.b3"] = b.reshape(n_layers, 3, h)
    head = {
        "ln_f.weight": params["gpt.ln_f.weight"],
        "ln_f.bias": params["gpt.ln_f.bias"],
    }
    if not tied:
        # copy keeps donation buffers unique
        head["wte"] = jnp.array(params["gpt.wte.weight"])
    return embed, blocks, head


def gpt_mp_param_specs(pp_axis="pp", mp_axis="mp"):
    """(embed, blocks, head) PartitionSpec trees for the mp param layout
    of ``gpt_split_params(mp=True)`` — column-parallel qkv/fc, row-parallel
    projections, vocab-parallel wte (Megatron placement, matching the
    tp_spec annotations the Layer model carries for the GSPMD engine)."""
    from jax.sharding import PartitionSpec as P

    embed = {"wte": P(mp_axis, None), "wpe": P()}
    blocks = {
        "attn.qkv.w3": P(pp_axis, None, None, mp_axis),
        "attn.qkv.b3": P(pp_axis, None, mp_axis),
        "attn.proj.weight": P(pp_axis, mp_axis, None),
        "attn.proj.bias": P(pp_axis, None),
        "mlp.fc.weight": P(pp_axis, None, mp_axis),
        "mlp.fc.bias": P(pp_axis, mp_axis),
        "mlp.proj.weight": P(pp_axis, mp_axis, None),
        "mlp.proj.bias": P(pp_axis, None),
        "ln_1.weight": P(pp_axis, None),
        "ln_1.bias": P(pp_axis, None),
        "ln_2.weight": P(pp_axis, None),
        "ln_2.bias": P(pp_axis, None),
    }
    head = {"ln_f.weight": P(), "ln_f.bias": P()}
    return embed, blocks, head


def gpt2_tiny(**kw):
    return GPTConfig(vocab_size=1024, hidden_size=128, num_layers=4, num_heads=4,
                     max_position_embeddings=256, hidden_dropout=0.0,
                     attention_dropout=0.0, **kw)


def gpt2_small(**kw):
    return GPTConfig(hidden_size=768, num_layers=12, num_heads=12, **kw)


def gpt2_medium(**kw):
    """GPT-2 345M (driver config #4)."""
    return GPTConfig(hidden_size=1024, num_layers=24, num_heads=16, **kw)
