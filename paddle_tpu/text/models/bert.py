"""BERT / ERNIE family — encoder LMs (driver configs #3 BERT-base fleet DP,
#5 ERNIE-3.0 1.5B pp+tp). API parity with the reference ecosystem's
BERT/ERNIE implementations over paddle.nn (nn/layer/transformer.py
TransformerEncoder usage pattern); TPU-first internals shared with GPT
(text/models/gpt.py): fused QKV in one MXU matmul, flash/blockwise
attention, tp_spec annotations so the fleet engine shards over 'mp'.
ERNIE (this snapshot's architecture) = BERT encoder with its own configs,
so ``ErnieModel``/``ernie_3_0_*`` are config variants of the same stack.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import nn
from paddle_tpu.core.tensor import Tensor, apply_op
from paddle_tpu.nn import functional as F
from paddle_tpu.nn import initializer as I
from paddle_tpu.ops.attention import dot_product_attention

__all__ = [
    "BertConfig", "BertModel", "BertForPretraining",
    "BertForSequenceClassification", "bert_base", "bert_large", "bert_tiny",
    "ErnieModel", "ernie_3_0_medium", "ernie_1_5b",
]


@dataclass
class BertConfig:
    vocab_size: int = 30528  # padded to a multiple of 128 for the MXU
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    hidden_dropout: float = 0.1
    attention_dropout: float = 0.1
    initializer_range: float = 0.02
    layer_norm_epsilon: float = 1e-12
    use_flash_attention: bool = True


class BertSelfAttention(nn.Layer):
    def __init__(self, config: BertConfig):
        super().__init__()
        h = config.hidden_size
        self.num_heads = config.num_heads
        self.head_dim = h // config.num_heads
        std = config.initializer_range
        attr = nn.ParamAttr(initializer=I.Normal(0.0, std))
        # fused QKV: one [h, 3h] matmul on the MXU
        self.qkv = nn.Linear(h, 3 * h, weight_attr=attr)
        self.proj = nn.Linear(h, h, weight_attr=attr)
        # Megatron column/row split over 'mp'
        self.qkv.weight.tp_spec = (None, "mp")
        self.qkv.bias.tp_spec = ("mp",)
        self.proj.weight.tp_spec = ("mp", None)
        self.dropout = nn.Dropout(config.attention_dropout)
        self.use_flash = config.use_flash_attention

    def forward(self, x, attn_bias=None):
        b, l, h = x.shape
        qkv = self.qkv(x)

        def attend(qkv_raw, bias):
            # [b, l, heads, d] is the layout the projection gives and the
            # attention einsums contract: no [b, h, l, d] transpose copies
            q, k, v = (t.reshape(b, l, self.num_heads, self.head_dim)
                       for t in jnp.split(qkv_raw, 3, axis=-1))
            o = dot_product_attention(q, k, v, causal=False, bias=bias,
                                      use_flash=self.use_flash,
                                      layout="blhd")
            return o.reshape(b, l, h)

        if attn_bias is not None:
            o = apply_op(attend, qkv, attn_bias)
        else:
            o = apply_op(lambda r: attend(r, None), qkv)
        return self.dropout(self.proj(o))


class BertLayer(nn.Layer):
    """Post-LN encoder block (original BERT residual structure)."""

    def __init__(self, config: BertConfig):
        super().__init__()
        h = config.hidden_size
        std = config.initializer_range
        attr = nn.ParamAttr(initializer=I.Normal(0.0, std))
        self.attn = BertSelfAttention(config)
        self.ln1 = nn.LayerNorm(h, epsilon=config.layer_norm_epsilon)
        self.fc1 = nn.Linear(h, config.intermediate_size, weight_attr=attr)
        self.fc2 = nn.Linear(config.intermediate_size, h, weight_attr=attr)
        self.fc1.weight.tp_spec = (None, "mp")
        self.fc1.bias.tp_spec = ("mp",)
        self.fc2.weight.tp_spec = ("mp", None)
        self.ln2 = nn.LayerNorm(h, epsilon=config.layer_norm_epsilon)
        self.dropout = nn.Dropout(config.hidden_dropout)

    def forward(self, x, attn_bias=None):
        with jax.named_scope("self_attn"):
            x = self.ln1(x + self.attn(x, attn_bias))
        with jax.named_scope("mlp"):
            y = self.fc2(F.gelu(self.fc1(x), approximate=True))
            return self.ln2(x + self.dropout(y))


class BertEmbeddings(nn.Layer):
    def __init__(self, config: BertConfig):
        super().__init__()
        std = config.initializer_range
        attr = nn.ParamAttr(initializer=I.Normal(0.0, std))
        self.word = nn.Embedding(config.vocab_size, config.hidden_size,
                                 weight_attr=attr)
        self.word.weight.tp_spec = ("mp", None)  # vocab-parallel rows
        self.position = nn.Embedding(config.max_position_embeddings,
                                     config.hidden_size, weight_attr=attr)
        self.token_type = nn.Embedding(config.type_vocab_size,
                                       config.hidden_size, weight_attr=attr)
        self.ln = nn.LayerNorm(config.hidden_size,
                               epsilon=config.layer_norm_epsilon)
        self.dropout = nn.Dropout(config.hidden_dropout)

    def forward(self, input_ids, token_type_ids=None):
        from paddle_tpu.tensor import arange, zeros_like

        b, l = input_ids.shape
        with jax.named_scope("embed"):
            pos = arange(l, dtype="int64")
            if token_type_ids is None:
                token_type_ids = zeros_like(input_ids)
            x = self.word(input_ids) + self.position(pos) + \
                self.token_type(token_type_ids)
            return self.dropout(self.ln(x))


class BertModel(nn.Layer):
    """Reference API shape: returns (sequence_output, pooled_output)."""

    def __init__(self, config: BertConfig):
        super().__init__()
        self.config = config
        self.embeddings = BertEmbeddings(config)
        self.encoder = nn.LayerList(
            [BertLayer(config) for _ in range(config.num_layers)])
        self.pooler = nn.Linear(config.hidden_size, config.hidden_size)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        attn_bias = None
        if attention_mask is not None:
            # [b, l] 1/0 mask → additive bias broadcastable to [b, h, lq, lk]
            attn_bias = apply_op(
                lambda m: (1.0 - m.astype(jnp.float32))[:, None, None, :] * -1e9,
                attention_mask,
            )
        x = self.embeddings(input_ids, token_type_ids)
        for layer in self.encoder:
            x = layer(x, attn_bias)
        pooled = F.tanh(self.pooler(x[:, 0]))
        return x, pooled


class BertForPretraining(nn.Layer):
    """MLM + NSP heads (BertPretrainingCriterion parity)."""

    def __init__(self, config: BertConfig):
        super().__init__()
        self.bert = BertModel(config)
        self.config = config
        h = config.hidden_size
        self.mlm_transform = nn.Linear(h, h)
        self.mlm_ln = nn.LayerNorm(h, epsilon=config.layer_norm_epsilon)
        self.nsp = nn.Linear(h, 2)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        seq, pooled = self.bert(input_ids, token_type_ids, attention_mask)
        with jax.named_scope("head_loss"):
            x = self.mlm_ln(F.gelu(self.mlm_transform(seq), approximate=True))
            # decoder tied to word embeddings
            logits = F.linear(x, apply_op(
                lambda w: w.T, self.bert.embeddings.word.weight))
            nsp_logits = self.nsp(pooled)
        return logits, nsp_logits

    def loss_fn(self, outputs, mlm_labels, nsp_labels=None):
        """mlm_labels: [b, l] with -100 = unmasked (ignored)."""
        logits, nsp_logits = outputs

        def masked_ce(lg, lab):
            v = lg.shape[-1]
            lg2 = lg.reshape(-1, v)
            lab2 = lab.reshape(-1)
            valid = lab2 >= 0
            lab_safe = jnp.where(valid, lab2, 0)
            logp = jax.nn.log_softmax(lg2, axis=-1)
            picked = jnp.take_along_axis(logp, lab_safe[:, None], axis=-1)[:, 0]
            return -(picked * valid).sum() / jnp.maximum(valid.sum(), 1)

        with jax.named_scope("head_loss"):
            loss = apply_op(masked_ce, logits, mlm_labels)
            if nsp_labels is not None:
                loss = loss + F.cross_entropy(nsp_logits, nsp_labels)
        return loss


class BertForSequenceClassification(nn.Layer):
    def __init__(self, config: BertConfig, num_classes: int = 2):
        super().__init__()
        self.bert = BertModel(config)
        self.dropout = nn.Dropout(config.hidden_dropout)
        self.classifier = nn.Linear(config.hidden_size, num_classes)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        _, pooled = self.bert(input_ids, token_type_ids, attention_mask)
        return self.classifier(self.dropout(pooled))


def bert_tiny(**kw):
    return BertConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                      num_heads=2, intermediate_size=512,
                      max_position_embeddings=128, hidden_dropout=0.0,
                      attention_dropout=0.0, **kw)


def bert_base(**kw):
    """BERT-base (driver config #3: fleet DP pretrain)."""
    return BertConfig(**kw)


def bert_large(**kw):
    return BertConfig(hidden_size=1024, num_layers=24, num_heads=16,
                      intermediate_size=4096, **kw)


# --- ERNIE: same encoder architecture, its own configs -----------------------
class ErnieModel(BertModel):
    """ERNIE (this reference snapshot's ERNIE is a BERT-architecture encoder
    with knowledge-masking pretraining; the model graph is identical)."""


def ernie_3_0_medium(**kw):
    return BertConfig(vocab_size=40064, hidden_size=768, num_layers=6,
                      num_heads=12, intermediate_size=3072, **kw)


def ernie_1_5b(**kw):
    """ERNIE-3.0 1.5B-class config (driver config #5: pp+tp on v5p-32)."""
    return BertConfig(vocab_size=40064, hidden_size=2048, num_layers=24,
                      num_heads=16, intermediate_size=8192,
                      max_position_embeddings=2048, **kw)
