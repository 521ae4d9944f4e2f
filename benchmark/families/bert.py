"""The BERT family: ``text.models.bert.BertForPretraining`` (masked LM over
every position plus next-sentence) under ``fleet.ParallelTrainStep``, bf16
compute with f32 masters, AdamW as ``bench_all.bench_bert_dp`` builds it,
with the harness's weights in place of the program's own initial ones.
"""
from __future__ import annotations

import numpy as np

from benchmark.reference import bert as reference  # noqa: F401 (the entry)

_L = "bert.encoder.{i}."
NAMES = {
    "word": "bert.embeddings.word.weight",
    "position": "bert.embeddings.position.weight",
    "token_type": "bert.embeddings.token_type.weight",
    "emb_ln_w": "bert.embeddings.ln.weight",
    "emb_ln_b": "bert.embeddings.ln.bias",
    "qkv_w": _L + "attn.qkv.weight", "qkv_b": _L + "attn.qkv.bias",
    "attn_out_w": _L + "attn.proj.weight", "attn_out_b": _L + "attn.proj.bias",
    "ln1_w": _L + "ln1.weight", "ln1_b": _L + "ln1.bias",
    "fc1_w": _L + "fc1.weight", "fc1_b": _L + "fc1.bias",
    "fc2_w": _L + "fc2.weight", "fc2_b": _L + "fc2.bias",
    "ln2_w": _L + "ln2.weight", "ln2_b": _L + "ln2.bias",
    "pooler_w": "bert.pooler.weight", "pooler_b": "bert.pooler.bias",
    "mlm_w": "mlm_transform.weight", "mlm_b": "mlm_transform.bias",
    "mlm_ln_w": "mlm_ln.weight", "mlm_ln_b": "mlm_ln.bias",
    "nsp_w": "nsp.weight", "nsp_b": "nsp.bias",
}
MASK_ID = 103  # [MASK] in the published uncased vocabulary


def build(config: dict, cell: dict, mesh, named_weights: dict):
    """The timed object. Call it through ``call``."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.engine import ParallelTrainStep
    from paddle_tpu.jit.functionalize import set_params
    from paddle_tpu.text.models.bert import BertConfig, BertForPretraining

    model = BertForPretraining(BertConfig(
        vocab_size=config["assumed"]["vocab_rows_held"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        intermediate_size=config["intermediate_size"],
        max_position_embeddings=config["max_position_embeddings"],
        type_vocab_size=config["type_vocab_size"],
        hidden_dropout=config["hidden_dropout_prob"],
        attention_dropout=config["attention_probs_dropout_prob"],
        initializer_range=config["initializer_range"],
        layer_norm_epsilon=config["layer_norm_eps"]))
    set_params(model, named_weights)
    o = cell["optimizer"]
    opt = paddle.optimizer.AdamW(
        learning_rate=o["lr"], beta1=o["beta1"], beta2=o["beta2"],
        epsilon=o["epsilon"], weight_decay=o["weight_decay"],
        parameters=model.parameters(), multi_precision=True)
    return ParallelTrainStep(
        model, loss_fn=model.loss_fn, optimizer=opt, mesh=mesh,
        zero_stage=0, recompute=False,
        compute_dtype=jnp.dtype(cell["compute_dtype"]))


def call(step, batch: dict):
    return step(
        (batch["ids"], batch["token_type_ids"], batch["attention_mask"]),
        (batch["mlm_labels"], batch["nsp_labels"]))


def make_batches(config: dict, traffic: dict, seed: int, n: int) -> list:
    """``n`` different batches of full-length sequences. In every row the
    same number of positions is masked (``mask_share`` of the length, so
    every seed gives the same work): 80% of them become [MASK], 10% a
    random id, 10% stay, as the paper has it. Segment 0 is the first half
    of a row and segment 1 the second. Every batch holds the same number
    of next-sentence rows (``nsp_share`` of the batch), placed by the seed:
    a share drawn from the seed made some seeds ill-conditioned (PERF.md
    section 2, "BERT's next-sentence labels")."""
    rng = np.random.default_rng([int(seed), 0x62657274])
    b, l = traffic["batch"], traffic["seq_len"]
    k = round(traffic["mask_share"] * l)
    ids = rng.integers(0, config["vocab_size"], (n, b, l), dtype=np.int32)
    picks = np.argsort(rng.random((n, b, l)), axis=-1)[..., :k]
    labels = np.full((n, b, l), -100, np.int32)
    np.put_along_axis(labels, picks, np.take_along_axis(ids, picks, -1), -1)
    how = rng.random((n, b, k))
    swapped = np.where(how < 0.8, MASK_ID, np.where(
        how < 0.9, rng.integers(0, config["vocab_size"], (n, b, k)),
        np.take_along_axis(ids, picks, -1))).astype(np.int32)
    np.put_along_axis(ids, picks, swapped, -1)
    segments = np.broadcast_to((np.arange(l) >= l // 2).astype(np.int32),
                               (b, l)).copy()
    is_next = round(traffic["nsp_share"] * b)
    nsp = (np.argsort(rng.random((n, b)), axis=-1) < is_next).astype(np.int32)
    return [{"ids": ids[i], "token_type_ids": segments,
             "attention_mask": np.ones((b, l), np.int32),
             "mlm_labels": labels[i], "nsp_labels": nsp[i]}
            for i in range(n)]


def tokens_per_step(traffic: dict) -> int:
    return traffic["batch"] * traffic["seq_len"]


def flops_per_token(config: dict, traffic: dict) -> float:
    """Forward and backward, nothing recomputed: 6 a parameter of the
    per-token matmuls (the blocks and the masked-LM head's dense layer; the
    pooler and the next-sentence head see one token a row and are left
    out), 6 h a row of the head at every position, as the program computes
    it, and bidirectional attention's whole score square."""
    h, layers = config["hidden_size"], config["num_hidden_layers"]
    n_matmul = layers * (4 * h * h + 2 * h * config["intermediate_size"]) \
        + h * h
    head = h * config["assumed"]["vocab_rows_held"]
    return (6.0 * n_matmul + 6.0 * head
            + 12.0 * layers * traffic["seq_len"] * h)
