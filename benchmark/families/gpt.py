"""The GPT family: ``text.models.gpt.GPTForCausalLM`` under
``fleet.ParallelTrainStep``, built as ``bench.build_trainer`` builds it
(bf16 compute, bf16 resident parameters, f32 masters and moments), with
the harness's weights in place of the program's own initial ones.
"""
from __future__ import annotations

import numpy as np

from benchmark.reference import gpt2 as reference  # noqa: F401 (the entry)

# reference leaf -> the program's parameter name
NAMES = {
    "wte": "gpt.wte.weight", "wpe": "gpt.wpe.weight",
    "ln_1_w": "gpt.h.{i}.ln_1.weight", "ln_1_b": "gpt.h.{i}.ln_1.bias",
    "qkv_w": "gpt.h.{i}.attn.qkv.weight", "qkv_b": "gpt.h.{i}.attn.qkv.bias",
    "attn_proj_w": "gpt.h.{i}.attn.proj.weight",
    "attn_proj_b": "gpt.h.{i}.attn.proj.bias",
    "ln_2_w": "gpt.h.{i}.ln_2.weight", "ln_2_b": "gpt.h.{i}.ln_2.bias",
    "fc_w": "gpt.h.{i}.mlp.fc.weight", "fc_b": "gpt.h.{i}.mlp.fc.bias",
    "mlp_proj_w": "gpt.h.{i}.mlp.proj.weight",
    "mlp_proj_b": "gpt.h.{i}.mlp.proj.bias",
    "ln_f_w": "gpt.ln_f.weight", "ln_f_b": "gpt.ln_f.bias",
}


def build(config: dict, cell: dict, mesh, named_weights: dict):
    """The timed object. Call it through ``call``."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.engine import ParallelTrainStep
    from paddle_tpu.jit.functionalize import set_params
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

    model = GPTForCausalLM(GPTConfig(
        vocab_size=config["assumed"]["vocab_rows_held"],
        hidden_size=config["n_embd"], num_layers=config["n_layer"],
        num_heads=config["n_head"],
        max_position_embeddings=config["n_positions"],
        hidden_dropout=config["resid_pdrop"],
        attention_dropout=config["attn_pdrop"],
        initializer_range=config["initializer_range"],
        layer_norm_epsilon=config["layer_norm_epsilon"]))
    set_params(model, named_weights)
    o = cell["optimizer"]
    opt = paddle.optimizer.Adam(
        learning_rate=o["lr"], beta1=o["beta1"], beta2=o["beta2"],
        epsilon=o["epsilon"], parameters=model.parameters(),
        multi_precision=True)
    # labels ride as a forward input: the model computes the loss itself
    return ParallelTrainStep(
        model, loss_fn=lambda out, lbl: out, optimizer=opt, mesh=mesh,
        zero_stage=0, recompute=False,
        compute_dtype=jnp.dtype(cell["compute_dtype"]))


def call(step, batch: dict):
    return step((batch["ids"], batch["labels"]), (batch["labels"],))


def make_batches(config: dict, traffic: dict, seed: int, n: int) -> list:
    """``n`` different batches of int32 ids from the published vocabulary;
    the label of a position is the next id (the last wraps to the first)."""
    rng = np.random.default_rng([int(seed), 0x67707432])
    ids = rng.integers(0, config["vocab_size"],
                       (n, traffic["batch"], traffic["seq_len"]),
                       dtype=np.int32)
    return [{"ids": x, "labels": np.roll(x, -1, axis=1)} for x in ids]


def tokens_per_step(traffic: dict) -> int:
    return traffic["batch"] * traffic["seq_len"]


def flops_per_token(config: dict, traffic: dict) -> float:
    """Forward and backward, nothing recomputed: 6 a parameter of the
    per-token matmuls, 6 h a row of the head (padded rows too: the chip
    multiplies them), and causal attention's half score square."""
    h, layers = config["n_embd"], config["n_layer"]
    inner = config.get("n_inner") or 4 * h
    n_matmul = layers * (4 * h * h + 2 * h * inner)
    head = h * config["assumed"]["vocab_rows_held"]
    return 6.0 * n_matmul + 6.0 * head + 6.0 * layers * traffic["seq_len"] * h
