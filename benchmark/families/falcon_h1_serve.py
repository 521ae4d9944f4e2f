"""The serve side of the Falcon-H1 family:
``text.models.falcon_h1.FalconH1ForCausalLM`` with the harness's weights
resident in bfloat16, under ``inference.serving.TokenServingEngine`` as the
cell's file sets it up. Found by ``drivers/serve.py`` as ``<family>_serve``.

Also here, because they belong to the yardstick: the bytes a decode step
has to move, the bytes of the state-space mixers' part of it, and the
operations a served token costs, from shapes alone, whatever implements
them.
"""
from __future__ import annotations

import jax

from benchmark.families.falcon_h1 import names_of
from benchmark.reference import common
from benchmark.reference import falcon_h1 as reference
from benchmark.reference.falcon_h1 import margins as reference_margins  # noqa: F401,E501 (the entry)

KV_BYTES = {"float32": 4, "bfloat16": 2}
STATE_BYTES = 4   # the recurrent state is held in float32
ACT_BYTES = 2     # activations and the convolution's tail in bfloat16

# what this process built last: the configuration's ``assumed`` group
# (``build_model``) and the cell's ``engine`` group (``build_engine``)
_last_assumed = {}
_last_engine = {}


def weights(config: dict, seed: int, dtype: str) -> dict:
    """The seed's weights under the program's names, in the type they are
    served in. Made on the device a layer at a time (the embedding and the
    head each alone), each leaf cast inside the call that makes it: no
    float32 copy of a leaf outlives its cast, and never more than one
    layer's are alive (the whole in float32 would be 16.8 GB)."""
    specs = reference.param_specs(config)
    names = names_of(config)
    key = common.seed_key(seed)
    groups = [["embed"], ["head_w", "final_norm"]] + [
        [f"l{i}_{n}" for n in reference.LAYER_LEAVES]
        for i in range(config["num_hidden_layers"])]
    out = {}
    for group in groups:
        made = jax.jit(lambda k, g=tuple(group): {
            names[n]: common.init_leaf(specs, n, k).astype(dtype)
            for n in g})(key)
        out.update(made)
    return out


def build_model(config: dict, named_weights: dict):
    import paddle_tpu as paddle
    from paddle_tpu.jit.functionalize import set_params
    from paddle_tpu.text.models.falcon_h1 import (FalconH1Config,
                                                  FalconH1ForCausalLM)

    dep = config["deployment"]
    cfg = FalconH1Config(
        vocab_size=dep["vocab_size_published"],
        vocab_rows_held=config["vocab_size"],
        num_hidden_layers=dep["num_hidden_layers_published"],
        layers_held=config["num_hidden_layers"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], rope_theta=config["rope_theta"],
        rms_norm_eps=config["rms_norm_eps"],
        max_position_embeddings=config["max_position_embeddings"],
        mamba_d_ssm=config["mamba_d_ssm"],
        mamba_n_heads=config["mamba_n_heads"],
        mamba_d_head=config["mamba_d_head"],
        mamba_n_groups=config["mamba_n_groups"],
        mamba_d_state=config["mamba_d_state"],
        mamba_d_conv=config["mamba_d_conv"],
        mamba_chunk_size=config["mamba_chunk_size"],
        embedding_multiplier=config["embedding_multiplier"],
        lm_head_multiplier=config["lm_head_multiplier"],
        attention_in_multiplier=config["attention_in_multiplier"],
        attention_out_multiplier=config["attention_out_multiplier"],
        key_multiplier=config["key_multiplier"],
        ssm_in_multiplier=config["ssm_in_multiplier"],
        ssm_out_multiplier=config["ssm_out_multiplier"],
        ssm_multipliers=tuple(config["ssm_multipliers"]),
        mlp_multipliers=tuple(config["mlp_multipliers"]),
        initializer_range=config["assumed"]["initializer_range"])
    # the harness brings every weight: the model draws none of its own (its
    # own float32 initialisation would not fit beside them)
    with paddle.LazyGuard():
        model = FalconH1ForCausalLM(cfg)
    _last_assumed.clear()
    _last_assumed.update(config["assumed"])
    set_params(model, named_weights)
    model.eval()
    return model


def build_engine(model, engine: dict):
    """``TokenServingEngine`` as the cell's ``engine`` group states it: no
    deadline, no speculation, nothing the group does not name but the
    admission cap ``max_seq_len``, which is the configuration's
    (``assumed``: the driver prints the engine's own beside the group, so
    the group cannot hold the key)."""
    from paddle_tpu.inference.serving import (TokenServeConfig,
                                              TokenServingEngine)

    _last_engine.clear()
    _last_engine.update(engine)
    return TokenServingEngine(model, TokenServeConfig(
        capacity=engine["capacity"],
        decode_buckets=tuple(engine["decode_buckets"]),
        max_running=engine["max_running"],
        prefill_chunk=engine["prefill_chunk"],
        kv_blocks=engine["kv_blocks"],
        kv_block_size=engine["kv_block_size"],
        kv_dtype=engine["kv_dtype"],
        max_seq_len=_last_assumed["max_seq_len"],
        default_deadline_s=None, spec_k=0,
        drain_grace_s=engine.get("drain_grace_s", 5.0)))


# -- counts from shapes ------------------------------------------------------

def _layer_matmul_params(z: dict) -> int:
    """Parameters of one layer's per-token matmuls."""
    h = z["h"]
    return (h * z["proj"] + z["d_ssm"] * h                     # the mixer
            + h * z["heads"] * z["d"] + 2 * h * z["kv_heads"] * z["d"]
            + z["heads"] * z["d"] * h                          # attention
            + 3 * h * z["inner"])                              # the MLP


def _layer_small_params(z: dict) -> int:
    """Of one layer, what is no matmul: conv weight and bias, dt_bias,
    A_log, D, the gated norm, the two RMSNorms."""
    return (z["conv_dim"] * (z["conv"] + 1) + 3 * z["ssm_heads"]
            + z["d_ssm"] + 2 * z["h"])


def weight_bytes(config: dict, dtype_bytes: int = 2) -> int:
    """What one forward reads of the weights: every layer held, the final
    norm and the head (the embedding is read a row a token, not whole)."""
    z = reference.sizes(config)
    per_layer = _layer_matmul_params(z) + _layer_small_params(z)
    return dtype_bytes * (z["layers"] * per_layer + z["h"]
                          + z["h"] * z["rows"])


def kv_bytes_per_token(config: dict, kv_dtype: str) -> int:
    """K and V of one position over every layer held: the key heads'."""
    z = reference.sizes(config)
    return 2 * z["layers"] * z["kv_heads"] * z["d"] * KV_BYTES[kv_dtype]


def state_bytes_per_row(config: dict) -> int:
    """One sequence's recurrent state over every layer held, read once and
    written once: heads x d_head x d_state in float32."""
    z = reference.sizes(config)
    return (2 * z["layers"] * z["ssm_heads"] * z["d_head"] * z["state"]
            * STATE_BYTES)


def ssm_step_bytes(config: dict, rows: float) -> float:
    """The least the ``ssm`` scope of a decode step must move for ``rows``
    sequences decoding: each one's state read and written in its stated
    type, its convolution's tail read and written, and the mixer's inputs
    (the projection's output: z, xBC, dt) read and its output (y) written,
    over every layer held. The update is bound by bytes: about 5
    operations a state element against 8 bytes."""
    z = reference.sizes(config)
    tail = 2 * (z["conv"] - 1) * z["conv_dim"] * ACT_BYTES
    in_out = (z["proj"] + z["d_ssm"]) * ACT_BYTES
    return rows * (state_bytes_per_row(config)
                   + z["layers"] * (tail + in_out))


def decode_step_bytes(config: dict, kv_dtype: str, live_tokens: float,
                      rows: float = None) -> float:
    """The least a decode step must move: the weights once, the cached K
    and V of every position its sequences attend to (``live_tokens``,
    summed over the step's sequences), and the recurrent state of the
    ``rows`` sequences decoding, read and written. The driver hands no
    rows: then they are the ``max_running`` of the engine this process
    built last, which is what a saturated cell decodes a step (a cell
    below saturation decodes fewer, and would be counted too high)."""
    if rows is None:
        rows = _last_engine.get("max_running", 0)
    return (weight_bytes(config)
            + live_tokens * kv_bytes_per_token(config, kv_dtype)
            + rows * state_bytes_per_row(config))


def forward_flops(config: dict, tokens: int, attended: int,
                  emitted: int) -> float:
    """Operations the served work needs: 2 a parameter of the layers'
    matmuls for each of ``tokens`` positions forwarded (prompt or output);
    the recurrence's 5 a state element a position and layer (decay, the
    write's product and sum, the read's product and sum); 4 d a query head
    and layer for each pair of a query and a cached key (``attended`` pairs
    in all: QK^T and PV); and the head, 2 h a row, for the ``emitted``
    tokens alone."""
    z = reference.sizes(config)
    state = z["ssm_heads"] * z["d_head"] * z["state"]
    return (tokens * z["layers"] * (2.0 * _layer_matmul_params(z)
                                    + 5.0 * state)
            + 4.0 * z["d"] * z["heads"] * z["layers"] * attended
            + 2.0 * emitted * z["h"] * z["rows"])
