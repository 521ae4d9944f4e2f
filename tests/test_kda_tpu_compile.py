"""The ``chunk_kda`` kernel pair through the TPU v5e's own compiler, at the
Kimi cell's widths (heads of 128, chunks of 64, eight heads a grid step,
bf16 operands): what interpret mode cannot show (a slice that no tiling
takes, more VMEM than a kernel may use) costs no chip time here. Nothing
runs: the chip is described, not attached, and a compile that passes is not
a chip run.

The last case compiles a whole ``KimiDeltaAttention`` layer, value and
gradient, and reads the optimised HLO: between the projections nothing may
compute on the heads as an axis, or the layout pass brings back the
relayouts of the whole tensor that PR 33 took out (43 ms a step of the Kimi
cell).

The topology is described inside a fixture, never at import, and the file
is the only one that does so: one worker of the test run loads the TPU's
library, and only when it is given this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops import kda_tpu, linear_attention

B, L, H, D, CHUNK = 1, 256, 8, 128, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def shapes(one_chip, dtype):
    s = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    tokens = s((B, L, H * D), dtype)
    return (tokens, tokens, tokens, s((B, L, H * D), jnp.float32),
            s((B, L, H), jnp.float32)), s((B, L // CHUNK, H, D, D),
                                          jnp.float32)


def compile_for_the_chip(fn, *args):
    # an executable for a described chip cannot be read back from the
    # persistent cache without one: keep it out
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return jax.jit(fn).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", before)


@pytest.mark.parametrize("keep_states", [False, True])
def test_the_forward_kernel_compiles_for_the_v5e(one_chip, keep_states):
    inputs, _ = shapes(one_chip, jnp.bfloat16)
    text = compile_for_the_chip(
        lambda *a: kda_tpu.forward(*a, CHUNK, 16, keep_states), *inputs)
    assert "tpu_custom_call" in text and "chunk_kda_fwd" in text


def test_the_backward_kernel_compiles_for_the_v5e(one_chip):
    inputs, states = shapes(one_chip, jnp.bfloat16)
    text = compile_for_the_chip(
        lambda *a: kda_tpu.backward(*a[:5], a[5], a[6], CHUNK, 16),
        *inputs, states, inputs[0])
    assert "tpu_custom_call" in text and "chunk_kda_bwd" in text


def test_a_kda_layer_computes_nothing_on_the_heads_axis(one_chip,
                                                        monkeypatch):
    """The layer at the cell's heads (32 of 128) and a reduced length and
    hidden size, bf16 activations, loss and every gradient: the kernels
    are there, and no ``copy`` or ``transpose`` touches an f32 tensor whose
    last axes are [heads, d], nor does a ``reshape`` that is no bitcast
    make the flat f32 tensor: the L2 norm of q and k, the decay and the
    gated output norm all run on [b, l, heads d]."""
    from paddle_tpu.jit.functionalize import functionalize, get_params
    from paddle_tpu.text.models.kimi_linear import (KimiDeltaAttention,
                                                    KimiLinearConfig)

    heads, d, length, hidden = 32, 128, 1024, 256
    monkeypatch.setattr(linear_attention, "_on_tpu", lambda: True)
    layer = KimiDeltaAttention(KimiLinearConfig(
        hidden_size=hidden, num_hidden_layers=1, kda_layers=(1,),
        full_attn_layers=(), kda_num_heads=heads, kda_head_dim=d))
    apply = functionalize(layer, training=True)

    def loss(params, x, ct):
        weights = {n: p.astype(jnp.bfloat16) if p.ndim == 2 else p
                   for n, p in params.items()}
        return jnp.sum(apply(weights, {}, x)[0].astype(jnp.float32) * ct)

    on_chip = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    x = on_chip((1, length, hidden), jnp.bfloat16)
    text = compile_for_the_chip(
        jax.value_and_grad(loss, argnums=(0, 1)),
        {n: on_chip(p.shape, p.dtype) for n, p in get_params(layer).items()},
        x, on_chip(x.shape, jnp.float32))
    assert "chunk_kda_fwd" in text and "chunk_kda_bwd" in text
    by_head = re.compile(r"f32\[[0-9,]*\b%d,%d\]" % (heads, d))
    flat = "f32[1,%d,%d]" % (length, heads * d)
    relayouts = [
        line.strip()[:160] for line in text.splitlines()
        if (m := re.match(r"\s*(?:ROOT )?\S+ = \S+ (copy|transpose|reshape)\(",
                          line))
        and (by_head.search(line) if m.group(1) != "reshape"
             else flat in line)]
    assert not relayouts, relayouts
