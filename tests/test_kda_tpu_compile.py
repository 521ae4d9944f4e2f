"""The ``chunk_kda`` kernel pair through the TPU v5e's own compiler, at the
Kimi cell's widths (heads of 128, chunks of 64, eight heads a grid step,
bf16 operands): what interpret mode cannot show (a slice that no tiling
takes, more VMEM than a kernel may use) costs no chip time here. Nothing
runs: the chip is described, not attached, and a compile that passes is not
a chip run.

The topology is described inside a fixture, never at import, and the file
is the only one that does so: one worker of the test run loads the TPU's
library, and only when it is given this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops import kda_tpu

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
    tokens = s((B, L, H, D), dtype)
    return (tokens, tokens, tokens, s((B, L, H, D), jnp.float32),
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
