"""The counting functions of the openPangu-Ultra-MoE serve family against
counts written out by hand: the parameters held (the configuration's
``parameters``), a cached token's bytes, the experts hit from a known
routing and from the engine's counters, and the work of the ``mla`` and
``moe`` scopes of a decode step."""
import pytest

from benchmark.families import pangu_ultra_moe_serve as family
from benchmark.lib import manifest
from paddle_tpu.profiler.telemetry import get_telemetry

CELL = "openpangu-ultra-moe-718b.serve-closed-reason"

# the served size, by hand
ATTN = (7680 * 1536 + 1536 * 128 * 192 + 7680 * 576 + 512 * 128 * 256
        + 128 * 128 * 7680)                    # W_qa, W_qb, W_kva, W_kvb, W_o
NORMS = 4 * 7680 + 1536 + 512                  # the block's four, the latents' two
DENSE = 3 * 7680 * 18432
EXPERT = 3 * 7680 * 2048
ROUTER = 7680 * 256
HEAD = 7680 * 19200


@pytest.fixture(scope="module")
def real():
    return manifest.load("BENCHMARK.json", CELL)


def test_the_parameters_are_the_configurations(real):
    config = real["config"]
    by_hand = (5 * (ATTN + NORMS) + DENSE + 4 * (17 * EXPERT + ROUTER)
               + 2 * HEAD + 7680)
    assert ATTN == 196_575_232 and EXPERT == 47_185_920
    assert family.parameters(config) == by_hand == config["parameters"] \
        == 4_919_139_840
    # what the program builds for the cell holds as many
    from benchmark.reference import pangu_ultra_moe as reference
    import math

    assert sum(math.prod(shape) for shape, _, _ in
               reference.param_specs(config).values()) == by_hand


def test_a_cached_token_is_one_latent_row_a_layer(real):
    config = real["config"]
    assert family.kv_bytes_per_token(config, "bfloat16") == 5 * 1152
    assert family.kv_bytes_per_token(config, "float32") == 5 * 2304
    # the pool the cell states holds 262,128 tokens of it
    engine = real["cell"]["engine"]
    assert (engine["kv_blocks"] - 1) * engine["kv_block_size"] == 262_128


def test_experts_hit_from_a_known_routing_and_from_the_counters(real):
    import jax.numpy as jnp

    from paddle_tpu.incubate.moe import held_load

    # four tokens of two pairs: experts 3 and 15 are held (0-15) and hit,
    # expert 3 twice; 16, 200 and 255 are another chip's
    chosen = jnp.asarray([[3, 16], [3, 200], [15, 255], [16, 200]])
    assert held_load(chosen, 0, 16).tolist() == [2, 3]
    tel = get_telemetry()
    tel.reset()
    assert family.experts_hit() is None      # the program has counted none
    tel.counter("moe/layer_steps.decode", 8)  # two steps of four layers
    tel.counter("moe/experts_hit.decode", 110)
    assert family.experts_hit() == pytest.approx(13.75)
    tel.reset()
    # uniform routing's expectation at the cell's 64 rows
    assert family.expected_hit(real["config"], 64) == pytest.approx(
        16 * (1 - (31 / 32) ** 64))
    assert family.expected_hit(real["config"], 64) == pytest.approx(
        13.9, abs=0.01)


def test_the_moe_scopes_bytes(real):
    config = real["config"]
    # four expert layers: the hit experts, the shared one, the router
    assert family.moe_step_bytes(config, 13.9) == pytest.approx(
        2 * 4 * (14.9 * EXPERT + ROUTER))
    assert family.moe_step_bytes(config, 16) == pytest.approx(6.43e9,
                                                              rel=2e-3)


def test_the_mla_scopes_work(real):
    config = real["config"]
    rows, live = 64, 110_000
    ops, moved = family.mla_step_work(config, "bfloat16", rows, live)
    # a head, a query and a cached row: 2 (576 + 512); the query into the
    # latent space and the output out of it: 2 x 512 x (128 + 128) a head
    assert ops == 5 * (rows * 2 * 128 * 512 * 256
                       + live * 128 * 2 * (576 + 512))
    assert moved == 5 * (live * 1152 + 2 * 512 * 128 * 256)
    # ISSUE 37's arithmetic: 278 kflop against 1,152 B a row, 242 a byte
    # where the v5e's ridge is 240.5: the two bounds lie a fifth apart
    # (W_kvb's 33.5 MB a layer tip it to the bytes' side)
    assert 128 * 2 * (576 + 512) == 278_528
    assert ops / 197e12 == pytest.approx(moved / 819e9, rel=0.2)


def test_decode_step_bytes_counts_the_experts_hit(real):
    config = real["config"]
    get_telemetry().reset()
    outside = 5 * (ATTN + NORMS) + DENSE + 4 * (EXPERT + ROUTER) + 7680 + HEAD
    family._last_engine.clear()
    family._last_engine.update(max_running=64)
    hit = family.expected_hit(config, 64)
    got = family.decode_step_bytes(config, "bfloat16", live_tokens=110_000)
    assert got == pytest.approx(2 * (outside + 4 * hit * EXPERT)
                                + 110_000 * 5760)
    # with the engine's own count it is that count's
    tel = get_telemetry()
    tel.counter("moe/layer_steps.decode", 4)
    tel.counter("moe/experts_hit.decode", 48)
    assert family.decode_step_bytes(config, "bfloat16", 0) == pytest.approx(
        2 * (outside + 4 * 12 * EXPERT))
    tel.reset()


def test_forward_flops(real):
    config = real["config"]
    got = family.forward_flops(config, tokens=1000, attended=500_000,
                               emitted=300)
    per_token = 5 * ATTN + DENSE + 4 * (ROUTER + EXPERT * (1 + 8 * 16 / 256))
    assert got == pytest.approx(
        2.0 * 1000 * per_token + 2 * (192 + 128) * 128 * 5 * 500_000
        + 2.0 * 300 * HEAD)
