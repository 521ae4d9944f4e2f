"""The counting functions of the LongCat-Flash serve family against counts
written out by hand: the parameters held (the configuration's
``parameters``), a cached token's bytes, the experts hit, and the work of
the ``moe``, ``ffn`` and ``mla`` scopes of a decode step at the tiny size,
and the operations a served token costs from the run's own pair counts."""
import math

import pytest

from benchmark.families import longcat_flash_serve as family
from benchmark.lib import manifest
from benchmark.reference import longcat_flash as reference
from paddle_tpu.profiler.telemetry import get_telemetry

CELL = "longcat-flash-chat.serve-closed-chat"
TINY = "benchmark/tests/rehearsal_longcat/BENCHMARK.json"

# the served size, by hand
MLA = (6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256
       + 64 * 128 * 6144 + 1536 + 512)    # W_qa, W_qb, W_kva, W_kvb, W_o, gains
FFN = 3 * 6144 * 12288
ROUTER = 6144 * 768
EXPERT = 3 * 6144 * 2048
LAYER = 2 * MLA + 2 * FFN + ROUTER + 16 * EXPERT + 4 * 6144
HEAD = 6144 * 16384

# the tiny size (the rehearsal's configuration), by hand
T_MLA = (64 * 24 + 24 * 4 * 24 + 64 * 40 + 32 * 4 * 32 + 4 * 16 * 64
         + 24 + 32)
T_FFN = 3 * 64 * 128
T_EXPERT = 3 * 64 * 32
T_ROUTER = 64 * 12


@pytest.fixture(scope="module")
def real():
    return manifest.load("BENCHMARK.json", CELL)


@pytest.fixture(scope="module")
def tiny():
    return manifest.load(TINY, "longcat-tiny.serve-closed")["config"]


def test_the_parameters_are_the_configurations(real):
    config = real["config"]
    assert (MLA, FFN, ROUTER, EXPERT) == (90_572_800, 226_492_416,
                                          4_718_592, 37_748_736)
    assert LAYER == 1_242_853_376
    by_hand = 4 * LAYER + 2 * HEAD + 6144
    assert family.parameters(config) == by_hand == config["parameters"] \
        == 5_172_746_240
    # what the reference (and through it the harness) makes holds as many
    assert sum(math.prod(shape) for shape, _, _ in
               reference.param_specs(config).values()) == by_hand


def test_the_tiny_parameters(tiny):
    assert family.parameters(tiny) == (
        2 * (2 * T_MLA + 2 * T_FFN + T_ROUTER + 4 * T_EXPERT + 4 * 64)
        + 2 * 250 * 64 + 64)
    assert family.parameters(tiny) == sum(
        math.prod(shape) for shape, _, _ in
        reference.param_specs(tiny).values())


def test_a_cached_token_is_two_latent_rows_a_double_layer(real):
    config = real["config"]
    assert family.kv_bytes_per_token(config, "bfloat16") == 8 * 1152
    # the pool the cell states: 262,128 tokens, 2.68 GB stored 640 wide
    engine = real["cell"]["engine"]
    assert (engine["kv_blocks"] - 1) * engine["kv_block_size"] == 262_128
    assert engine["kv_blocks"] * 16 * 640 * 2 * 8 == pytest.approx(
        2.684e9, rel=1e-3)


def test_experts_hit_and_the_uniform_expectation(real):
    tel = get_telemetry()
    tel.reset()
    assert family.experts_hit() is None and family.run_counts() is None
    tel.counter("moe/layer_steps.decode", 8)   # two steps of four layers
    tel.counter("moe/experts_hit.decode", 111)
    assert family.experts_hit() == pytest.approx(13.875)
    tel.reset()
    # 128 rows, 12 of 768 columns a row: 2 pairs a held expert, 13.9 hit
    assert family.expected_hit(real["config"], 128) == pytest.approx(
        16 * (1 - (1 - 12 / 768) ** 128))
    assert family.expected_hit(real["config"], 128) == pytest.approx(
        13.9, abs=0.05)


def test_the_moe_scopes_bytes(real, tiny):
    assert family.moe_step_bytes(tiny, 3.0) == 2 * 2 * (
        3 * T_EXPERT + T_ROUTER)
    # the cell's reckoning: 13.9 experts of four layers, 4.19 GB
    assert family.moe_step_bytes(real["config"], 13.9) == pytest.approx(
        2 * 4 * (13.9 * EXPERT + ROUTER))
    assert 2 * 4 * 13.9 * EXPERT == pytest.approx(4.19e9, rel=2e-3)


def test_the_ffn_scopes_bytes(real, tiny):
    # two feed-forwards a double layer, each read once, and each row's
    # input read and output written at 2 B
    assert family.ffn_step_bytes(tiny, 5) == 2 * 2 * (
        2 * T_FFN + 5 * 2 * 64 * 2)
    assert family.ffn_step_bytes(real["config"], 0) == 8 * 2 * FFN
    assert family.ffn_step_bytes(real["config"], 0) == pytest.approx(
        3.62e9, rel=2e-3)


def test_the_mla_scopes_work(real, tiny):
    rows, live = 3, 100
    ops, moved = family.mla_step_work(tiny, "bfloat16", rows, live)
    # four attentions; a head, a query and a cached row 2 (40 + 32); the
    # query into the latent space and the output out of it 2 x 32 x 32 a
    # head; a cached row 40 x 2 B; W_kvb 32 x 4 x 32 at 2 B
    assert ops == 4 * (rows * 4 * 2 * 32 * 32 + live * 4 * 2 * (40 + 32))
    assert moved == 4 * (live * 80 + 2 * 32 * 4 * 32)
    ops, moved = family.mla_step_work(real["config"], "bfloat16", 128,
                                      120_000)
    assert moved == 8 * (120_000 * 1152 + 2 * 512 * 64 * 256)


def test_decode_step_bytes_counts_the_experts_hit(real):
    config = real["config"]
    tel = get_telemetry()
    tel.reset()
    outside = 4 * (LAYER - 16 * EXPERT) + 6144 + HEAD
    tel.counter("moe/layer_steps.decode", 4)
    tel.counter("moe/experts_hit.decode", 56)
    assert family.decode_step_bytes(config, "bfloat16", 1000) == \
        pytest.approx(2 * (outside + 4 * 14 * EXPERT) + 1000 * 9216)
    tel.reset()
    # the cell's reckoning of a decode step's least bytes: about 10.6 GB
    family._last_engine.update(max_running=128)
    assert family.decode_step_bytes(config, "bfloat16", 120_000) \
        == pytest.approx(10.6e9, rel=0.03)


def test_forward_flops_from_the_runs_own_pairs(tiny):
    tel = get_telemetry()
    tel.reset()
    # no counts: uniform routing, 3 of 12 columns, 4 held and 4 identity
    per_token = 2 * (2 * (T_MLA - 24 - 32) + 2 * T_FFN + T_ROUTER
                     + 1.0 * T_EXPERT + 1.0 * 64)
    pair = 2 * (16 + 8 + 16) * 4 * 4
    head = 2 * 64 * 250
    assert family.forward_flops(tiny, 10, 200, 3) == pytest.approx(
        2 * 10 * per_token + pair * 200 + 3 * head)
    # the run's own: 0.5 held and 2 identity pairs a position and layer;
    # an identity pair costs its weighted add alone
    for kind, (here, zero, positions) in (("decode", (30, 120, 60)),
                                          ("chunk", (20, 80, 40))):
        tel.counter(f"moe/pairs_here.{kind}", here)
        tel.counter(f"moe/pairs_zero.{kind}", zero)
        tel.counter(f"moe/positions.{kind}", positions)
    per_token = 2 * (2 * (T_MLA - 24 - 32) + 2 * T_FFN + T_ROUTER
                     + 0.5 * T_EXPERT + 2.0 * 64)
    assert family.forward_flops(tiny, 10, 200, 3) == pytest.approx(
        2 * 10 * per_token + pair * 200 + 3 * head)
    tel.reset()
