"""``decode_step_bytes``, ``ssm_step_bytes`` and ``forward_flops`` of the
Falcon-H1 serve family against counts written out by hand for the tiny
rehearsal size, and against the issue's arithmetic for the real one."""
import pytest

from benchmark.families import falcon_h1_serve as family
from benchmark.lib import manifest

MANIFEST = "benchmark/tests/rehearsal_falcon/BENCHMARK.json"
CELL = "falcon-h1-tiny.serve-closed"

# the tiny size, by hand: h 64, 2 layers, 250 rows
PROJ = 64 + 64 + 32 + 32 + 4                  # z | x | B | C | dt = 196
MIXER = 64 * PROJ + 64 * 64                   # in_proj, out_proj
ATTN = 64 * 64 + 2 * 64 * 32 + 64 * 64        # q; k, v (2 heads of 16); o
MLP = 3 * 64 * 128
SMALL = 128 * 4 + 128 + 3 * 4 + 64 + 2 * 64   # conv w, b; dt_bias, A_log, D; norms
STATE = 4 * 16 * 16                           # a layer's state elements


@pytest.fixture(scope="module")
def tiny():
    return manifest.load(MANIFEST, CELL)["config"]


def test_weight_and_cache_bytes(tiny):
    layer = MIXER + ATTN + MLP + SMALL
    assert family.weight_bytes(tiny) == 2 * (2 * layer + 64 + 64 * 250)
    # K and V: 2 layers x 2 key heads x 16, bfloat16
    assert family.kv_bytes_per_token(tiny, "bfloat16") == 2 * 2 * 32 * 2
    # the state: read and written, float32
    assert family.state_bytes_per_row(tiny) == 2 * 2 * STATE * 4


def test_decode_step_bytes(tiny):
    got = family.decode_step_bytes(tiny, "bfloat16", live_tokens=1000, rows=6)
    assert got == (family.weight_bytes(tiny) + 1000 * 256
                   + 6 * 2 * 2 * STATE * 4)
    # the driver hands no rows: the engine last built says how many decode
    family._last_engine.clear()
    family._last_engine.update(max_running=8)
    assert family.decode_step_bytes(tiny, "bfloat16", 1000) == \
        family.decode_step_bytes(tiny, "bfloat16", 1000, rows=8)


def test_ssm_step_bytes(tiny):
    # a row and layer: state in and out (f32), the tail of 3 x 128 in and
    # out (bf16), the projection's 196 columns in and y's 64 out (bf16)
    per_row_layer = 2 * STATE * 4 + 2 * 3 * 128 * 2 + (PROJ + 64) * 2
    assert family.ssm_step_bytes(tiny, 5) == 5 * 2 * per_row_layer


def test_forward_flops(tiny):
    got = family.forward_flops(tiny, tokens=100, attended=3000, emitted=40)
    per_token = 2 * (2.0 * (MIXER + ATTN + MLP) + 5.0 * STATE)
    assert got == pytest.approx(100 * per_token + 4.0 * 16 * 4 * 2 * 3000
                                + 2.0 * 40 * 64 * 250)


def test_the_real_size_is_the_issues_arithmetic():
    config = manifest.load("BENCHMARK.json",
                           "falcon-h1-34b.serve-closed-chat")["config"]
    assert config["parameters"] == 4205319008
    # 9 x 860 MB + the head's 334 MB
    assert family.weight_bytes(config) == pytest.approx(8.08e9, rel=2e-3)
    assert family.kv_bytes_per_token(config, "bfloat16") == 18432
    assert family.state_bytes_per_row(config) == 9 * 2 * 4 * 1048576
    step = family.decode_step_bytes(config, "bfloat16", 64 * 400, rows=64)
    assert step == pytest.approx(8.08e9 + 0.47e9 + 4.83e9, rel=5e-3)
