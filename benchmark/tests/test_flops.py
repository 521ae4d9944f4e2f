"""``flops_per_token`` of both families against a count over the real
parameter shapes, and against the figures ISSUE 26 gives."""
import json
import os

import pytest

from conftest import ROOT
from benchmark.lib import manifest

NOT_PER_TOKEN = {"gpt": {"wte", "wpe"},
                 "bert": {"word", "position", "token_type", "pooler_w",
                          "nsp_w"}}
CASES = {"gpt": ("gpt2-345m", "gpt2-345m.pretrain-8x1024", 6, 2.27e9, 18.6e12),
         "bert": ("bert-large-uncased", "bert-large.pretrain-16x512", 12,
                  2.16e9, 17.7e12)}


@pytest.mark.parametrize("name", sorted(CASES))
def test_flops_per_token(name):
    config_name, cell_name, attn, per_token, per_step = CASES[name]
    with open(os.path.join(ROOT, "benchmark/configs", config_name + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmark/workloads", cell_name + ".json")) as f:
        traffic = json.load(f)["traffic"]
    family = manifest.family(config["family"])
    specs = family.reference.param_specs(config)
    # every weight matrix a token is multiplied by once, outside the
    # embeddings (looked up, not multiplied) and the per-row heads
    n_matmul = 0
    for leaf, (shape, _, stacked) in specs.items():
        matrix = shape[1:] if stacked else shape
        if len(matrix) == 2 and leaf not in NOT_PER_TOKEN[name]:
            n_matmul += (shape[0] if stacked else 1) * matrix[0] * matrix[1]
    embedding = specs["wte" if name == "gpt" else "word"][0]
    hidden, layers = embedding[1], specs["qkv_w"][0][0]
    counted = (6 * n_matmul + 6 * embedding[0] * embedding[1]
               + attn * layers * traffic["seq_len"] * hidden)
    got = family.flops_per_token(config, traffic)
    assert got == pytest.approx(counted, rel=1e-12)
    assert got == pytest.approx(per_token, rel=5e-3)
    assert got * family.tokens_per_step(traffic) == pytest.approx(per_step,
                                                                   rel=5e-3)
    assert family.tokens_per_step(traffic) == 8192
    total = sum(_size(shape) for shape, _, _ in specs.values())
    assert total == config["parameters"]


def _size(shape):
    n = 1
    for d in shape:
        n *= d
    return n


def test_unknown_device_kind_raises():
    from benchmark.lib import peaks

    assert peaks.peak("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(LookupError):
        peaks.peak("TPU v9 imaginary")
