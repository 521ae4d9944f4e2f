"""``flops_per_token`` of the Kimi-Linear family against a count over the
reference's parameter shapes and against the figures ISSUE 30 gives, and
the two roofline counts (``kda_work``, ``moe_work``) against theirs."""
import json
import os

import pytest

from conftest import ROOT
from benchmark.lib import manifest

CELL = "kimi-linear-48b-a3b.pretrain-1x8192"


@pytest.fixture(scope="module")
def found():
    got = manifest.load("BENCHMARK.json", CELL)
    got["family"] = manifest.family(got["config"]["family"])
    return got


def _size(shape):
    n = 1
    for d in shape:
        n *= d
    return n


def test_the_cut_holds_what_the_issue_counts(found):
    config, family = found["config"], found["family"]
    specs = family.reference.param_specs(config)
    total = sum(_size(shape) for shape, _, _ in specs.values())
    assert total == config["parameters"] == 602_433_408
    assert total * 16 == pytest.approx(9.64e9, rel=2e-3)
    by = lambda prefix: sum(_size(s) for n, (s, _, _) in specs.items()  # noqa: E731
                            if n.startswith(prefix))
    assert by("l0_kda_") == pytest.approx(39.51e6, rel=1e-3)
    assert by("l3_mla_") == pytest.approx(29.11e6, rel=1e-3)
    assert by("l0_dense_") == pytest.approx(63.70e6, rel=1e-3)
    assert _size(specs["l1_moe_e_gate_w"][0]) * 3 / 8 == \
        pytest.approx(7.08e6, rel=1e-3)
    assert by("embed") + by("head_w") == pytest.approx(94.4e6, rel=1e-3)
    assert set(family.NAMES) == set(specs)
    assert family.reference.layer_kinds(config) == [
        ("kda", "dense"), ("kda", "moe"), ("kda", "moe"), ("mla", "moe"),
        ("kda", "moe")]


def test_flops_per_token(found):
    config, family = found["config"], found["family"]
    traffic = found["cell"]["traffic"]
    specs = family.reference.param_specs(config)
    # every weight matrix a token is multiplied by: all of them outside
    # the embedding (looked up), the convolutions and the vectors; the
    # held stacks at 0.25 of one expert a token
    held_share = 8 * 8 / 256
    n_matmul = 0.0
    for leaf, (shape, _, _) in specs.items():
        if leaf == "embed" or leaf.endswith("_conv") or len(shape) < 2:
            continue
        if len(shape) == 3:
            n_matmul += held_share * shape[1] * shape[2]
        elif leaf != "head_w":
            n_matmul += shape[0] * shape[1]
    head = _size(specs["head_w"][0])
    scores = 3.0 * traffic["seq_len"] * 32 * (192 + 128)      # one MLA layer
    recurrence = 4 * 3.0 * 7 * 128 * 128 * 32                  # four KDA layers
    counted = 6.0 * n_matmul + 6.0 * head + scores + recurrence
    got = family.flops_per_token(config, traffic)
    assert got == pytest.approx(counted, rel=1e-12)
    # ISSUE 30: 2.3 GFLOP a token (projections and head 2.0, MLA's score
    # space 0.25, the recurrence 0.04), about 19 TFLOP a step
    assert got == pytest.approx(2.3e9, rel=3e-2)
    assert scores == pytest.approx(0.25e9, rel=2e-2)
    assert recurrence == pytest.approx(0.044e9, rel=2e-2)
    assert family.tokens_per_step(traffic) == 8192
    assert got * 8192 == pytest.approx(19e12, rel=3e-2)


def test_roofline_counts(found):
    config, family = found["config"], found["family"]
    traffic = found["cell"]["traffic"]
    kda = family.kda_work(config, traffic)
    # four layers x 8192 tokens x 32 heads x 7 x 128 x 128, three times
    assert kda["flops"] == 4 * 8192 * 32 * 7 * 128 * 128 * 3
    # q, k, v, o at 2 bytes, g and beta at 4, and again for the gradients
    a_token = 2 * (4 * 4096 * 2 + 4096 * 4 + 32 * 4)
    assert kda["bytes"] == 4 * 8192 * a_token
    # the bytes bound it: 3.9 ms a step against 1.8 ms of operations
    assert kda["bytes"] / 819e9 > kda["flops"] / 197e12
    moe = family.moe_work(config, traffic)
    assert moe["flops"] == 4 * 8192 * 6 * (2304 * 256
                                           + 0.25 * 3 * 2304 * 1024)
    assert moe["bytes"] == 4 * 3 * 8 * 3 * 2304 * 1024 * 2
    # neither may read over 100%: the least time is under a millisecond a
    # layer, far below what any implementation takes
    assert max(moe["flops"] / 197e12, moe["bytes"] / 819e9) < 3e-3


def test_the_manifest_names_the_cell_as_its_file_does():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        whole = json.load(f)
    entry = {w["name"]: w for w in whole["workloads"]}[CELL]
    with open(os.path.join(ROOT, "benchmark/workloads", CELL + ".json")) as f:
        cell = json.load(f)
    assert entry["why"] == cell["why"] and len(entry["why"]) <= 200
    assert entry["chips"] == cell["chips"] == 1
    assert cell["remat"] != "auto"
    mine = [m["name"] for m in whole["per_layer"] if CELL in m["workloads"]]
    assert len(mine) == 14 and mine[-4:] == [
        "kda_ms.train", "moe_ms.train", "kda_roofline_pct.train",
        "moe_roofline_pct.train"]
