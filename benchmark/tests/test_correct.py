"""What decides ``correct``: the references agree with the program, the
control (the reference one precision down, in the program's place) does
not, and a run with the timed path broken underneath comes out false."""
import json
import os

import pytest

from conftest import CELLS, REHEARSAL, ROOT
from benchmark.lib import compare, manifest
from benchmark.reference import common


def _cell(family):
    found = manifest.load(REHEARSAL, CELLS[family])
    return found, manifest.family(found["config"]["family"])


def _readings(family, seed, precision):
    found, fam = _cell(family)
    cell, config = found["cell"], found["config"]
    batches = fam.make_batches(config, cell["traffic"], seed, 3)
    return common.three_steps(
        fam.reference, config, cell["optimizer"], seed, batches,
        precision=precision, rows_per_block=cell["reference"]["rows_per_block"])


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_is_not_correct(seed):
    """The reference one precision down (float8 under the bfloat16 the
    configurations state), put in the program's place, fails the cell's
    limits; the reference itself passes them. At this size only the GPT
    rehearsal can show it: see the next test."""
    found, _ = _cell("gpt")
    want = _readings("gpt", seed, "float32")
    control = _readings("gpt", seed, "float8")
    ok, compared = compare.verdict(compare.numbers(control, want),
                                   found["cell"]["limits"])
    assert not ok, compared
    same, _ = compare.verdict(compare.numbers(want, want),
                              found["cell"]["limits"])
    assert same


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_bert_control_reads_far_worse_than_the_stated_precision(seed):
    """At a width of 64 the program's own bfloat16 noise (activations,
    softmax and gradients all rounded) is as large as float8 matmuls in an
    otherwise float32 reference, so no limit parts the two here; on the
    chip at the cell's size they lie ten times apart (PERF.md section 2).
    What a test run can hold: against the same reference computed in the
    stated bfloat16, the control's gradients read three times worse."""
    want = _readings("bert", seed, "float32")
    stated = compare.numbers(_readings("bert", seed, "bfloat16"), want)
    control = compare.numbers(_readings("bert", seed, "float8"), want)
    assert control["grad_norm_gap"] > 3 * stated["grad_norm_gap"]


class _Broken:
    """The timed object with a fault planted under the harness."""

    def __init__(self, step, fault):
        self._step, self._fault = step, fault

    def __getattr__(self, name):
        return getattr(self._step, name)

    def __call__(self, inputs, labels):
        if self._fault == "state_unchanged":
            kept = self._step.snapshot_state()
            loss = self._step(inputs, labels)
            self._step.restore_state(kept)
            return loss
        half = lambda t: tuple(a[:len(a) // 2] for a in t)  # noqa: E731
        return self._step(half(inputs), half(labels))


@pytest.mark.parametrize("family", sorted(CELLS))
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_broken_timed_path_is_not_correct(family, fault, run_cell,
                                          monkeypatch):
    _, fam = _cell(family)
    build = fam.build
    monkeypatch.setattr(fam, "build",
                        lambda *a, **k: _Broken(build(*a, **k), fault))
    line, err = run_cell(family, seed=4)
    assert line["correct"] is False
    failed = [n for n, c in line["compared"].items()
              if c["limit"] is not None and not c["value"] <= c["limit"]]
    assert failed and all(f"compared {n} " in err for n in failed)


def test_worst_leaf_gap_is_a_gap_of_norms_against_the_larger_scale():
    want = {"a": [1.0, 1.0, 1.0], "tiny": [1e-6]}
    got = {"a": [1.0, 1.1, 1.0], "tiny": [3e-6]}
    gap, leaf = compare.worst_leaf_gap(got, want)
    assert leaf == "a[1]" and gap == pytest.approx(0.1)
    # the tiny leaf is measured against the median leaf, not itself
    gap, leaf = compare.worst_leaf_gap({"a": [1, 1, 1], "tiny": [3e-6]}, want)
    assert leaf == "tiny" and gap == pytest.approx(2e-6)
    assert compare.worst_leaf_gap({"a": [1, float("nan"), 1], "tiny": [0]},
                                  want)[0] == float("inf")


def test_leaves_the_reference_does_not_move_are_left_out_of_the_change():
    want = {"losses": [1.0], "grad_norms": {"a": [1.0, 1.0], "k_bias": [1e-9]},
            "change_norms": {"a": [1.0, 1.0], "k_bias": [1.0]}}
    got = {"losses": [1.0], "grad_norms": {"a": [1.0, 1.0], "k_bias": [0.0]},
           "change_norms": {"a": [1.0, 1.0], "k_bias": [5.0]}}
    nums = compare.numbers(got, want)
    assert nums["change_norm_gap"] == 0.0
    assert nums["worst"]["leaves_not_compared"] == 1
