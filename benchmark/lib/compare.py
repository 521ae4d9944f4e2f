"""The comparison that decides ``correct`` for a training cell.

Both sides give the same readings of the first steps of training: each
step's loss, the norm of every leaf of the first gradient, and the norm of
every leaf's change over those steps. The numbers compared:

- ``loss_gap``: the widest |loss - reference's| over the steps, as a share
  of the reference's loss;
- ``grad_norm_gap``, ``change_norm_gap``: by the worst leaf, the gap
  between the two norms (not the norm of a difference), against the
  reference's norm of that leaf or of the median leaf, whichever is larger.
  ``change_norm_gap`` leaves out the leaves whose reference gradient is
  under a thousandth of the median leaf's: Adam moves those by round-off;
- ``grad_norm_gap_median``, ``change_norm_gap_median``: the same gaps by
  the median leaf, steady from seed to seed where the worst leaf is one
  small, noisy one (PERF.md section 2 says which cell holds which).

A number that is not finite fails whatever its limit.
"""
from __future__ import annotations

import math

import numpy as np

NOUGHT_SHARE = 1e-3  # of the median leaf's gradient norm


def _flat(norms: dict):
    names = sorted(norms)
    labels = [f"{n}[{i}]" if len(norms[n]) > 1 else n
              for n in names for i in range(len(norms[n]))]
    return labels, np.concatenate(
        [np.asarray(norms[n], np.float64).reshape(-1) for n in names])


def leaf_gaps(got: dict, want: dict):
    """(labels, gap of every leaf); a gap that is not finite reads inf."""
    labels, w = _flat(want)
    _, g = _flat(got)
    gaps = np.abs(g - w) / np.maximum(w, np.median(w))
    return labels, np.where(np.isfinite(gaps), gaps, np.inf)


def _worst(labels, gaps, keep=None):
    if keep is not None:
        gaps = np.where(keep, gaps, 0.0)
    i = int(np.argmax(gaps))
    return float(gaps[i]), labels[i]


def worst_leaf_gap(got: dict, want: dict, keep=None):
    """(gap, leaf) over the leaves where ``keep`` is true (all if None)."""
    return _worst(*leaf_gaps(got, want), keep)


def numbers(got: dict, want: dict) -> dict:
    """The numbers compared, with the leaf each norm gap was worst on."""
    losses_g = np.asarray(got["losses"], np.float64)
    losses_w = np.asarray(want["losses"], np.float64)
    loss_gap = np.abs(losses_g - losses_w) / np.abs(losses_w)
    loss_gap = float(np.max(np.where(np.isfinite(loss_gap), loss_gap,
                                     np.inf)))
    _, ref_grad = _flat(want["grad_norms"])
    moved = ref_grad >= NOUGHT_SHARE * np.median(ref_grad)
    labels, grad_gaps = leaf_gaps(got["grad_norms"], want["grad_norms"])
    _, change_gaps = leaf_gaps(got["change_norms"], want["change_norms"])
    grad_gap, grad_leaf = _worst(labels, grad_gaps)
    change_gap, change_leaf = _worst(labels, change_gaps, keep=moved)
    return {"loss_gap": loss_gap, "grad_norm_gap": grad_gap,
            "change_norm_gap": change_gap,
            "grad_norm_gap_median": float(np.median(grad_gaps)),
            "change_norm_gap_median": float(np.median(change_gaps[moved])),
            "worst": {"grad_norm_gap": grad_leaf,
                      "change_norm_gap": change_leaf,
                      "leaves_not_compared": int(np.sum(~moved))}}


def verdict(nums: dict, limits: dict):
    """(correct, {name: {"value", "limit"}}) for the limits that are set.
    A limit of null means the number is printed and not held."""
    compared, ok = {}, True
    for name, limit in limits.items():
        value = nums[name]
        compared[name] = {"value": value, "limit": limit}
        if limit is not None and not (math.isfinite(value)
                                      and value <= limit):
            ok = False
    return ok, compared
