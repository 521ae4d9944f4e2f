"""The comparison that decides ``correct`` for a cell that serves a model.

After the window, a sample of the requests it finished (drawn from the
seed, the longest always in it) goes once through the family's plain
reference: one full float32 forward over each prompt with the tokens the
timed engine emitted. At every emitted position the reference gives the gap
by which its logit of the emitted token lies below its best, in units of
the standard deviation of that position's logits. The numbers compared:

- ``token_margin_gap``: the widest such gap over all positions compared;
- ``token_margin_gap_mean``: their mean, steady from seed to seed where the
  widest swings.

Greedy tokens only. Token equality is not asked: with weights from a seed
the best two logits lie within rounding of each other at many positions.
"""
from __future__ import annotations

import numpy as np


def sample(finished: list, seed: int, n: int) -> list:
    """``n`` of ``finished`` ((prompt, emitted) pairs), the longest first,
    the others drawn from the seed."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i][0]) + len(finished[i][1])))
    longest, rest = order[0], np.array(order[1:], int)
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    picked = rng.permutation(rest)[:max(0, n - 1)]
    return [finished[longest]] + [finished[int(i)] for i in picked]


def rows(pairs: list, length: int):
    """``ids``, ``served`` and ``mask`` ``[len(pairs), length]``: row r
    feeds prompt + emitted tokens but the last; ``served[r, p]`` is the
    token that followed position p, ``mask`` where it was an emitted one."""
    ids = np.zeros((len(pairs), length), np.int32)
    served = np.zeros((len(pairs), length), np.int32)
    mask = np.zeros((len(pairs), length), bool)
    for r, (prompt, emitted) in enumerate(pairs):
        toks = np.concatenate([np.asarray(prompt, np.int32),
                               np.asarray(emitted, np.int32)])
        if len(toks) - 1 > length:
            raise ValueError(f"a served sequence of {len(toks)} tokens does "
                             f"not fit the reference's {length} positions")
        ids[r, :len(toks) - 1] = toks[:-1]
        served[r, :len(toks) - 1] = toks[1:]
        mask[r, len(prompt) - 1:len(toks) - 1] = True
    return ids, served, mask


def numbers(gaps: np.ndarray, mask: np.ndarray) -> dict:
    """The numbers compared, from the gaps of ``reference.margins``. A gap
    that is not finite, or nothing to compare, reads inf."""
    picked = np.asarray(gaps, np.float64)[mask]
    if picked.size == 0:
        return {"token_margin_gap": float("inf"),
                "token_margin_gap_mean": float("inf"), "tokens_compared": 0}
    picked = np.where(np.isfinite(picked), picked, np.inf)
    return {"token_margin_gap": float(picked.max()),
            "token_margin_gap_mean": float(picked.mean()),
            "tokens_compared": int(picked.size)}
