"""Faults a serving cell can have, planted under the harness from outside
the program, for the tests of ``correct`` and for readings on the chip
(``tools/serve_control.py --fault``). No benchmark run plants one.

- ``altered_token``: every ``every``-th token is altered where it is
  produced (the scheduler's ``_append_token`` gets the id one higher), so
  the sequence goes on from a token the model did not choose.
- ``block_table_mixup``: every ``every``-th sequence's block table names, in
  its first place, the first page of another sequence that holds cache at
  that moment: a cache mix-up, the sequence reads (and writes) another's
  keys and values.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def altered_token(eng, config: dict, every: int = 7):
    sched, vocab = eng._scheduler, config["vocab_size"]
    inner, count = sched._append_token, [0]

    def outer(r, tok):
        count[0] += 1
        return inner(r, (tok + 1) % vocab if count[0] % every == 0 else tok)

    sched._append_token = outer
    try:
        yield
    finally:
        del sched._append_token


@contextlib.contextmanager
def block_table_mixup(eng, config: dict, every: int = 2):
    pool = eng.pool
    inner = pool.block_table

    def outer(owner, width):
        table = inner(owner, width)
        if owner % every == 0:
            others = [o for o in list(pool._owned) if o != owner
                      and pool._owned.get(o)]
            if others:
                table[0] = pool._owned[min(others)][0]
        return table

    pool.block_table = outer
    try:
        yield
    finally:
        del pool.block_table


FAULTS = {"altered_token": altered_token,
          "block_table_mixup": block_table_mixup}


def plant(name, eng, config: dict):
    """The context manager of fault ``name`` (None: no fault)."""
    if name is None:
        return contextlib.nullcontext()
    return FAULTS[name](eng, config)
