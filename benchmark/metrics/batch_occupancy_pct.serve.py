"""Sequences in a decode step over ``max_running``, mean over the window's
steps: the tokens the window's decode steps gave (``serve/tokens_generated``
less the first tokens, which prefill gives) over ``serve/decode_steps``."""


def read(run: dict):
    if not run.get("decode_steps"):
        return None
    return 100.0 * run["batch_occupancy"]
