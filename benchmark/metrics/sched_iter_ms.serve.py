"""Host milliseconds a scheduler iteration that decoded: the window's wall
time over the engine's ``serve/decode_steps`` counted inside it. With every
running sequence in every step it is the pace at which a sequence gets its
tokens."""


def read(run: dict):
    return run.get("sched_iter_ms") if run.get("decode_steps") else None
