"""Host milliseconds around a decode step's call, through the fetch of its
tokens: the engine's own ``serve/decode_ms`` histogram, its sum over its
count inside the window."""


def read(run: dict):
    return run.get("sched_decode_ms") if run.get("decode_steps") else None
