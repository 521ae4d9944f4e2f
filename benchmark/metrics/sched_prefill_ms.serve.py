"""Host milliseconds around a prefill chunk's call, through the fetch of
its tokens: the engine's own ``serve/prefill_ms`` histogram, its sum over
its count inside the window. Nothing where the window held no chunk."""


def read(run: dict):
    return run.get("sched_prefill_ms")
