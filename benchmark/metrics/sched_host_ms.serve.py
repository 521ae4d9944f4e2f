"""Host milliseconds an iteration spends outside the two timed calls: the
window's wall time less the sums of ``serve/decode_ms`` and
``serve/prefill_ms``, over the decode steps: admission, block accounting,
batch arrays, token bookkeeping, and waiting where nothing runs."""


def read(run: dict):
    return run.get("sched_host_ms") if run.get("decode_steps") else None
