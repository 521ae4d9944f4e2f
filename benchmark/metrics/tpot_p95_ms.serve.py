"""Time per output token as a client feels it: for each request that ended
OK inside the window, (last token - first token) / (tokens - 1) from the
stamps the engine sets; the 95th percentile over those requests. In a
saturated closed loop it follows ``sched_iter_ms.serve``; a request may
have begun before the window opened."""


def read(run: dict):
    return run.get("tpot_p95_ms")
