"""The ``kda`` scope's share of its roofline: the least time the chip could
take for the recurrence of a step (``families/kimi_linear.py``
``kda_work``: 7 dk dv operations a token and head forward, three times
that with the backward, against q, k, v, g, beta read and o written and
the same for the gradients; the larger of operations over 197 TFLOP/s and
bytes over 819 GB/s) over ``kda_ms.train``, in percent."""
from benchmark.lib import inner_scopes


def read(run: dict):
    return inner_scopes.roofline_pct(run, "kda")
