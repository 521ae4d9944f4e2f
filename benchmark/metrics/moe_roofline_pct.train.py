"""The ``moe`` scope's share of its roofline: the least time the chip could
take for a step's routed experts (``families/kimi_linear.py``
``moe_work``: the router and the expected 0.25 held experts a token, 6
operations a parameter, against the held stacks read once forward and
twice backward) over ``moe_ms.train``, in percent."""
from benchmark.lib import inner_scopes


def read(run: dict):
    return inner_scopes.roofline_pct(run, "moe")
