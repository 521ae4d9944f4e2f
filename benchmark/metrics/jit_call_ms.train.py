"""The program's ``pt.compute`` span (``ParallelTrainStep.__call__``: the
jitted call's host time, pytree flattening and dispatch), median over the
traced window, in milliseconds, on the profiler's clock."""
from benchmark.lib import scopes


def read(run: dict):
    return scopes.span_ms(run, "pt.compute")
