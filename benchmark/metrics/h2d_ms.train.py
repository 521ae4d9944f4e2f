"""The program's ``pt.h2d`` span (``ParallelTrainStep.__call__``: the
batch's one ``device_put``), median over the traced window, in
milliseconds, on the profiler's clock."""
from benchmark.lib import scopes


def read(run: dict):
    return scopes.span_ms(run, "pt.h2d")
