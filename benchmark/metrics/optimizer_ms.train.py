"""Device time a step in the optimizer: the events under the ``optimizer``
scope (``fleet/engine.py`` ``apply_optimizer_update``: clip, decay, the
Adam or AdamW update, master to resident cast), in milliseconds. A fusion
whose root is a weight-gradient matmul carries its Adam update with it
and is booked to the matmul's scope (PERF.md section 5 lists them)."""
from benchmark.lib import scopes


def read(run: dict):
    return scopes.device_ms(run, "optimizer")
