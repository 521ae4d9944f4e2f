"""Device time a step in the routed experts: the events whose innermost
inner name is ``moe`` (``incubate/moe.py`` ``DroplessMoE``: router, top-k,
sort, the grouped products over the held stack, the weighted scatter-add,
forward and backward; the shared expert is ``mlp``'s), in milliseconds."""
from benchmark.lib import inner_scopes


def read(run: dict):
    return inner_scopes.device_ms(run, "moe")
