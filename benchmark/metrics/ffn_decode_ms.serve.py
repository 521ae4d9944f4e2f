"""Device milliseconds a decode step in the dense feed-forwards' own work:
the ``XLA Ops`` events of the traced stretch's whole decode runs whose
innermost inner name is ``ffn`` (``text/models/longcat_flash.py``: the
gate, up and down products of the two dense feed-forwards of every double
layer; their norms and the residual adds stay ``mlp``'s), summed over the
layers, mean over those runs."""
from benchmark.lib import ffn_scopes


def read(run: dict):
    return ffn_scopes.device_ms(run, "decode")
