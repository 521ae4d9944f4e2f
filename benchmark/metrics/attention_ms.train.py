"""Device time a step in score space: the ``XLA Ops`` events of the traced
stretch whose innermost ``jax.named_scope`` is ``attention``
(``ops/attention.py`` ``dot_product_attention``: QK, mask or bias,
softmax, PV, forward and backward, whatever tier runs), in milliseconds."""
from benchmark.lib import scopes


def read(run: dict):
    return scopes.device_ms(run, "attention")
