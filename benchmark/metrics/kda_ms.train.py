"""Device time a step in the linear-attention layers' own work: the
``XLA Ops`` events of the traced stretch whose innermost inner name is
``kda`` (``text/models/kimi_linear.py`` ``KimiDeltaAttention``: the short
convolutions, the decay and write gates and ``ops.linear_attention.
chunk_kda``, forward and backward; the projections around them are
``self_attn``'s), in milliseconds."""
from benchmark.lib import inner_scopes


def read(run: dict):
    return inner_scopes.device_ms(run, "kda")
