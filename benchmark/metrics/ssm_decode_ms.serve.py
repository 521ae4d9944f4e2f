"""Device milliseconds a decode step in the state-space mixers' own work:
the ``XLA Ops`` events of the traced stretch's whole decode runs whose
innermost inner name is ``ssm`` (``text/models/falcon_h1.py``: the state's
and the convolution tail's gather and scatter, the convolution, the gates,
``ops.linear_attention.ssd_step`` and the gated norm; the projections
around them are ``self_attn``'s), mean over those runs."""
from benchmark.lib import serve_scopes


def read(run: dict):
    return serve_scopes.device_ms(run, "ssm", "decode")
