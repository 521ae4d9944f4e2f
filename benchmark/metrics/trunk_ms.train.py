"""Device time a step in the blocks outside score space: the events whose
innermost scope is ``embed``, ``self_attn`` (LayerNorm, QKV and output
projections, residual) or ``mlp``, forward and backward, in milliseconds."""
from benchmark.lib import scopes


def read(run: dict):
    return scopes.device_ms(run, "embed", "self_attn", "mlp")
