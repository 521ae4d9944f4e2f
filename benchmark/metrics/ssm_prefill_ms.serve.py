"""Device milliseconds a prefill chunk in the state-space mixers' own work:
as ``ssm_decode_ms.serve``, over the traced stretch's whole prefill runs
(there the recurrence is ``ops.linear_attention.chunk_ssd``, carried from
chunk to chunk)."""
from benchmark.lib import serve_scopes


def read(run: dict):
    return serve_scopes.device_ms(run, "ssm", "prefill")
