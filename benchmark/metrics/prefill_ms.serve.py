"""Device milliseconds a prefill chunk (one run of the ``serve.prefill.c*``
entry), mean over the traced stretch's whole runs (``lib.serve_trace``)."""
from benchmark.lib import serve_trace


def read(run: dict):
    if run["device"]["platform"] != "tpu":
        return None
    return serve_trace.mean_ms(run.get("trace"), "prefill")
