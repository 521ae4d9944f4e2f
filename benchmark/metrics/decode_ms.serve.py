"""Device milliseconds a decode step (one run of a ``serve.decode.b*``
entry), mean over the traced stretch's whole runs, whatever bucket each
took (``lib.serve_trace``)."""
from benchmark.lib import serve_trace


def read(run: dict):
    if run["device"]["platform"] != "tpu":
        return None
    return serve_trace.mean_ms(run.get("trace"), "decode")
