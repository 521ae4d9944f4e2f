"""Device idle milliseconds a decode run of the traced stretch while the
scheduler's ``pt.serve.dispatch`` span was the innermost open on the host:
the jitted call until it returns (``inference/serving/decode.py``;
``lib/serve_spans.py``)."""
from benchmark.lib import serve_spans


def read(run: dict):
    return serve_spans.idle_ms(run, *serve_spans.GROUPS["dispatch"])
