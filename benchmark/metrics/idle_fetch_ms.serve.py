"""Device idle milliseconds a decode run of the traced stretch while the
scheduler's ``pt.serve.fetch`` span was the innermost open on the host:
the tokens' ``np.asarray``, which waits for the device and then copies
(``inference/serving/decode.py``; ``lib/serve_spans.py``)."""
from benchmark.lib import serve_spans


def read(run: dict):
    return serve_spans.idle_ms(run, *serve_spans.GROUPS["fetch"])
