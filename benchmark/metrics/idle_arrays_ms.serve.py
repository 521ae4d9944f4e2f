"""Device idle milliseconds a decode run of the traced stretch while the
scheduler's ``pt.serve.arrays`` span was the innermost open on the host:
a feed's numpy arrays and their one ``device_put``
(``inference/serving/decode.py``; ``lib/serve_spans.py``)."""
from benchmark.lib import serve_spans


def read(run: dict):
    return serve_spans.idle_ms(run, *serve_spans.GROUPS["arrays"])
