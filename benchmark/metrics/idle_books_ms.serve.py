"""Device idle milliseconds a decode run of the traced stretch while the
scheduler kept its books: the innermost open span on the host was
``pt.serve.admit`` (queue, gauges, deadline shedding), ``pt.serve.blocks``
(a round's block accounting, evictions included), ``pt.serve.tokens``
(cursors, tokens appended, telemetry) or ``pt.serve.retire``
(``inference/serving/decode.py``; ``lib/serve_spans.py``)."""
from benchmark.lib import serve_spans


def read(run: dict):
    return serve_spans.idle_ms(run, *serve_spans.GROUPS["books"])
