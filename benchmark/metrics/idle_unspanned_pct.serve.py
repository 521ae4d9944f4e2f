"""The share of the traced stretch's device idle time that no phase of the
scheduler holds: outside every ``pt.serve.*`` span, or in an iteration or
a round span with no phase open. The check that the split of
``lib/serve_spans.py`` is whole, as ``unscoped_ms.train`` is for the
scopes."""
from benchmark.lib import serve_spans


def read(run: dict):
    return serve_spans.unspanned_pct(run)
