"""Device idle milliseconds a decode run of the traced stretch that lie
inside a compiled entry's own run (an ``XLA Modules`` event): the gaps
between the operations of one step, which no change on the host removes
(``lib/serve_spans.py``). Nothing where the scheduler opens no
``pt.serve.*`` span."""
from benchmark.lib import serve_spans


def read(run: dict):
    return serve_spans.idle_ms(run, serve_spans.IN_STEP)
