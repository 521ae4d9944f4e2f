"""Device milliseconds a prefill chunk in latent attention's own work: as
``mla_decode_ms.serve``, over the traced stretch's whole prefill runs
(there the form is the expanded one: a group of cached rows taken up to
keys and values a head, then plain attention)."""
from benchmark.lib import latent_scopes


def read(run: dict):
    return latent_scopes.device_ms(run, "mla", "prefill")
