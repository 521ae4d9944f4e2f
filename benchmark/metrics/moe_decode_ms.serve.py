"""Device milliseconds a decode step in the expert layers' own work: the
events of the traced stretch's whole decode runs whose innermost inner name
is ``moe`` (``text/models/pangu_ultra_moe.py``: router, top-k, the sort by
expert, three grouped products over the held experts, the combine, the
shared expert) or is one of the grouped products themselves, whose
``op_name`` the compiler writes anew (``lib/latent_scopes.py``), summed
over the expert layers, mean over those runs."""
from benchmark.lib import latent_scopes


def read(run: dict):
    return latent_scopes.device_ms(run, "moe", "decode")
