"""Device milliseconds a decode step in latent attention's own work: the
``XLA Ops`` events of the traced stretch's whole decode runs whose
innermost inner name is ``mla`` (``ops/attention.py::mla_paged_attention``
in its absorbed form: the query taken into the latent space, the walk over
the rows' latent pages, the output taken out of it; the projections and
the cache write around it are ``self_attn``'s), summed over the layers,
mean over those runs."""
from benchmark.lib import latent_scopes


def read(run: dict):
    return latent_scopes.device_ms(run, "mla", "decode")
