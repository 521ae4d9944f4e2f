"""The ``moe`` scope's share of its memory roofline in a decode step: the
bytes it must read (``families/pangu_ultra_moe_serve.py``
``moe_step_bytes``: of every expert layer the held experts that received at
least one pair, the run's own count ``counter/moe/experts_hit.decode`` over
``moe/layer_steps.decode``, so that a step which skips an expert without a
pair cannot read over 100%; the shared expert; the router) over the
published bandwidth, over ``moe_decode_ms.serve``. Two pairs an expert: an
expert's time is its weights' pass."""
from benchmark.lib import latent_scopes, peaks


def read(run: dict):
    ms = latent_scopes.device_ms(run, "moe", "decode")
    found = latent_scopes.cell_and_family(run)
    if not ms or not found:
        return None
    _, config, family = found
    hit = family.experts_hit()
    if hit is None:
        return None
    least_s = family.moe_step_bytes(config, hit) / peaks.peak(
        run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
