"""The ``mla`` scope's share of its roofline in a decode step: the greater
of the operations over the published bf16 peak and the bytes over the
published bandwidth that the absorbed form needs at the least
(``families/pangu_ultra_moe_serve.py`` ``mla_step_work``: for
``batch_occupancy`` x ``max_running`` rows and the cached positions the
pool holds, ``kv_occupancy`` x its usable blocks x the block size, each
latent row read once a layer and met by every head; W_kvb once a layer),
over ``mla_decode_ms.serve``. From shapes and the run's own gauges,
whatever implements the walk."""
from benchmark.lib import latent_scopes, peaks


def read(run: dict):
    ms = latent_scopes.device_ms(run, "mla", "decode")
    found = latent_scopes.cell_and_family(run)
    if not ms or not found or not run.get("batch_occupancy") \
            or not run.get("kv_occupancy"):
        return None
    cell, config, family = found
    engine = cell["engine"]
    rows = run["batch_occupancy"] * engine["max_running"]
    live = run["kv_occupancy"] * (engine["kv_blocks"] - 1) \
        * engine["kv_block_size"]
    ops, moved = family.mla_step_work(config, engine["kv_dtype"], rows, live)
    peak = peaks.peak(run["device"]["kind"])
    least_s = max(ops / peak["flops_per_s"], moved / peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
