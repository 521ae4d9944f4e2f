"""The ``ffn`` scope's share of its memory roofline in a decode step: the
bytes it must move (``families/longcat_flash_serve.py`` ``ffn_step_bytes``:
both dense feed-forwards of every double layer read once, and for
``batch_occupancy`` x ``max_running`` rows each row's input read and
output written) over the published bandwidth, over ``ffn_decode_ms.serve``.
At a decode step's rows a feed-forward is bound by its weights' pass."""
from benchmark.lib import ffn_scopes, peaks


def read(run: dict):
    ms = ffn_scopes.device_ms(run, "decode")
    found = ffn_scopes.cell_and_family(run)
    if not ms or not found or not run.get("batch_occupancy"):
        return None
    cell, config, family = found
    rows = run["batch_occupancy"] * cell["engine"]["max_running"]
    least_s = family.ffn_step_bytes(config, rows) / peaks.peak(
        run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
