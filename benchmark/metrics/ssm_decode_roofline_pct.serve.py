"""The ``ssm`` scope's share of its memory roofline in a decode step: the
bytes it must move (``families/falcon_h1_serve.py`` ``ssm_step_bytes``:
each decoding sequence's recurrent state read and written in float32, its
convolution's tail read and written, the mixer's inputs read and its
output written; for ``batch_occupancy`` x ``max_running`` sequences, from
shapes alone, whatever implements it) over the published bandwidth, over
``ssm_decode_ms.serve``. The update is bound by bytes."""
from benchmark.lib import manifest, peaks, serve_scopes


def read(run: dict):
    ms = serve_scopes.device_ms(run, "ssm", "decode")
    if not ms or not run.get("batch_occupancy"):
        return None
    try:
        found = manifest.load("BENCHMARK.json", serve_scopes.cell_of(run))
    except SystemExit:
        return None
    work = getattr(manifest.family(found["config"]["family"] + "_serve"),
                   "ssm_step_bytes", None)
    if work is None:
        return None
    rows = run["batch_occupancy"] * found["cell"]["engine"]["max_running"]
    least_s = work(found["config"], rows) / peaks.peak(
        run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
