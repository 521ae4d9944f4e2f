"""A decode step's share of its memory roofline: the bytes it must read
(the family's ``decode_step_bytes``: the weights once and the cached K and
V of the positions its sequences attend to, mean over the traced stretch)
over the published bandwidth, over ``decode_ms.serve``. It counts the work,
whatever tier does it. Nothing without a traced decode step."""
from benchmark.lib import peaks, serve_trace


def read(run: dict):
    device = run["device"]
    ms = serve_trace.mean_ms(run.get("trace"), "decode")
    if device["platform"] != "tpu" or not ms or not run.get(
            "decode_step_bytes"):
        return None
    least_s = run["decode_step_bytes"] / peaks.peak(
        device["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
