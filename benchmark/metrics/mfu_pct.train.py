"""The whole step's share of the chip's peak: the model's operations a
token (the family's shape function) times the tokens of the window, over
the window's wall time, over chips times the published bf16 peak. Only
from a chip: a run without a TPU has no such number."""
from benchmark.lib import peaks


def read(run: dict):
    device = run["device"]
    if device["platform"] != "tpu" or not run.get("window_seconds"):
        return None
    peak = peaks.peak(device["kind"])["flops_per_s"]
    achieved = (run["flops_per_token"] * run["window_tokens"]
                / run["window_seconds"])
    return 100.0 * achieved / (run["chips"] * peak)
