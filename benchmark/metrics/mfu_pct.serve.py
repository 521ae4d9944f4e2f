"""The whole window's share of the chip's peak: the operations the served
work needs (the family's ``forward_flops`` over the positions forwarded,
the pairs attended and the tokens emitted inside the window, from the
requests' stamps) over the window's wall time, over chips times the
published bf16 peak. Only from a chip."""
from benchmark.lib import peaks


def read(run: dict):
    device = run["device"]
    if device["platform"] != "tpu" or not run.get("window_seconds"):
        return None
    peak = peaks.peak(device["kind"])["flops_per_s"]
    return 100.0 * run["flops"] / run["window_seconds"] / (
        run["chips"] * peak)
