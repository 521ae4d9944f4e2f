"""The share of the traced stretch of the serving window in which no
operation ran on the device (``lib.serve_trace.reduce``: first to last
whole run of the engine's compiled entries): how far the host's scheduler
holds the chip back. Nothing where the trace has no device in it."""


def read(run: dict):
    traced = run.get("trace")
    if not traced or run["device"]["platform"] != "tpu":
        return None
    return 100.0 * traced["idle_share"]
