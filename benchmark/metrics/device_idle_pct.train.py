"""The share of a steady stretch of whole steps in which no operation ran
on the device, from the profiler's trace (``lib.trace.reduce``). Nothing
where the trace has no device in it."""


def read(run: dict):
    traced = run.get("trace")
    if not traced or run["device"]["platform"] != "tpu":
        return None
    return 100.0 * traced["idle_share"]
