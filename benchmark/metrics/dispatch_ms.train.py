"""Host time inside each ``step(...)`` call of the window, which returns
before the device finishes: the median, in milliseconds. The host's cost a
step, on the harness's own clock."""
import statistics


def read(run: dict):
    times = run.get("dispatch_ms")
    return statistics.median(times) if times else None
