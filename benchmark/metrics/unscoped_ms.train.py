"""Device time a step under none of the program's six scopes, in
milliseconds: the check that the split of the step is whole. Nothing
where the program names no scope at all."""
from benchmark.lib import scopes


def read(run: dict):
    return scopes.device_ms(run, scopes.UNSCOPED)
