"""Device milliseconds per stage-2 epoch of the generator (core.generator,
scope ``generator``): its forward, its backward and its Adam update.
Self time of the traced window's operations in that scope
(harness.scopes), over the window's epochs."""
from harness import scopes


def read(run):
    return scopes.ms_per_epoch(run, "generator")
