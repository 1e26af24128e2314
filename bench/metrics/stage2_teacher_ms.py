"""Device milliseconds per stage-2 epoch of the grouped client ensemble
(core.ensemble, scope ``teacher``): its forward in both steps and its
backward into the generator. Self time of the traced window's operations
in that scope (harness.scopes), over the window's epochs."""
from harness import scopes


def read(run):
    return scopes.ms_per_epoch(run, "teacher")
