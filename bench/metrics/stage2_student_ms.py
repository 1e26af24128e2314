"""Device milliseconds per stage-2 epoch of the student (models.cnn, scope
``student``): its forward in both steps, its backward, the SGD update
and the merge of its BN statistics. Self time of the traced window's
operations in that scope (harness.scopes), over the window's epochs."""
from harness import scopes


def read(run):
    return scopes.ms_per_epoch(run, "student")
