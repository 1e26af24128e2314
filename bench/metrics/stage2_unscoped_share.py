"""Share of the traced stage-2 window's device busy time in operations
that carry none of the program's scopes (harness.scopes): loop control,
random draws, copies the compiler adds. It guards the per-scope metrics
against a change that drops a scope."""
from harness import scopes


def read(run):
    if run.traffic["driver"] != "stage2" or not run.trace.busy_s:
        return None
    secs = scopes.scope_seconds(run.trace)
    if secs is None:
        return None
    return 100.0 * secs.get(None, 0.0) / sum(secs.values())
