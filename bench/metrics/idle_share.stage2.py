"""Share of the traced stage-2 window in which no operation ran on the
device: 100 * (1 - union of device-op intervals / window)."""


def read(run):
    if run.traffic["driver"] != "stage2" or not run.trace.devices:
        return None
    return 100.0 * run.trace.idle_share
