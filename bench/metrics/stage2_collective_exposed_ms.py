"""The part of ``stage2_collective_ms`` during which no other operation
runs on the chip: the collective time the rate pays for. Per stage-2
epoch, the mean over the chips of the traced window
(harness.collectives)."""
from harness import collectives


def read(run):
    return collectives.ms_per_epoch(run, exposed=True)
