"""Model FLOP/s utilization of stage 2: the operations one epoch needs
(harness.flops.stage2_epoch_flops), times the epochs per second of the
traced window, over the chips' bf16 peak."""
from harness import flops


def read(run):
    if run.traffic["driver"] != "stage2" or not run.unit_rate:
        return None
    done = flops.stage2_epoch_flops(run.cfg) * run.unit_rate
    return 100.0 * done / (run.chips * run.peaks["bf16_flops_per_s"])
