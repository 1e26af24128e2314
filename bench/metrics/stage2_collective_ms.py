"""Device milliseconds per stage-2 epoch in which a collective operation
(all-reduce, all-gather, reduce-scatter, collective-permute, all-to-all,
found by HLO opcode) is on the chip: the client-sharded teacher's psum
of logits and of the generator's input gradient, and the gathering of
per-client BN statistics. The mean over the chips of the traced window
(harness.collectives)."""
from harness import collectives


def read(run):
    return collectives.ms_per_epoch(run, exposed=False)
