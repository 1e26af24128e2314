"""Roofline share of the fused distill_kl kernel pair in stage 2.

Its calls are the Pallas custom calls (``tpu_custom_call``) on the
(synth_batch, num_classes) logits. Kernel time is their summed device
time in the traced window. The least time of a call is the larger of
its operations over the bf16 peak and its bytes over HBM bandwidth
(harness.flops.distill_kl_cost); at (128, 10) the bytes bound every
call. A call is a forward when it writes per-row statistics only, and a
backward with or without the teacher gradient when it writes two or one
logit-shaped blocks."""
import re

from harness import flops


def read(run):
    if run.traffic["driver"] != "stage2":
        return None
    rows, vocab = run.cfg["synth_batch"], run.cfg["num_classes"]
    block = f"f32[{rows},{vocab}]"
    calls = run.trace.matching(
        r"custom-call\(" + re.escape(block) + r".*tpu_custom_call")
    if not calls:
        return None
    pk = run.peaks
    least = 0.0
    for name, _ in calls:
        outputs = name.split(" custom-call(")[0].count(block)
        ops, nbytes = flops.distill_kl_cost(
            rows, vocab, backward=outputs > 0, teacher_grad=outputs > 1)
        least += max(ops / pk["bf16_flops_per_s"],
                     nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / sum(d for _, d in calls)
