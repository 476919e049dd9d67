"""Kernels: the least seconds the update's delta rule needs — forward and
backward of the recurrence over whole sequences, its operands and results
moved once a pass and its products at the chunk-free count
(harness/flops_kda.py) — over the device time under
`update_epoch/.../delta_rule`, the chunk products, the loop over chunks, its
backward pass and its rematerialised forward alike."""

from benchmarks.harness import program_reads_lm


def read(ctx):
    return program_reads_lm.roofline_share(
        ctx, ["update_epoch", "delta_rule"], "delta_rule_update_cost"
    )
