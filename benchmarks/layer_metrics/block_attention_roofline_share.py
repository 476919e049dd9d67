"""Kernels: the least seconds the update's forward and backward score and
value products need over the (query, key) pairs the block mask ALLOWS
(harness/flops_sdar.py: q k^T and p v, x3 for the backward; q, k, v read and
the output written once a pass), over the device time under
`update_epoch/.../attention_scores`, whatever implements them — masked
products over whole rows of keys count their masked half as time, not as
work."""

from benchmarks.harness import program_reads_lm


def read(ctx):
    return program_reads_lm.roofline_share(
        ctx, ["update_epoch", "attention_scores"], "block_attention_update_cost"
    )
