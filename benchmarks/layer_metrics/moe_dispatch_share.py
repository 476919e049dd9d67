"""Network block: the share of the learner program's device time under
`moe_dispatch` — the sort of (token, slot) pairs by expert, the gather into
expert order, the un-permute and the weighted combine: the latency-bound glue
around the grouped matmuls, both phases."""

from benchmarks.harness import program_reads_lm


def read(ctx):
    return program_reads_lm.learner_share(ctx, ["moe_dispatch"])
