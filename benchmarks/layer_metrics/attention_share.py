"""Network block: the share of the learner program's device time under
`attention` — input norm, q/k/v/o projections, q/k norms, RoPE, the cache
write and the cached softmax in the decode, the flash kernel and its
plain-JAX backward in the update."""

from benchmarks.harness import program_reads_lm


def read(ctx):
    return program_reads_lm.learner_share(ctx, ["attention"])
