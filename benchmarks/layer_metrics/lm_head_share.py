"""Network block: the share of the learner program's device time under
`lm_head` — final norm, the [2048 -> 50,304] head matmul, and the
categorical over the vocabulary (log-softmax, sampling in the decode;
log-prob and entropy in the update)."""

from benchmarks.harness import program_reads_lm


def read(ctx):
    return program_reads_lm.learner_share(ctx, ["lm_head"])
