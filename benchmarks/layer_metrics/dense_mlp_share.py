"""Network block (networks/lfm2.py): the share of the learner program's
device time under the `dense_mlp` scope — the dense SwiGLU feed-forwards of
the leading layers, in the decode and in the update together. A program
without the scope gives None."""

from benchmarks.harness import program_reads_lm


def read(ctx):
    return program_reads_lm.learner_share(ctx, ["dense_mlp"])
