"""Network block (networks/olmoe.py): the share of the learner program's
device time under the `moe` scope — router, dispatch and the expert matmuls,
in the decode and in the update together."""

from benchmarks.harness import program_reads_lm


def read(ctx):
    return program_reads_lm.learner_share(ctx, ["moe"])
