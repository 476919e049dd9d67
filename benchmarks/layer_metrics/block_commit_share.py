"""Learner program: the share of its device time under the program's
`block_commit` scope — the pass that runs a finished block (and, once a
rollout, the prompt block) through the trunk to write its keys and values
into the cache: one pass in S + 1 of generation, and no token comes of it."""

from benchmarks.harness import program_reads_lm


def read(ctx):
    return program_reads_lm.learner_share(ctx, ["block_commit"])
