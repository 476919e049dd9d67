"""Learner program: the share of its device time under the program's `gae`
scope: the batched bootstrap-value critic apply and the `ops/multistep`
advantage recurrence between the rollout and the epochs."""

from benchmarks.harness import program_reads


def read(ctx):
    return program_reads.learner_scope_share(ctx, "gae")
