"""Learner program: the share of its device time under the program's
`rollout_env` scope, `env.step` inside the rollout (the Ant physics in the
Anakin cells)."""

from benchmarks.harness import program_reads


def read(ctx):
    return program_reads.learner_scope_share(ctx, "rollout_env")
