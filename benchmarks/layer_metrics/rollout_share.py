"""Learner program: the share of its device time under the program's
`rollout` scope — the env-step scan body of the Anakin learner: policy
inference (`rollout_policy`) and env physics (`rollout_env`). With
`update_share` and `gae_share` it splits the learner program.
`rollout_policy`'s share is this minus `rollout_env_share`."""

from benchmarks.harness import program_reads


def read(ctx):
    return program_reads.learner_scope_share(ctx, "rollout")
