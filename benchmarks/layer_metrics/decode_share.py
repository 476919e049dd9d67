"""Learner program: the share of its device time under the program's
`rollout` scope — in the token-policy cell the cached decode, one `step` of
the block a token (policy) plus the token task (env). The rest is GAE and the
teacher-forced update (`update_share`)."""

from benchmarks.harness import program_reads_lm


def read(ctx):
    return program_reads_lm.learner_share(ctx, ["rollout"])
