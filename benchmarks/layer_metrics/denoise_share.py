"""Learner program: the share of its device time under the program's
`denoise` scope — the rollout's denoise passes: a block's positions through
the trunk against the cache (nothing written), the head over the slice, the
sampling and the choice of the commit set. With `block_commit_share` it is
the model's part of `decode_share`; the rest of that is the token task."""

from benchmarks.harness import program_reads_lm


def read(ctx):
    return program_reads_lm.learner_share(ctx, ["denoise"])
