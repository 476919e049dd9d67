"""Network block (networks/lfm2.py): the share of the learner program's
device time under the `conv_mixer` scope — operator norm, W_in, the two
gates, the 3-tap convolution, W_out and, in the decode, the tail's write; in
the decode and in the update together. A program without the scope gives
None."""

from benchmarks.harness import program_reads_lm


def read(ctx):
    return program_reads_lm.learner_share(ctx, ["conv_mixer"])
