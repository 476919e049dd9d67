"""Network block (networks/lfm2.py, `sliding_attention`): the share of the
learner program's device time under the `window_mixer` scope — operator
norm, W_q W_k W_v, the per-head norms, the rotation, the ring's write, the
attend, the gate and W_o of the window layers; in the decode and in the
update together. A program without the scope gives None."""

from benchmarks.harness import program_reads_lm


def read(ctx):
    return program_reads_lm.learner_share(ctx, ["window_mixer"])
