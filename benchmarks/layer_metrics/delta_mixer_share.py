"""Network block (networks/kda.py): the share of the learner program's device
time under the `delta_mixer` scope — operator norm, W_q W_k W_v W_f and the
gates, the three 4-tap convolutions, the delta rule, the output norm and
gate, W_o and, in the decode, the state's and the tails' writes; in the
decode and in the update (its rematerialised forward included) together. A
program without the scope gives None."""

from benchmarks.harness import program_reads_lm


def read(ctx):
    return program_reads_lm.learner_share(ctx, ["delta_mixer"])
