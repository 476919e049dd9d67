"""Network block (networks/lfm2.py::RoutedMLP): the share of the learner
program's device time under the `shared_expert` scope — the SwiGLU every
token passes beside the routed experts, in the decode and in the update
together. A program without the scope gives None."""

from benchmarks.harness import program_reads_lm


def read(ctx):
    return program_reads_lm.learner_share(ctx, ["shared_expert"])
