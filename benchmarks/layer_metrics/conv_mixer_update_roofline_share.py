"""Kernels: the least seconds the conv mixers' forward and backward need in
one update (harness/flops_lfm2.py: the two projections, x3 for the backward,
and the bytes a fused pass over the gates and the convolution moves; the
chip's bf16 peak binds) over the device time under
`update_epoch/.../conv_mixer`, whatever implements them."""

from benchmarks.harness import program_reads_lm


def read(ctx):
    return program_reads_lm.roofline_share(
        ctx, ["update_epoch", "conv_mixer"], "conv_mixer_update_cost"
    )
