"""Kernels: the least seconds one decode step's conv mixers need — each
mixer's weights read once, its tail read and written, plus their FLOPs; HBM
bandwidth binds — times the rollout's steps, over the device time under
`rollout/.../conv_mixer`, whatever implements them."""

from benchmarks.harness import program_reads_lm


def read(ctx):
    return program_reads_lm.roofline_share(
        ctx, ["rollout", "conv_mixer"], "conv_mixer_decode_step_cost",
        calls_per_update=ctx.shapes.get("rollout_length", 0),
    )
