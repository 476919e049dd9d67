"""Kernels: the least seconds one decode step's expert matmuls need — read
the weights of every expert the step's tokens reach (all 64 at 256 tokens)
once, plus their FLOPs; HBM bandwidth binds — times the rollout's steps, over
the device time under `rollout/.../moe_experts`."""

from benchmarks.harness import program_reads_lm


def read(ctx):
    return program_reads_lm.roofline_share(
        ctx, ["rollout", "moe_experts"], "experts_decode_step_cost",
        calls_per_update=ctx.shapes.get("rollout_length", 0),
    )
