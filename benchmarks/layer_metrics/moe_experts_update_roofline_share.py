"""Kernels: the least seconds the ACTIVE experts' forward and backward
grouped matmuls need (harness/flops_lm.py: top-k rows a token, three matmuls,
x3 for the backward; the chip's bf16 peak binds) over the device time under
`update_epoch/.../moe_experts` (XLA:TPU's grouped-matmul kernels for
`jax.lax.ragged_dot` and the SwiGLU between them)."""

from benchmarks.harness import program_reads_lm


def read(ctx):
    return program_reads_lm.roofline_share(
        ctx, ["update_epoch", "moe_experts"], "experts_update_cost"
    )
