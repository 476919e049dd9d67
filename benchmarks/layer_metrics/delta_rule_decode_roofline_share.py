"""Kernels: the least seconds one decode step's delta-rule state updates
need — every layer's matrix states read and written once in float32, q, k, v,
g, beta in and o out (harness/flops_kda.py; HBM bandwidth binds) — times the
rollout's steps, over the device time under `rollout/.../delta_rule`,
whatever implements it."""

from benchmarks.harness import program_reads_lm


def read(ctx):
    return program_reads_lm.roofline_share(
        ctx, ["rollout", "delta_rule"], "delta_rule_decode_step_cost",
        calls_per_update=ctx.shapes.get("rollout_length", 0),
    )
