"""Kernels: the least seconds one decode step's ring reads need — every
window layer's live rows, min(t + 1, W) a sequence as a mean over the
rollout's positions, keys and values read once in float32
(harness/flops_swa.py; HBM bandwidth binds) — times the rollout's steps, over
the device time under `rollout/.../window_attend`, whatever implements it."""

from benchmarks.harness import program_reads_lm


def read(ctx):
    return program_reads_lm.roofline_share(
        ctx, ["rollout", "window_attend"], "window_attend_decode_step_cost",
        calls_per_update=ctx.shapes.get("rollout_length", 0),
    )
