"""Kernels: the least seconds one decode step's latent attention needs — the
live latent rows read once in float32, W_kvb's absorbed halves once, plus the
FLOPs of the absorbed scores and values (harness/flops_mla.py; HBM bandwidth
binds) — times the rollout's steps, over the device time under
`rollout/.../latent_attend`, whatever implements it."""

from benchmarks.harness import program_reads_lm


def read(ctx):
    return program_reads_lm.roofline_share(
        ctx, ["rollout", "latent_attend"], "latent_attend_decode_step_cost",
        calls_per_update=ctx.shapes.get("rollout_length", 0),
    )
