"""Kernels: the least seconds one decode step's reads of the growing caches
need — every full layer's live rows, t + 1 a sequence as a mean over the
rollout's input positions P .. P + G - 1, keys and values read once in
float32 (harness/flops_mellum2.py; HBM bandwidth binds) — times the rollout's
steps, over the device time under `rollout/.../attention_scores`, whatever
implements it (the prefill's causal pass runs under `prefill`, beside
`rollout`: it is in neither the time nor the cost)."""

from benchmarks.harness import program_reads_lm


def read(ctx):
    return program_reads_lm.roofline_share(
        ctx, ["rollout", "attention_scores"], "full_attend_decode_step_cost",
        calls_per_update=ctx.shapes.get("rollout_length", 0),
    )
