"""Kernels: the least seconds the update's latent attention needs — forward
and backward of the expansion W_kvb and of the causal score and value
products at head sizes 192 | 128 (harness/flops_mla.py; the chip's bf16 peak
binds) — over the device time under `update_epoch/.../latent_attend`, the
flash kernel and its plain backward alike."""

from benchmarks.harness import program_reads_lm


def read(ctx):
    return program_reads_lm.roofline_share(
        ctx, ["update_epoch", "latent_attend"], "latent_attend_update_cost"
    )
