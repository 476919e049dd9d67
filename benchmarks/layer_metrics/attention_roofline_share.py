"""Kernels: the least seconds the causal attention forward of one update
needs (q k^T and p v over the lower triangle; q, k, v read and the output
written once) over the device time of the Pallas flash kernel
(ops/pallas_attention.py, `flash_attention`) under `update_epoch/.../attention`.
The kernel's backward is plain JAX and is not in this share."""

from benchmarks.harness import program_reads_lm

KERNEL = "flash_attention"


def read(ctx):
    return program_reads_lm.roofline_share(
        ctx, ["update_epoch", "attention"], "attention_forward_cost",
        only=lambda kind: KERNEL in kind.path or KERNEL in kind.name,
    )
