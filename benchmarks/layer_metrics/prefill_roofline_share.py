"""Kernels: the least seconds one prefill's forward pass needs — every
layer's projections, the router, the held experts' rows, the band's and the
triangle's pairs over the prompt, and the state written once, no head
(harness/flops_mellum2.py `prefill_cost`) — over the device time under
`prefill` of the learner's whole executions, whatever implements
it (the banded and the causal flash kernels' forward, the grouped matmuls,
XLA's fusions)."""

from benchmarks.harness import program_reads_lm


def read(ctx):
    return program_reads_lm.roofline_share(ctx, ["prefill"], "prefill_cost")
