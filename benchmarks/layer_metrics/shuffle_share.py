"""Learner program: the share of its device time under the program's
`minibatch_shuffle` scope inside each epoch: `jax.random.permutation` (a
sort) and the `take` (a gather) over every trajectory leaf — the largest
device cost in both systems (PERF.md §5)."""

from benchmarks.harness import program_reads


def read(ctx):
    return program_reads.learner_scope_share(ctx, "minibatch_shuffle")
