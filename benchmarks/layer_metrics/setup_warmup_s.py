"""Set-up: seconds of the learner's warm-up ahead of the loop (`aot_warmup`:
trace, lower, and a compile or a cache read) and of the evaluator's build
(`evaluator_setup`), from the program's set-up gauge
`stoix_tpu_setup_phase_seconds`. Both phases exist since PR 23. Moves
`setup_s`."""

from benchmarks.harness import program_reads


def read(ctx):
    return program_reads.setup_phase_seconds(ctx, ("aot_warmup", "evaluator_setup"))
