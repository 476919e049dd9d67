"""Set-up: seconds from the end of the learner's warm-up compile (Sebulba:
from the first thread started) to the first completed window or update — the
remaining first compiles and first dispatches — from the program's set-up
gauge `stoix_tpu_setup_phase_seconds{phase=first_tick}`. Moves `setup_s`."""

from benchmarks.harness import program_reads


def read(ctx):
    return program_reads.setup_phase_seconds(ctx, ("first_tick",))
