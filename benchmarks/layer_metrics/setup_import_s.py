"""Set-up: seconds the program's two runner modules (`systems/runner.py`,
`sebulba/runner.py`: what every system imports, and what pulls in the
checkpointing, env and network packages) spend in their own import blocks,
from the program's set-up gauge `stoix_tpu_setup_phase_seconds{phase=imports}`.
Moves `setup_s`."""

from benchmarks.harness import program_reads


def read(ctx):
    return program_reads.setup_phase_seconds(ctx, ("imports",))
