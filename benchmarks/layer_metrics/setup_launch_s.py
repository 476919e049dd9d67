"""Set-up: seconds between the import of the program's package and the entry
of `run_experiment` that are not the runner modules' imports — here the
reference's load and its `check_before`, the driver's seams (`launch`) and
`config_lib.compose` (`compose`) — from the program's set-up gauge
`stoix_tpu_setup_phase_seconds`. Moves `setup_s`."""

from benchmarks.harness import program_reads


def read(ctx):
    return program_reads.setup_phase_seconds(ctx, ("launch", "compose"))
