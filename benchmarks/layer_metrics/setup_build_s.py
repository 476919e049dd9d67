"""Set-up: seconds building what runs — the env (and the C++ pool), network
init and the learner (`learner_setup`) — from the program's set-up gauge
`stoix_tpu_setup_phase_seconds`. Moves `setup_s`."""

from benchmarks.harness import program_reads


def read(ctx):
    return program_reads.setup_phase_seconds(
        ctx, ("env_build", "network_init", "learner_setup")
    )
