"""Sebulba actors (`systems/ppo/sebulba/ff_ppo.py::_rollout_body`): the
inference part of one actor step in milliseconds — stage the observation,
run `act_fn`, and wait until the action is on the host (so a wait behind the
learner's program on the shared device queue is in here): the actors'
`inference` means a step over whole rollouts, in the MISC log events."""

from benchmarks.harness import program_reads


def read(ctx):
    return program_reads.actor_timing_ms(ctx, "_inference_time")
