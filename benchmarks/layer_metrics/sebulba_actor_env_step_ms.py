"""Sebulba actors: the env part of one actor step in milliseconds — the
host pool's `step` alone, given an action that is already on the host: the
actors' `env_step` means a step over whole rollouts, in the MISC log
events. With `sebulba_actor_inference_ms` it splits `sebulba_actor_step_ms`."""

from benchmarks.harness import program_reads


def read(ctx):
    return program_reads.actor_timing_ms(ctx, "_env_step_time")
