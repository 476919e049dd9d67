"""Sebulba actors (`systems/ppo/sebulba/ff_ppo.py::_rollout_body`): what an
actor spends, once a rollout, between its last env step and the push into the
queue, in milliseconds — span `actor_prepare_data`: bringing the collected
rollout into the `[T, E/n, ...]` arrays on the learner's devices that the
pipeline carries. The learner waits for it in series after the rollout's
steps.

Read from the actors' `prepare_data` MEDIANS in the MISC log events, not
their means: both are taken over the timer's last ten rollouts, and the
first rollouts of a run compile inside this span (3-30 s once, against 0.7 s
steady), so the mean still holds set-up when the interval begins (PERF.md
section 6, PR 28: 1,880 ms for 680)."""

from benchmarks.harness import program_reads


def read(ctx):
    return program_reads.actor_timing_ms(ctx, "_prepare_data_p50")
