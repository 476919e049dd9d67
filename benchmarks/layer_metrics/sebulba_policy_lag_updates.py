"""Sebulba host loop: policy lag on the on-policy path — for every rollout
the learner consumed over the whole updates of the interval, its newest
parameter version minus the version the actor acted with (the histogram
`stoix_tpu_sebulba_policy_lag_updates`), mean in updates. A pipeline change
that buys steps/s with staleness shows here."""

from benchmarks.harness import observe


def read(ctx):
    span = ctx.registry_span()
    if span is None:
        return None
    before, after, _ = span
    name = "stoix_tpu_sebulba_policy_lag_updates"
    consumed = observe.registry_delta(before, after, name, "count")
    if consumed <= 0.0:
        return None
    return observe.registry_delta(before, after, name, "sum") / consumed
