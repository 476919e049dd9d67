"""Sebulba actors (`systems/ppo/sebulba/ff_ppo.py::rollout_thread`): one
actor step — stage observations, policy inference, pool step — in
milliseconds: the actors' `rollout` TimingTracker means (rolling over ten
rollouts, logged with every eval block) inside the interval, averaged over
actors and blocks, over the rollout length."""


def read(ctx):
    if not ctx.shapes.get("rollout_length") or ctx.clock.start is None:
        return None
    end = ctx.clock.start + ctx.clock.seconds
    readings = [
        value
        for at, metrics in ctx.misc
        if ctx.clock.start <= at <= end
        for key, value in metrics.items()
        if key.startswith("actor") and key.endswith("_rollout_time")
    ]
    if not readings:
        return None
    return 1000.0 * (sum(readings) / len(readings)) / ctx.shapes["rollout_length"]
