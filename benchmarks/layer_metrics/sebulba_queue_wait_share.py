"""Sebulba host loop (`sebulba/core.py`): the share of the learner's wall
spent blocked on the rollout queues — the consumer-side waits the pipeline
records in `stoix_tpu_sebulba_queue_get_wait_seconds{queue=rollout}`, over
whole updates of the interval."""

from benchmarks.harness import observe


def read(ctx):
    span = ctx.registry_span()
    if span is None:
        return None
    before, after, wall = span
    waited = observe.registry_delta(
        before, after, "stoix_tpu_sebulba_queue_get_wait_seconds", "sum", queue="rollout"
    )
    if wall <= 0.0:
        return None
    return 100.0 * waited / wall
