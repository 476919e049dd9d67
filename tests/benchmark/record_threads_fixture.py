#!/usr/bin/env python3
"""Records the small two-thread `.xplane.pb` that proves the join of the
program's host spans with the device ops. Run once on the chip (1 chip):

    python3 tests/benchmark/record_threads_fixture.py <out_dir>

A tiny program shaped like Sebulba, marked with the PROGRAM'S OWN `span`
(stoix_tpu.observability) and captured through the harness's own path
(benchmarks/harness/trace_capture.py): an "actor" thread that alternates a
small jitted call dispatched under `span("actor_inference")` with a host
sleep under `span("actor_env_step")`, inside `span("actor_rollout")`, and
hands each "rollout" to the main thread, which waits for it under
`span("learner_rollout_wait")` and runs a jitted update to its end under
`span("learner_update")`. The actor's call is ~1 ms of device work and is
not waited for inside its span, so the device goes idle INSIDE the sleep
that follows: the gaps on the op line begin under `actor_env_step`. Nothing
here is a metric. Written to <out_dir>/fixture_threads_1chip.xplane.pb.
"""

from __future__ import annotations

import os
import queue
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

ROLLOUTS, STEPS, SLEEP_S = 3, 4, 0.004


def main() -> int:
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import trace_capture
    from stoix_tpu.observability import span

    out_dir = sys.argv[1]
    os.makedirs(out_dir, exist_ok=True)

    @jax.jit
    def act_fn(w, x):
        for _ in range(8):  # about a millisecond of device work on a v5e
            x = jnp.tanh(x @ w)
        return x

    @jax.jit
    def per_shard(w, x):
        with jax.named_scope("ppo_epoch"):
            grad = jax.grad(lambda w_: jnp.mean((x @ w_) ** 2))(w)
            return w - 0.01 * grad

    w = jnp.ones((1024, 1024), jnp.float32) / 1024.0
    x = jnp.ones((2048, 1024), jnp.float32)
    jax.block_until_ready((act_fn(w, x), per_shard(w, x)))  # compiled before the trace

    rollouts: "queue.Queue" = queue.Queue(maxsize=1)

    def actor() -> None:
        obs = x
        for idx in range(ROLLOUTS):
            with span("actor_rollout", idx=idx):
                for _ in range(STEPS):
                    with span("actor_inference"):
                        obs = act_fn(w, obs)  # dispatched, not waited for
                    with span("actor_env_step"):
                        time.sleep(SLEEP_S)  # the "pool": the device idles in here
            with span("pipeline_put"):
                rollouts.put(obs)

    target = os.path.join(out_dir, "fixture_threads_1chip.xplane.pb")
    session = trace_capture.start()
    thread = threading.Thread(target=actor, name="actor-0")
    thread.start()
    weights = w
    for idx in range(ROLLOUTS):
        with span("learner_rollout_wait", update=idx):
            batch = rollouts.get(timeout=60.0)
        with span("learner_update", update=idx):
            weights = jax.block_until_ready(per_shard(weights, batch))
    thread.join()
    trace_capture.stop(session, target)
    print(f"{target}: {os.path.getsize(target)} bytes")

    from benchmarks.harness import trace_reduce

    names = ["actor_rollout", "actor_inference", "actor_env_step", "pipeline_put",
             "learner_rollout_wait", "learner_update"]
    trace = trace_reduce.read_xplane(target, host_names=names)
    print("gaps:", trace_reduce.longest_idle_gaps(trace, names, 12))
    print("host lines:", sorted({(e.line, e.name) for e in trace.host}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
