#!/usr/bin/env python3
"""Records the small `.xplane.pb` the trace-reduction tests read. Run once on
the chip (1 chip, or 4 for the collective fixture):

    python3 tests/benchmark/record_fixture.py <out_dir>

A few steps of a tiny program shaped like the learner — a "rollout" of
elementwise work, then two `ppo_epoch`-scoped matmul steps with a gradient
`pmean` over the `data` axis — and of a second program standing for the
evaluator, with a `learn_dispatch` TraceAnnotation around each dispatch and
host sleeps between steps so the idle gaps are real. Captured through the
harness's own path (benchmarks/harness/trace_capture.py) into
<out_dir>/fixture_<n>chip.xplane.pb; nothing here is a metric. (The 4-chip
fixture in the tree was recorded before that, through
`jax.profiler.start_trace`/`stop_trace`, which export the same XSpace.)
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    out_dir = sys.argv[1]
    os.makedirs(out_dir, exist_ok=True)
    devices = jax.devices()
    n = len(devices)
    mesh = Mesh(np.asarray(devices), ("data",))

    def learner_fn(w, x):
        for _ in range(2):  # the "rollout"
            x = jnp.tanh(x) * 1.01

        def step(w, _):
            with jax.named_scope("ppo_epoch"):
                grad = jax.grad(lambda w_: jnp.mean((x @ w_) ** 2))(w)
                grad = jax.lax.pmean(grad, axis_name="data")
                return w - 0.01 * grad, None

        w, _ = jax.lax.scan(step, w, None, 2)
        return w, x

    learn = jax.jit(jax.shard_map(
        learner_fn, mesh=mesh, in_specs=(P(), P("data")), out_specs=(P(), P("data")),
        check_vma=False,
    ))

    def _shard_eval(w, x):
        return jnp.sum(jnp.tanh(x @ w), axis=-1)

    evaluate = jax.jit(jax.shard_map(
        _shard_eval, mesh=mesh, in_specs=(P(), P("data")), out_specs=P("data"), check_vma=False,
    ))

    w = jax.device_put(jnp.ones((256, 256), jnp.float32) / 256.0, NamedSharding(mesh, P()))
    x = jax.device_put(jnp.ones((512 * n, 256), jnp.float32), NamedSharding(mesh, P("data")))
    w, x = learn(w, x)
    jax.block_until_ready(evaluate(w, x))

    from benchmarks.harness import trace_capture

    target = os.path.join(out_dir, f"fixture_{n}chip.xplane.pb")
    session = trace_capture.start()
    for _ in range(3):
        with jax.profiler.TraceAnnotation("learn_dispatch"):
            w, x = learn(w, x)
        jax.block_until_ready(evaluate(w, x))
        time.sleep(0.002)
    trace_capture.stop(session, target)
    print(f"{target}: {os.path.getsize(target)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
