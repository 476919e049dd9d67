"""Driver for the Sebulba PPO system (`systems/ppo/sebulba/ff_ppo.py`):
actor threads step a host env pool, a learner loop on the main thread trains,
an evaluator thread evaluates. The program logs only every
`num_updates_per_eval` updates, so a tick is one learner update, stamped in
the `learn_step_builder` seam of `run_experiment` (as chip_smoke.py uses it)
once that update's metrics are ready; the program blocks on the same arrays
right after, so the wait moves and nothing is added. Steps a tick are the
program's own `rollout_length * total_num_envs`.

Set-up ends at the first update, after the cell's warm-up updates, that
began after the first evaluation was back on the host and during which
nothing compiled (`settled`). The evaluator works on its own thread: it
compiles its program at the first eval block, logs the EVAL event as soon as
the evaluation is dispatched, and its result handler compiles a small mean
once the returns are on the host. Actors, learner and evaluator share one
device queue, so when the learner's next update gets the device first the
returns arrive a whole update after the EVAL event was logged, and that mean
compiles later still; no compilation may fall inside the interval. So the
event is stamped once its returns are read, not when it is logged, and the
margin is one whole update of this machine's own, checked against the
compile events themselves rather than a number of seconds.
"""

from __future__ import annotations

import importlib
import time
from typing import Any, Callable, Dict, Optional, Sequence

from benchmarks.harness import flops, observe


def settled(
    ticks: Sequence[Any], first_eval_at: Optional[float],
    compiles_inside: Callable[[float, float], int],
) -> bool:
    """Asked by the clock right after it stamped the newest tick: the update
    that ended there began after the first evaluation was back, and no
    compilation ended while it ran. `ticks` carry `.time`;
    `compiles_inside(a, b)` counts the compilations that ended in [a, b]."""
    if len(ticks) < 2 or first_eval_at is None:
        return False
    began, ended = ticks[-2].time, ticks[-1].time
    return first_eval_at <= began and not compiles_inside(began, ended)


def _counter_total(name: str) -> float:
    from stoix_tpu.observability import get_registry

    return sum(v for _, v in get_registry().counter(name).labels_and_values())


def run(ctx: Any) -> None:
    import jax
    import numpy as np

    from stoix_tpu.utils import config as config_lib
    from stoix_tpu.utils.logger import LogEvent

    spec = ctx.cell.config
    module = importlib.import_module(spec["system_module"])
    config = config_lib.compose(
        config_lib.default_config_dir(), spec["default_yaml"], ctx.overrides()
    )
    seen: Dict[str, Any] = {"updates": 0, "metrics": []}
    steps_per_update = int(config.system.rollout_length) * int(config.arch.total_num_envs)

    nets_attr = spec["networks_seam"].split(":")[1]
    build_networks = getattr(module, nets_attr)

    def recording_build_networks(*args: Any, **kwargs: Any) -> Any:
        actor, critic = build_networks(*args, **kwargs)
        seen["networks"] = (actor, critic)
        return actor, critic

    def observing_builder(*args: Any, **kwargs: Any) -> Callable:
        inner = module.get_learn_step(*args, **kwargs)

        def learn_step(state: Any, batch: Any) -> Any:
            if ctx.placement is None:
                ctx.placement = observe.placement(state)
                seen["obs_dim"] = int(np.prod(batch.obs.agent_view.shape[2:]))
            new_state, train_metrics = inner(state, batch)
            jax.block_until_ready(train_metrics)
            seen["state"] = new_state
            seen["updates"] += 1
            # Kept on the device, read after the run: no transfer is added.
            seen["metrics"].append((len(ctx.clock.ticks), train_metrics))
            ctx.clock.tick(seen["updates"] * steps_per_update)
            return new_state, train_metrics

        return learn_step

    def on_event(metrics: Dict[str, Any], t: int, t_eval: int, event: Any) -> None:
        if event == LogEvent.EVAL:
            # Reading the returns waits for the device; only then is the
            # evaluation back.
            value = float(np.mean(np.asarray(metrics["episode_return"])))
            seen.setdefault("first_eval_at", time.perf_counter())
            ctx.evals.append((int(t), value))
        elif event == LogEvent.MISC:
            ctx.misc.append((time.perf_counter(), observe.mean_scalars(metrics)))

    ctx.ready_checks.append(
        lambda: settled(ctx.clock.ticks, seen.get("first_eval_at"), ctx.compiles.inside)
    )
    errors_before = _counter_total("stoix_tpu_sebulba_evaluator_errors_total")
    crashes_before = _counter_total("stoix_tpu_sebulba_actor_crashes_total")
    setattr(module, nets_attr, recording_build_networks)
    try:
        with observe.tee_logger(on_event):
            module.run_experiment(config, learn_step_builder=observing_builder)
    finally:
        setattr(module, nets_attr, build_networks)

    stats = dict(module.LAST_RUN_STATS)
    ctx.run_stats = stats
    ctx.train = [(idx, observe.mean_scalars(jax.device_get(m))) for idx, m in seen["metrics"]]
    ctx.health = {
        "skipped_updates": int(stats["resilience"]["skipped_updates"]),
        "actor_restarts": int(stats["resilience"]["actor_restarts"]),
        "actor_crashes": int(_counter_total("stoix_tpu_sebulba_actor_crashes_total") - crashes_before),
        "evaluator_errors": int(_counter_total("stoix_tpu_sebulba_evaluator_errors_total") - errors_before),
        "preempted": bool(stats["resilience"]["preempted"]),
        "updates": seen["updates"],
    }
    if not stats["resilience"]["preempted"]:
        ctx.problems.append(
            "the run ended by itself before the interval did: give the cell more updates"
        )

    actor, critic = seen["networks"]
    params = jax.device_get(seen["state"].params) if "state" in seen else None
    ctx.networks = {
        "actor_apply": actor.apply, "critic_apply": critic.apply,
        "actor_vars": params.actor_params if params is not None else None,
        "critic_vars": params.critic_params if params is not None else None,
        "obs_dim": seen.get("obs_dim", 0), "action_width": int(config.system.action_dim),
    }
    ctx.shapes = flops.ppo_shapes(
        config, spec["reference"]["action_head"], seen.get("obs_dim", 0),
        envs_per_chip=int(config.arch.total_num_envs) // max(1, len(ctx.placement["device_ids"])),
        updates_per_tick=1,
    )
