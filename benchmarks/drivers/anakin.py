"""Driver for the Anakin systems that run through `run_anakin_experiment`
(one jitted shard_mapped learner, the pipelined host loop of
`systems/runner.py`). A tick is one eval window: the runner logs ACT, TRAIN
and EVAL for a window once its fetched metrics are on the host, and the EVAL
event is the tick, stamped with the runner's own `window.t`.

Seams, all looked up by the program at call time and restored afterwards:
`<system module>.learner_setup` (state placement; the learner swapped for a
forwarding recorder that keeps the newest output state), the configuration's
`networks_seam` (the very network objects the program builds), and
`StoixLogger.log`.
"""

from __future__ import annotations

import importlib
from typing import Any, Dict

from benchmarks.harness import flops, observe


def _resolve(dotted: str) -> Any:
    module_name, attribute = dotted.split(":")
    return importlib.import_module(module_name), attribute


def run(ctx: Any) -> None:
    import jax
    import numpy as np

    from stoix_tpu.systems import runner
    from stoix_tpu.utils import config as config_lib
    from stoix_tpu.utils.logger import LogEvent

    spec = ctx.cell.config
    module = importlib.import_module(spec["system_module"])
    config = config_lib.compose(
        config_lib.default_config_dir(), spec["default_yaml"], ctx.overrides()
    )
    seen: Dict[str, Any] = {}

    # -- the program's own networks ---------------------------------------
    nets_module, nets_attr = _resolve(spec["networks_seam"])
    build_networks = getattr(nets_module, nets_attr)

    def recording_build_networks(*args: Any, **kwargs: Any) -> Any:
        actor, critic = build_networks(*args, **kwargs)
        seen["networks"] = (actor, critic)
        return actor, critic

    # -- learner_setup: placement, the recording learner --------------------
    learner_setup = module.learner_setup

    def keep_output(output: Any) -> None:
        seen["state"] = output.learner_state
        seen["dispatched"] = seen.get("dispatched", 0) + 1

    def observing_setup(env: Any, cfg: Any, mesh: Any, key: Any, *args: Any, **kwargs: Any) -> Any:
        setup = learner_setup(env, cfg, mesh, key, *args, **kwargs)
        ctx.placement = observe.placement(setup.learner_state)
        seen["config"] = cfg
        seen["obs_dim"] = int(np.prod(env.observation_value().agent_view.shape))
        return setup._replace(learn=observe.RecordingLearn(setup.learn, keep_output))

    # -- the logger: TRAIN losses, EVAL returns, the tick -------------------
    def on_event(metrics: Dict[str, Any], t: int, t_eval: int, event: Any) -> None:
        if event == LogEvent.TRAIN:
            ctx.train.append((len(ctx.clock.ticks), observe.mean_scalars(metrics)))
        elif event == LogEvent.EVAL:
            ctx.evals.append((int(t), float(np.mean(np.asarray(metrics["episode_return"])))))
            ctx.clock.tick(int(t))

    setattr(nets_module, nets_attr, recording_build_networks)
    module.learner_setup = observing_setup
    try:
        with observe.tee_logger(on_event):
            module.run_experiment(config)
    finally:
        module.learner_setup = learner_setup
        setattr(nets_module, nets_attr, build_networks)

    stats = dict(runner.LAST_RUN_STATS)
    ctx.run_stats = stats
    ctx.health = {
        "skipped_updates": int(stats["resilience"]["skipped_updates"]),
        "preempted": bool(stats["resilience"]["preempted"]),
        "pipelined": bool(stats["pipelined"]),
        "fused_eval": bool(stats["fused_eval"]),
        "windows_dispatched": seen.get("dispatched", 0),
    }
    if not stats["resilience"]["preempted"]:
        ctx.problems.append(
            "the run ended by itself before the interval did: give the cell more windows"
        )

    cfg = seen["config"]
    chips = ctx.cell.chips
    actor, critic = seen["networks"]
    params = jax.device_get(seen["state"].params) if "state" in seen else None
    first_replica = lambda tree: jax.tree.map(lambda x: np.asarray(x)[0], tree)
    actor_vars = first_replica(params.actor_params) if params is not None else None
    critic_vars = first_replica(params.critic_params) if params is not None else None
    ctx.networks = {
        "actor_apply": actor.apply, "critic_apply": critic.apply,
        "actor_vars": actor_vars, "critic_vars": critic_vars,
        "obs_dim": seen["obs_dim"], "action_width": int(cfg.system.action_dim),
    }
    ctx.shapes = flops.ppo_shapes(
        cfg, spec["reference"]["action_head"], seen["obs_dim"],
        envs_per_chip=int(cfg.arch.total_num_envs) // chips,
        updates_per_tick=int(cfg.arch.num_updates_per_eval),
    )
