"""Driver for the Anakin block-diffusion token-policy system (`ff_sdar_ppo`):
the seams and the tick of drivers/anakin_lm.py — `<system module>.learner_setup`
(state placement; the learner swapped for the forwarding recorder that keeps
the newest output state and the executable the runner compiled ahead of
time), the configuration's `networks_seam` and `StoixLogger.log`, all
restored afterwards; a tick is one eval window, stamped by the EVAL log event.

What differs is what a step is, and so what the run leaves for the
configuration's reference file and readers: the system's entry points are a
block step through the cache and the teacher-forced pass over `[clean ; noisy
copies]`, its env is the block token task, and `ctx.shapes` comes from
harness/flops_sdar.py — with the pairs a token a layer that landed on the
held experts as the run itself logged them.
"""

from __future__ import annotations

import importlib
from typing import Any, Dict

from benchmarks.harness import flops_sdar, loader, observe


def run(ctx: Any) -> None:
    import time

    import numpy as np

    entered = time.perf_counter()

    from stoix_tpu.systems import runner
    from stoix_tpu.utils import config as config_lib
    from stoix_tpu.utils.logger import LogEvent

    keeping_learn = loader.load_driver("anakin_lm", ctx.cell.root).KeepingLearn
    spec = ctx.cell.config
    module = importlib.import_module(spec["system_module"])
    config = config_lib.compose(
        config_lib.default_config_dir(), spec["default_yaml"], ctx.overrides()
    )
    seen: Dict[str, Any] = {}

    nets_module_name, nets_attr = spec["networks_seam"].split(":")
    nets_module = importlib.import_module(nets_module_name)
    build_networks = getattr(nets_module, nets_attr)

    def recording_build_networks(*args: Any, **kwargs: Any) -> Any:
        seen["networks"] = build_networks(*args, **kwargs)
        return seen["networks"]

    learner_setup = module.learner_setup

    def keep_output(output: Any) -> None:
        seen["state"] = output.learner_state
        seen["dispatched"] = seen.get("dispatched", 0) + 1

    def observing_setup(env: Any, cfg: Any, mesh: Any, key: Any, *args: Any, **kwargs: Any) -> Any:
        setup = learner_setup(env, cfg, mesh, key, *args, **kwargs)
        ctx.placement = observe.placement(setup.learner_state)
        seen["config"], seen["shards"], seen["env"] = cfg, int(mesh.shape["data"]), env
        seen.setdefault("learn", setup.learn)  # a runner that compiles nothing ahead calls this
        return setup._replace(
            learn=keeping_learn(setup.learn, keep_output, lambda fn: seen.update(learn=fn))
        )

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
            called = time.perf_counter()
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
        "setup_phases": {
            "before_driver": round(entered - ctx.clock.process_start, 3),
            "compose": round(called - entered, 3),
            **{k: round(float(v), 3) for k, v in (stats.get("setup_phases") or {}).items()},
        },
    }
    if not stats["resilience"]["preempted"]:
        ctx.problems.append(
            "the run ended by itself before the interval did: give the cell more windows"
        )

    cfg, env = seen["config"], seen["env"]
    actor, critic = seen["networks"]
    functions = module.network_functions(actor, critic, module.sequence_length(env), int(env.passes))
    system = cfg.system
    ctx.networks = {
        "block_step": functions.block_step, "trunk_copies": functions.trunk_copies,
        "head": functions.head, "value": functions.value, "init_cache": functions.init_cache,
        "learn": seen.get("learn"), "state": seen.get("state"), "shards": seen.get("shards", 1),
        "hyper": {
            "clip_eps": float(system.clip_eps), "ent_coef": float(system.ent_coef),
            "vf_coef": float(system.vf_coef), "aux_coef": float(system.router_aux_loss_coef),
            "gamma": float(system.gamma), "gae_lambda": float(system.gae_lambda),
            "standardize_advantages": bool(system.get("standardize_advantages", True)),
            "actor_lr": float(system.actor_lr), "critic_lr": float(system.critic_lr),
            "max_grad_norm": float(system.max_grad_norm),
            "decay_learning_rates": bool(system.get("decay_learning_rates", False)),
            "epochs": int(system.epochs), "num_minibatches": int(system.num_minibatches),
            "env_modulus": int(cfg.env.kwargs.get("modulus", 2)),
        },
    }
    logged = lambda name: [rec[name] for _, rec in ctx.train if name in rec]
    mean = lambda values: sum(values) / len(values) if values else None
    ctx.shapes = flops_sdar.sdar_ppo_shapes(
        cfg, envs_per_chip=int(cfg.arch.total_num_envs) // ctx.cell.chips,
        updates_per_tick=int(cfg.arch.num_updates_per_eval),
        held_pairs={
            "update": mean(logged("held_pairs_per_token")),
            "rollout": mean(logged("rollout_held_pairs_per_token")),
        },
    )
