"""Driver for the Anakin token-policy system (`ff_lm_ppo`): the seams and
the tick of drivers/anakin.py — `<system module>.learner_setup` (state
placement; the learner swapped for a forwarding recorder that keeps the
newest output state), the configuration's `networks_seam` (the very network
objects the program builds) and `StoixLogger.log`, all restored afterwards;
a tick is one eval window, stamped by the EVAL log event.

What differs from the MLP driver is what the run leaves for the
configuration's reference file and readers: the TIMED learner itself — the
executable the runner compiled ahead of time and called every window — with
the run's final state, still on the device (7.5 GB), so that the reference
can run one more window of exactly what was timed and compare what it
produced; the two entry points of the program's own block; the constants
the composed config resolved to; and `ctx.shapes` from harness/flops_lm.py.
`ctx.health["setup_phases"]` is set-up split by phase (what comes before
the program, and the runner's own gauge), printed with every run.
"""

from __future__ import annotations

import importlib
from typing import Any, Callable, Dict

from benchmarks.harness import flops_lm, observe


class KeepingLearn(observe.RecordingLearn):
    """The forwarding recorder, which also hands over the executable the
    runner compiles from it ahead of time: the one every window runs."""

    def __init__(self, inner: Any, on_output: Callable, on_compiled: Callable) -> None:
        super().__init__(inner, on_output)
        self._on_compiled = on_compiled

    def lower(self, *args: Any, **kwargs: Any) -> Any:
        return _Lowered(self._inner.lower(*args, **kwargs), self._on_output, self._on_compiled)


class _Lowered:
    def __init__(self, lowered: Any, on_output: Callable, on_compiled: Callable) -> None:
        self._lowered, self._on_output, self._on_compiled = lowered, on_output, on_compiled

    def compile(self, *args: Any, **kwargs: Any) -> observe.RecordingLearn:
        compiled = self._lowered.compile(*args, **kwargs)
        self._on_compiled(compiled)
        return observe.RecordingLearn(compiled, self._on_output)

    def __getattr__(self, name: str) -> Any:
        return getattr(self._lowered, name)


def run(ctx: Any) -> None:
    import time

    import numpy as np

    entered = time.perf_counter()

    from stoix_tpu.systems import runner
    from stoix_tpu.utils import config as config_lib
    from stoix_tpu.utils.logger import LogEvent

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
        seen["config"], seen["shards"] = cfg, int(mesh.shape["data"])
        seen.setdefault("learn", setup.learn)  # a runner that compiles nothing ahead calls this
        return setup._replace(
            learn=KeepingLearn(setup.learn, keep_output, lambda fn: seen.update(learn=fn))
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
        # Set-up, split: process start to this driver (imports, the chip's
        # start-up, the reference's check_before), compose and the seams, then
        # the runner's own phases; what is left of `setup_s` is the runner
        # between its phases and the warm-up window.
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

    cfg = seen["config"]
    actor, critic = seen["networks"]
    functions = module.network_functions(actor, critic, int(cfg.system.rollout_length))
    system = cfg.system
    ctx.networks = {
        "forward": functions.forward, "step": functions.step, "value": functions.value,
        "init_cache": functions.init_cache,
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
    ctx.shapes = flops_lm.lm_ppo_shapes(
        cfg, envs_per_chip=int(cfg.arch.total_num_envs) // ctx.cell.chips,
        updates_per_tick=int(cfg.arch.num_updates_per_eval),
    )
