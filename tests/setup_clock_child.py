"""Child process of tests/test_setup_clock.py: launched as an operator
launches a system — import the system module, compose, `run_experiment` —
twice in one process, with the set-up gauge read after each run. Prints one
JSON line. Not a test module (no `test_` prefix)."""

import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ANAKIN_TINY = [
    "env=identity_game", "arch.total_num_envs=16", "arch.total_timesteps=~",
    "arch.num_updates=4", "arch.num_evaluation=2", "arch.num_eval_episodes=4",
    "system.rollout_length=4", "system.epochs=1", "system.num_minibatches=2",
    "arch.absolute_metric=False", "logger.use_console=False",
]
SEBULBA_TINY = [
    "env=cartpole", "env.backend=cvec", "arch.total_num_envs=16",
    "arch.actor.device_ids=[0]", "arch.actor.actor_per_device=2",
    "arch.learner.device_ids=[0]", "arch.evaluator_device_id=0",
    "arch.total_timesteps=~", "arch.num_updates=4", "arch.num_evaluation=2",
    "arch.num_eval_episodes=4", "system.rollout_length=8", "system.epochs=1",
    "system.num_minibatches=2", "logger.use_console=False",
]


def main(architecture: str) -> None:
    import json

    if architecture == "anakin":
        from stoix_tpu.systems import runner as stats_of
        from stoix_tpu.systems.ppo.anakin import ff_ppo as system
        root, overrides = "default/anakin/default_ff_ppo.yaml", ANAKIN_TINY
    else:
        from stoix_tpu.systems.ppo.sebulba import ff_ppo as system
        stats_of = system
        root, overrides = "default/sebulba/default_ff_ppo.yaml", SEBULBA_TINY
    from stoix_tpu import observability as obs
    from stoix_tpu.observability import trace
    from stoix_tpu.utils import config as config_lib

    closed = {}
    close = trace.SetupClock._close

    def stamping_close(self):
        close(self)
        closed["epoch"], closed["at"] = time.time(), time.perf_counter()

    trace.SetupClock._close = stamping_close
    runs = []
    for _ in range(2):
        config = config_lib.compose(config_lib.default_config_dir(), root, overrides)
        entered = time.perf_counter()
        system.run_experiment(config)
        snapshot = obs.get_registry().snapshot()
        runs.append({
            "phases": {
                series["labels"]["phase"]: series["value"]
                for series in snapshot["stoix_tpu_setup_phase_seconds"]["series"]
            },
            "entry_to_first_tick_s": closed["at"] - entered,
            "first_tick_epoch": closed["epoch"],
            "backend_up_at_entry": snapshot["stoix_tpu_setup_backend_up_at_entry"]["series"][0]["value"],
            "stats_setup_phases": dict(stats_of.LAST_RUN_STATS["setup_phases"]),
            "stats_launch_phases": stats_of.LAST_RUN_STATS["launch_phases"],
            "goodput": stats_of.LAST_RUN_STATS["goodput"],
        })
    # Steady state: set-up is over, and a program that compiles now is named.
    import jax
    import jax.numpy as jnp

    compiles = obs.get_registry().counter("stoix_tpu_compiles_total")

    def steady_state_recompile_probe(x):
        return jnp.sqrt(x) + 2.0

    before = compiles.value({"program": "steady_state_recompile_probe"})
    jax.jit(steady_state_recompile_probe)(jnp.ones(7)).block_until_ready()
    after = compiles.value({"program": "steady_state_recompile_probe"})
    print(json.dumps({"runs": runs, "steady_state_recompiles": [before, after]}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
