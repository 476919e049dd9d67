"""Child process of tests/test_setup_clock.py: launched as an operator
launches a system — import the system module, compose, `run_experiment` —
twice in one process, with the set-up gauge read after each run. Prints one
JSON line. Not a test module (no `test_` prefix).

`anakin_saving` is the Anakin child with checkpointing ON: its first run
saves (under the working directory, which the test makes a scratch one), its
second restores what the first saved. Every child also reports, at each
moment of `moments`, whether the checkpoint library is loaded and what its
gauge reads (docs/DESIGN.md §2.2, ISSUE 37)."""

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


CHECKPOINT_UID = "setup-clock-child"
# Per run of the `anakin_saving` child: save, then restore what was saved.
CHECKPOINTING = [
    ["logger.checkpointing.save_model=True",
     f"logger.checkpointing.save_args.checkpoint_uid={CHECKPOINT_UID}"],
    ["logger.checkpointing.load_model=True",
     f"logger.checkpointing.load_args.checkpoint_uid={CHECKPOINT_UID}"],
]


def checkpoint_library(moment: str) -> dict:
    """Is orbax (and the `google.cloud.logging` it brings) loaded, and what
    does `stoix_tpu_checkpoint_library_import_seconds` read (None: absent)?
    `google.cloud` itself is a namespace a `.pth` file of the installation
    puts in `sys.modules` at interpreter start: its submodules are the test."""
    from stoix_tpu import observability as obs

    series = obs.get_registry().snapshot().get(
        "stoix_tpu_checkpoint_library_import_seconds", {}
    ).get("series") or []
    return {
        "moment": moment,
        "orbax": any(m == "orbax" or m.startswith("orbax.") for m in sys.modules),
        "google_cloud": any(m.startswith("google.cloud.") for m in sys.modules),
        "import_seconds": series[0]["value"] if series else None,
    }


def main(architecture: str) -> None:
    import json

    moments = []
    if architecture.startswith("anakin"):
        from stoix_tpu.systems import runner as stats_of

        moments.append(checkpoint_library("runner_imported"))
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
    for run in range(2):
        extra = CHECKPOINTING[run] if architecture == "anakin_saving" else []
        config = config_lib.compose(config_lib.default_config_dir(), root, overrides + extra)
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
        moments.append(checkpoint_library(f"run_{run}"))
    # Steady state: set-up is over, and a program that compiles now is named.
    import jax
    import jax.numpy as jnp

    compiles = obs.get_registry().counter("stoix_tpu_compiles_total")

    def steady_state_recompile_probe(x):
        return jnp.sqrt(x) + 2.0

    before = compiles.value({"program": "steady_state_recompile_probe"})
    jax.jit(steady_state_recompile_probe)(jnp.ones(7)).block_until_ready()
    after = compiles.value({"program": "steady_state_recompile_probe"})
    print(json.dumps({
        "runs": runs, "steady_state_recompiles": [before, after], "moments": moments,
        "saved_steps": sorted(
            int(d) for d in os.listdir(os.path.join("checkpoints", CHECKPOINT_UID, "ff_ppo"))
            if d.isdigit()
        ) if architecture == "anakin_saving" else None,
    }), flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
