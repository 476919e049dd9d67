"""Scaling-efficiency benchmark harness (BASELINE.json north star: >=80%
efficiency from v5e-8 to v5e-64).

Runs the Anakin PPO throughput benchmark over growing mesh sizes with the
per-shard workload held CONSTANT (weak scaling — more devices, proportionally
more envs) and reports steps/sec plus efficiency vs the smallest mesh.

On real hardware this measures ICI collectives; without enough chips it runs
on virtual CPU devices (still validating that the sharded program's collective
structure scales, with CPU-fidelity numbers only).

Usage: python scaling_bench.py [--sizes 1 2 4 8] [--envs-per-device 512]
"""

from __future__ import annotations

import argparse
import json
import time


def measure(n_devices: int, envs_per_device: int, rollout_length: int) -> float:
    import jax
    import numpy as np

    from stoix_tpu import envs
    from stoix_tpu.parallel import create_mesh
    from stoix_tpu.systems.ppo.anakin.ff_ppo import learner_setup
    from stoix_tpu.utils import config as config_lib
    from stoix_tpu.utils.timestep_checker import check_total_timesteps

    config = config_lib.compose(
        config_lib.default_config_dir(),
        "default/anakin/default_ff_ppo.yaml",
        [
            f"arch.total_num_envs={envs_per_device * n_devices}",
            f"system.rollout_length={rollout_length}",
            "arch.num_updates=8",
            "arch.total_timesteps=~",
            "arch.num_evaluation=2",
            "logger.use_console=False",
        ],
    )
    mesh = create_mesh({"data": n_devices}, devices=jax.devices()[:n_devices])
    config = check_total_timesteps(config, n_devices)
    env, _ = envs.make(config)
    setup = learner_setup(env, config, mesh, jax.random.PRNGKey(0))

    steps_per_call = (
        rollout_length * envs_per_device * n_devices * int(config.arch.num_updates_per_eval)
    )

    def force(out):
        leaf = jax.tree.leaves(out.learner_state.params)[0]
        return float(np.asarray(jax.numpy.sum(leaf)))

    out = setup.learn(setup.learner_state)
    force(out)
    state = out.learner_state
    start = time.perf_counter()
    out = setup.learn(state)
    force(out)
    elapsed = time.perf_counter() - start
    return steps_per_call / elapsed


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--sizes", nargs="+", type=int, default=None)
    parser.add_argument("--envs-per-device", type=int, default=512)
    parser.add_argument("--rollout-length", type=int, default=32)
    parser.add_argument(
        "--cpu",
        action="store_true",
        help="run on the virtual-CPU platform (same as bench.py --cpu): a "
        "wiring check, never a device measurement",
    )
    args = parser.parse_args()

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    from stoix_tpu.utils import compilecache

    compilecache.configure()  # persistent cache on before the first compile

    n_avail = len(jax.devices())
    sizes = args.sizes or [s for s in (1, 2, 4, 8, 16, 32, 64) if s <= n_avail]

    results = []
    base_per_device = None
    for n in sizes:
        sps = measure(n, args.envs_per_device, args.rollout_length)
        per_device = sps / n
        if base_per_device is None:
            base_per_device = per_device
        results.append(
            {
                # Payload-shaped (bench.py --check contract): metric/value/
                # median/rel_spread make each per-size line gate-composable,
                # so `python scaling_bench.py | python bench.py --check
                # SCALING_BASE.json --candidate -` holds a variance band
                # around weak-scaling throughput with zero glue.
                "metric": f"scaling_ppo_weak_d{n}_env_steps_per_sec",
                "value": round(sps, 1),
                "median": round(sps, 1),
                "rel_spread": 0.0,
                "unit": "env_steps/sec (weak scaling)",
                "devices": n,
                "env_steps_per_sec": round(sps, 1),
                "per_device": round(per_device, 1),
                "efficiency_vs_smallest": round(per_device / base_per_device, 3),
            }
        )
        print(json.dumps(results[-1]), flush=True)
    # The trailing summary is itself a --check-loadable baseline: bench.py
    # converts it into the per-size throughput metrics plus the efficiency
    # ratios (scaling_ppo_weak_eff_dN) the per-size lines cannot carry.
    print(json.dumps({"scaling": results}))


if __name__ == "__main__":
    main()
