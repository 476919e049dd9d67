"""Run a system experiment (or a sweep) on the forced-CPU backend.

Sets JAX_PLATFORMS=cpu (and the virtual device count) before jax is
imported, like tests/conftest.py. Used for hyperparameter sweeps and long
validation runs on machines with no accelerator. What it prints are CPU
numbers; they are never device measurements.

Usage:
    python scripts/cpu_run.py --module stoix_tpu.systems.q_learning.ff_dqn \
        --default default/anakin/default_ff_dqn.yaml \
        [--devices 8] [override ...]
    python scripts/cpu_run.py --sweep [--devices 8] -- <stoix_tpu.sweep args>
"""

from __future__ import annotations

import argparse
import os
import sys


def _force_cpu(devices: int) -> None:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={devices}"
    ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def arm_watchdog_from_env() -> None:
    """Opt-in hard exit if the run outlives RUN_WATCHDOG_MINUTES (<= 0 or
    unset = disabled). A wedged device runtime can hang a call forever; a
    stuck process also blocks any serial experiment queue behind it, so a
    structured timeout line + exit beats waiting. Covers both the single-run
    and --sweep paths (armed from main())."""
    import json
    import threading

    try:
        minutes = float(os.environ.get("RUN_WATCHDOG_MINUTES", "0") or "0")
    except ValueError:
        minutes = 0.0
    if minutes <= 0.0:
        return

    def _fire() -> None:
        print(
            json.dumps({"error": "watchdog_timeout", "minutes": minutes}),
            flush=True,
        )
        os._exit(124)

    timer = threading.Timer(minutes * 60.0, _fire)
    timer.daemon = True
    timer.start()


def run_module(module: str, default: str, overrides: list) -> None:
    """Compose the config, run the system's run_experiment, print a JSON line.

    Shared by this CPU launcher and scripts/run_exp.py (ambient platform).
    """
    import importlib
    import json

    from stoix_tpu.utils import config as config_lib

    arm_watchdog_from_env()
    config = config_lib.compose(config_lib.default_config_dir(), default, overrides)
    mod = importlib.import_module(module)
    score = mod.run_experiment(config)
    print(json.dumps({"module": module, "final_eval_return": float(score)}), flush=True)


def main() -> None:
    # Sweep mode: everything except the launcher's own flags belongs to
    # stoix_tpu.sweep's parser, in the order given (a shared argparse would
    # reorder interleaved flags and positionals).
    argv = sys.argv[1:]
    if "--sweep" in argv:
        argv.remove("--sweep")
        devices = 8
        if "--devices" in argv:
            i = argv.index("--devices")
            devices = int(argv[i + 1])
            del argv[i : i + 2]
        _force_cpu(devices)
        arm_watchdog_from_env()
        from stoix_tpu import sweep

        sweep.main(argv)
        return

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--module", required=True)
    parser.add_argument("--default", required=True)
    parser.add_argument("--devices", type=int, default=8)
    parser.add_argument("rest", nargs="*", help="dotted overrides")
    args = parser.parse_args()

    _force_cpu(args.devices)
    run_module(args.module, args.default, args.rest)


if __name__ == "__main__":
    main()
