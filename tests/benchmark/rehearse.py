#!/usr/bin/env python3
"""Test-only entry: one cell's driver end to end on the CPU backend at a
tiny size — the rehearsal to make before any chip call. Not an option of the
benchmark's command, which has no CPU mode.

    python3 tests/benchmark/rehearse.py --workload <cell> --seconds 5 --trace 0 \
        arch.total_num_envs=8 arch.num_eval_episodes=4

Virtual CPU devices stand in for the cell's chips (4 for a four-chip cell),
so meshes and sharding rules are exercised; nothing printed here is a device
number.
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=5.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--scratch", default=None)
    parser.add_argument("overrides", nargs="*")
    args = parser.parse_args()

    sys.path.insert(0, ROOT)
    os.chdir(ROOT)
    from benchmarks.harness import loader

    cell = loader.load_cell(args.workload)
    # A tiny run never reaches a learn_check's step budget.
    cell = cell._replace(spec={**cell.spec, "learn_check": None})
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={cell.chips}"

    from benchmarks.harness import cell_runner

    result = cell_runner.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), _PROCESS_START,
        require_platform="cpu", extra_overrides=args.overrides, scratch_dir=args.scratch,
    )
    print(json.dumps(result, default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
