#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json, on the chips of this machine:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the cell's chips. It composes the cell's
configuration and traffic into the system's config and calls the system
module's own `run_experiment` (the path `main()` takes); set-up ends with the
cell's warm-up window, the measured interval is `--seconds` long, and the run
is ended through the program's own graceful stop. The last line of stdout is
one JSON object: correct, attempted, failed, metrics, device (with `--trace 1`
also breakdown and trace, what happened to the profiler sessions), and last
compared, each number the reference compared beside its limit. `--trace 0`
gives the end-to-end metrics, `--trace 1` the per-layer ones. What the run
saw besides goes to stderr.

There is no CPU mode. Without a TPU, or with another number of chips than
the cell's, it exits 2 and prints no result line.
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device", "breakdown", "trace", "compared")


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args()

    # The program resolves its config, compile cache (<checkout>/xla_cache
    # unless JAX_COMPILATION_CACHE_DIR is set), results/ and the C++ pool's
    # build from the checkout: run from its root, import from it.
    os.chdir(ROOT)
    sys.path.insert(0, ROOT)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if not os.path.isdir(os.path.join(ROOT, "stoix_tpu")):
        print(f"no stoix_tpu package under {ROOT}: nothing to measure", file=sys.stderr)
        return 2

    from benchmarks.harness import cell_runner, loader

    cell = loader.load_cell(args.workload)
    try:
        result = cell_runner.run_cell(
            cell, args.seed, args.seconds, bool(args.trace), _PROCESS_START
        )
    except cell_runner.DeviceMismatch as exc:
        print(str(exc), file=sys.stderr)
        return 2
    extra = {k: v for k, v in result.items() if k not in RESULT_KEYS}
    print("[bench] " + json.dumps(extra, default=str), file=sys.stderr, flush=True)
    sys.stderr.flush()
    print(json.dumps({k: result[k] for k in RESULT_KEYS if k in result}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
