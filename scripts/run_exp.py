"""Run a system experiment on the ambient JAX platform (TPU when available).

The sibling `cpu_run.py` forces the CPU backend for machines with no
accelerator; this launcher uses whatever platform JAX picks (the TPU on a
machine that has one) — used for long validation runs.

Usage:
    python scripts/run_exp.py --module stoix_tpu.systems.q_learning.ff_ddqn \
        --default default/anakin/default_ff_ddqn.yaml [override ...]
"""

from __future__ import annotations

import argparse
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))  # repo root for stoix_tpu
sys.path.insert(0, _HERE)  # scripts dir for cpu_run

from cpu_run import run_module  # noqa: E402


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--module", required=True)
    parser.add_argument("--default", required=True)
    parser.add_argument("rest", nargs="*", help="dotted overrides")
    args = parser.parse_args()
    run_module(args.module, args.default, args.rest)


if __name__ == "__main__":
    main()
