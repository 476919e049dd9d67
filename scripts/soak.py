"""Fault-injected resize soak (docs/DESIGN.md §2.14).

Drives repeated preempt -> shrink -> resume -> grow cycles END TO END on the
forced-CPU backend: each leg launches a real training subprocess under
`launcher.run_supervised(..., elastic=True)` with a `shrink:N`/`grow:N`
chaos spec armed, lets it vacate with the elastic-resize code (89), and lets
the elastic supervision relaunch it at the requested topology through the
emergency restore path. After EVERY leg the harness asserts the §2.14
contract, not just "it exited 0":

  * the resize request was consumed one-shot (a stale request would answer
    the NEXT leg's exit with the WRONG topology);
  * the hard exit left a schema-valid `flight_record.json`
    (observability/flightrec.validate_flight_record returns no problems);
  * survivors are digest-identical: `restore_report.json`'s post-transform
    leaf digests match the rescue manifest's for every leaf both sides hold
    (topology-bound leaves are re-placed and exempt by construction);
  * the relaunch's restore wall landed in the goodput ledger's `recovery`
    phase (`goodput.recovery_s > 0` in the completing incarnation's stats).

Usage:
    python scripts/soak.py [--cycles 2] [--devices 8] [--windows 3]
                           [--workdir DIR] [--timeout 600]

Exit 0 when every cycle upholds the contract; 1 with the failure list
otherwise. tests/test_elastic.py runs one cycle of this harness in its slow
lane; bench.py --elastic reuses `run_leg` for recovery-wall statistics.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import textwrap
import time
from typing import Any, Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# The training child: composed config -> run_anakin_experiment -> stats JSON.
# A separate process per incarnation because the XLA virtual device count is
# fixed at jax init — resizing REQUIRES a fresh process (exactly the
# production shape: the supervisor relaunches, never re-configures in place).
_CHILD = textwrap.dedent(
    """
    import json, os, sys
    sys.path.insert(0, {repo!r})
    import jax
    jax.config.update("jax_platforms", "cpu")
    stats_path = sys.argv[1]
    overrides = sys.argv[2:]
    from stoix_tpu.utils import config as cl
    from stoix_tpu.systems import runner as runner_mod
    from stoix_tpu.systems.ppo.anakin.ff_ppo import learner_setup
    cfg = cl.compose(
        cl.default_config_dir(), "default/anakin/default_ff_ppo.yaml", overrides
    )
    ret = runner_mod.run_anakin_experiment(cfg, learner_setup)
    with open(stats_path, "w") as f:
        json.dump(
            {{
                "final_return": float(ret),
                "devices": jax.device_count(),
                "goodput": runner_mod.LAST_RUN_STATS.get("goodput"),
            }},
            f,
        )
    print("SOAK_CHILD_OK", flush=True)
    """
)


def _base_overrides(workdir: str, windows: int) -> List[str]:
    return [
        "env=identity_game",
        "arch.total_num_envs=16",
        f"arch.num_updates={windows}",
        "arch.total_timesteps=~",
        f"arch.num_evaluation={windows}",
        "arch.num_eval_episodes=8",
        "arch.absolute_metric=False",
        "arch.evaluation_greedy=True",
        "system.rollout_length=4",
        "system.epochs=1",
        "system.num_minibatches=2",
        "logger.use_console=False",
        f"logger.base_exp_path={os.path.join(workdir, 'results')}",
        # The fleet layer supplies the emergency store the resize exit
        # secures; single-process agreement is trivially local.
        "arch.fleet.enabled=True",
        f"arch.fleet.emergency_dir={os.path.join(workdir, 'fleet_emergency')}",
    ]


def _child_env(devices: int) -> Dict[str, str]:
    """Environment of one training child: `devices` virtual CPU devices.

    One process per chip: the caller (bench.py --elastic) may already hold
    the accelerator, which belongs to one process at a time. The children are
    therefore pinned to the CPU backend here (and again in `_CHILD`) and never
    ask for it."""
    env = dict(os.environ)
    env.pop("STOIX_TPU_FAULT", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    flags = [
        flag
        for flag in env.get("XLA_FLAGS", "").split()
        if not flag.startswith("--xla_force_host_platform_device_count")
    ]
    flags.append(f"--xla_force_host_platform_device_count={devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    return env


def check_leg_artifacts(
    workdir: str,
    *,
    expect_action: str,
    expect_devices: int,
    stats: Dict[str, Any],
) -> List[str]:
    """The §2.14 post-leg contract (module docstring); returns the list of
    violations (empty = the leg upheld it)."""
    from stoix_tpu.observability import flightrec
    from stoix_tpu.resilience import elastic as elastic_lib
    from stoix_tpu.resilience import fleet as fleet_lib

    problems: List[str] = []
    emergency_dir = os.path.join(workdir, "fleet_emergency")

    # 1. One-shot consumption: no request may outlive the leg.
    if elastic_lib.read_resize_request(emergency_dir) is not None:
        problems.append(
            f"{elastic_lib.RESIZE_REQUEST_NAME} survived the leg — the next "
            f"rc-89 would relaunch at a STALE topology"
        )

    # 2. The hard exit's flight record is schema-valid and names rc 89.
    record_path = os.path.join(emergency_dir, flightrec.FLIGHT_RECORD_FILENAME)
    try:
        with open(record_path) as f:
            record = json.load(f)
    except (OSError, ValueError) as exc:
        problems.append(f"no readable flight record at {record_path}: {exc}")
        record = None
    if record is not None:
        for problem in flightrec.validate_flight_record(record):
            problems.append(f"flight record invalid: {problem}")
        if record.get("exit_code") != 89:
            problems.append(
                f"flight record exit_code {record.get('exit_code')!r}, want 89"
            )
        kinds = [e.get("kind") for e in record.get("events") or []]
        if "elastic_resize" not in kinds:
            problems.append(
                f"flight record events carry no elastic_resize (kinds: {kinds})"
            )

    # 3. Digest identity: the relaunch's restore report must echo the rescue
    # manifest's digest for every leaf both sides hold.
    report = fleet_lib.read_restore_report(emergency_dir)
    if report is None:
        problems.append(f"no {fleet_lib.RESTORE_REPORT_NAME} under {emergency_dir}")
    else:
        if float(report.get("recovery_wall_s") or 0.0) <= 0.0:
            problems.append(
                f"restore report recovery_wall_s "
                f"{report.get('recovery_wall_s')!r} not positive"
            )
        manifest_digests: Dict[str, str] = {}
        for manifest_dir in sorted(
            d for d in os.listdir(emergency_dir)
            if os.path.isdir(os.path.join(emergency_dir, d))
        ):
            manifest_path = os.path.join(
                emergency_dir, manifest_dir, fleet_lib.MANIFEST_NAME
            )
            try:
                with open(manifest_path) as f:
                    manifest_digests.update(json.load(f).get("digests") or {})
            except (OSError, ValueError):
                continue
        restored = dict(report.get("digests") or {})
        shared = sorted(set(manifest_digests) & set(restored))
        if not shared:
            problems.append(
                f"restore report and rescue manifest share no leaves "
                f"(manifest {len(manifest_digests)}, report {len(restored)})"
            )
        for key in shared:
            if restored[key] != manifest_digests[key]:
                problems.append(
                    f"survivor leaf {key} NOT digest-identical after the "
                    f"{expect_action} relaunch"
                )

    # 4. The completing incarnation ran the target topology and charged its
    # restore wall to the goodput ledger's recovery phase.
    if int(stats.get("devices") or 0) != expect_devices:
        problems.append(
            f"completing incarnation saw {stats.get('devices')} device(s), "
            f"want {expect_devices}"
        )
    goodput = dict(stats.get("goodput") or {})
    if float(goodput.get("recovery_s") or 0.0) <= 0.0:
        problems.append(
            f"goodput recovery_s {goodput.get('recovery_s')!r} not positive — "
            f"the relaunch wall was not attributed to recovery"
        )
    return problems


def run_leg(
    workdir: str,
    *,
    action: str,
    devices: int,
    windows: int = 3,
    fault_window: int = 1,
    max_relaunches: int = 2,
    extra_overrides: Optional[List[str]] = None,
) -> Dict[str, Any]:
    """One supervised leg: launch at `devices` with `{action}:{fault_window}`
    armed, let the elastic supervision relaunch at the requested topology,
    and check the contract. Returns {rc, wall_s, stats, problems, target}."""
    from stoix_tpu import launcher as launcher_lib
    from stoix_tpu.resilience import elastic as elastic_lib

    os.makedirs(workdir, exist_ok=True)
    child_path = os.path.join(workdir, "soak_child.py")
    with open(child_path, "w") as f:
        f.write(_CHILD.format(repo=REPO))
    stats_path = os.path.join(workdir, f"stats_{action}.json")
    try:
        os.remove(stats_path)
    except OSError:
        pass
    overrides = [
        *_base_overrides(workdir, windows),
        f"arch.fault_spec={action}:{fault_window}",
        *(extra_overrides or []),
    ]
    emergency_dir = os.path.join(workdir, "fleet_emergency")
    resume_overrides = [
        "logger.checkpointing.load_model=true",
        f"logger.checkpointing.load_args.load_path={emergency_dir}",
    ]
    target = elastic_lib.plan_resize(action, devices)
    t0 = time.perf_counter()
    rc = launcher_lib.run_supervised(
        [sys.executable, child_path, stats_path, *overrides],
        _child_env(devices),
        max_relaunches,
        resume_overrides,
        elastic=True,
        fleet_resume_path=emergency_dir,
        job_overrides=overrides,
    )
    wall_s = time.perf_counter() - t0
    problems: List[str] = []
    if rc != 0:
        problems.append(f"{action} leg finished rc {rc}, want 0")
    try:
        with open(stats_path) as f:
            stats = json.load(f)
    except (OSError, ValueError) as exc:
        stats = {}
        problems.append(f"no stats from the completing incarnation: {exc}")
    problems.extend(
        check_leg_artifacts(
            workdir, expect_action=action, expect_devices=target, stats=stats
        )
    )
    return {
        "rc": rc,
        "wall_s": wall_s,
        "stats": stats,
        "problems": problems,
        "target": target,
    }


def run_cycle(
    workdir: str, *, devices: int = 8, windows: int = 3, timeout: float = 600.0
) -> List[str]:
    """One full preempt -> shrink -> resume -> grow cycle; returns the
    violation list (empty = the cycle passed)."""
    del timeout  # per-leg walls are bounded by the tiny window counts
    problems: List[str] = []
    shrink = run_leg(workdir, action="shrink", devices=devices, windows=windows)
    problems.extend(f"[shrink] {p}" for p in shrink["problems"])
    # The grow leg starts where the shrink leg landed and relaunches back up;
    # the restore then comes from the SHRUNK incarnation's emergency store.
    grow = run_leg(
        workdir, action="grow", devices=shrink["target"], windows=windows
    )
    problems.extend(f"[grow] {p}" for p in grow["problems"])
    if not grow["problems"] and grow["target"] != devices:
        problems.append(
            f"[grow] cycle did not return to {devices} device(s) "
            f"(landed at {grow['target']})"
        )
    return problems


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--cycles", type=int, default=2)
    parser.add_argument("--devices", type=int, default=8)
    parser.add_argument("--windows", type=int, default=3)
    parser.add_argument(
        "--workdir", default=None,
        help="soak working directory (default: a fresh temp dir)",
    )
    parser.add_argument("--timeout", type=float, default=600.0)
    args = parser.parse_args(argv)

    workdir = args.workdir or tempfile.mkdtemp(prefix="stoix_tpu_soak_")
    failures: List[str] = []
    for cycle in range(args.cycles):
        cycle_dir = os.path.join(workdir, f"cycle{cycle}")
        problems = run_cycle(
            cycle_dir, devices=args.devices, windows=args.windows,
            timeout=args.timeout,
        )
        status = "PASS" if not problems else "FAIL"
        print(  # noqa: STX002 — the soak's stdout contract
            json.dumps(
                {"cycle": cycle, "status": status, "problems": problems}
            ),
            flush=True,
        )
        failures.extend(f"cycle {cycle}: {p}" for p in problems)
    print(  # noqa: STX002 — the soak's stdout contract
        json.dumps(
            {
                "cycles": args.cycles,
                "devices": args.devices,
                "status": "PASS" if not failures else "FAIL",
                "failures": failures,
                "workdir": workdir,
            }
        ),
        flush=True,
    )
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
