"""Batch launcher: fan out (system x env x seed) runs to SLURM or local shells.

The reference uses a submitit-based SLURM launcher
(reference stoix/slurm_launcher.py:40-83, configs/launcher/slurm.yaml) taking
the cartesian product of algorithm files, environments, and seeds. submitit is
not a dependency here; this launcher emits/submits plain `sbatch` scripts (or
runs locally with `--local`), and for multi-host TPU pods it injects the
`jax.distributed` coordination env vars consumed by
stoix_tpu.parallel.maybe_initialize_distributed.

Usage:
    python -m stoix_tpu.launcher \
        --systems stoix_tpu.systems.ppo.anakin.ff_ppo stoix_tpu.systems.sac.ff_sac \
        --envs cartpole pendulum --seeds 0 1 2 \
        [--local | --submit | --preflight-only [--changed-only]] \
        [--nodes 1] [--time 04:00:00] [--partition tpu] [overrides...]

    python -m stoix_tpu.launcher serve \
        arch.serve.checkpoint.path=checkpoints/<uid>/<model> \
        [--config default/serve.yaml] [--duration S] [--loadgen] [overrides...]

`serve` (docs/DESIGN.md §2.8) starts the in-process policy server
(stoix_tpu/serve): composes the serve root config, restores the checkpoint's
actor through the topology-elastic path, warms every batch bucket under the
compile watchdog, and serves until SIGINT/SIGTERM (or `--duration S`).
`--loadgen` instead drives the server with the configured open-loop load
generator and prints ONE JSON latency report line (the bench payload body),
then exits — the CI smoke mode.

`--preflight-only` (docs/DESIGN.md §2.4) runs the launch-hardening preflight —
the static-analysis gate, then ONE subprocess-isolated backend probe for the
host, then config cross-validation for every (system x env x seed) job
against the probed topology — prints a one-page report, and exits 0 (all
pass) or 1. Wire it into CI or a SLURM prolog so a wedged chip or a bad
config fails the batch in seconds instead of after scheduling.
`--changed-only` restricts the lint stage to git-changed files so the prolog
stays fast as the rule count grows.

`--supervise N` (docs/DESIGN.md §2.6 + §2.9) makes `--local` runs elastic: a
job that exits with the fleet-partition code (87, resilience/fleet.py — a
peer host died and the survivors secured a local-shard emergency checkpoint)
is relaunched up to N times at the surviving topology with resume overrides
appended (`logger.checkpointing.load_model=true` + the emergency-store
load_path); topology-elastic restore brings the params back bit-identical on
the shrunk mesh. A job that exits with the state-corruption code (88,
resilience/integrity.py — the integrity sentinel proved a silent replica
mismatch or a failed determinism probe) is relaunched with the resume
overrides the quarantine file records, restoring the newest DIGEST-VERIFIED
checkpoint; the offending host stays named in `--quarantine-file` for the
scheduler to drain. Any other exit code is final — 87 and 88 are the only
codes that mean "the run is healthy, the hardware was not".
"""

from __future__ import annotations

import argparse
import itertools
import os
import re
import shlex
import subprocess
import sys
from typing import Any, List, Optional

from stoix_tpu.observability import get_logger

SBATCH_TEMPLATE = """#!/bin/bash
#SBATCH --job-name={job_name}
#SBATCH --output={log_dir}/{job_name}_%j.out
#SBATCH --nodes={nodes}
#SBATCH --ntasks-per-node=1
#SBATCH --time={time}
#SBATCH --signal=TERM@{preempt_grace}
{partition_line}{extra_lines}
# Multi-host JAX coordination: process 0's host is the coordinator. The
# per-task process id must be read INSIDE the srun'd command (the batch shell's
# SLURM_PROCID is always 0).
export JAX_COORDINATOR_ADDRESS="$(scontrol show hostnames "$SLURM_JOB_NODELIST" | head -n1):12345"
export JAX_NUM_PROCESSES="$SLURM_NNODES"
{cache_line}
srun bash -c 'JAX_PROCESS_ID="$SLURM_PROCID" python -m {module} {overrides}'
"""


def _default_yaml_for(module: str) -> Optional[str]:
    """The root config a system module composes in its main() — every system
    entry point carries exactly one `default/{anakin,sebulba}/*.yaml` literal.
    None when the module cannot be located or breaks the convention."""
    import importlib.util

    try:
        spec = importlib.util.find_spec(module)
    except (ImportError, ModuleNotFoundError, ValueError):
        return None
    if spec is None or not spec.origin:
        return None
    try:
        with open(spec.origin) as f:
            source = f.read()
    except OSError:
        return None
    match = re.search(r"default/(?:anakin|sebulba)/[\w.\-]+\.yaml", source)
    return match.group(0) if match else None


def run_preflight_only(jobs: List[dict], changed_only: bool = False) -> int:
    """Static-analysis gate + ONE backend probe for the host + per-job config
    cross-validation against the probed topology; prints the one-page report.
    Returns the process exit code (0 = every stage passed)."""
    from stoix_tpu.resilience import preflight
    from stoix_tpu.utils import config as config_lib

    # Static-analysis gate FIRST (docs/DESIGN.md §2.5): pure-AST, no jax
    # import, milliseconds — a SLURM prolog catches an axis-name typo
    # (STX007), a misshard (STX010), or a typo'd config read (STX009) before
    # the backend probe spends its timeout budget, let alone before burning a
    # TPU allocation.
    from stoix_tpu import analysis

    lint_paths = None
    lint_scope = "files clean"
    with_tree_rules = True
    if changed_only:
        changed = analysis.changed_paths()
        if changed:
            # Tree-scoped rules need the full file set (see --changed-only in
            # the analysis CLI). git-unavailable AND a clean checkout (the
            # CI/prolog case — the bad change is already committed) both
            # fall back to the full scan: a vacuous 0-file pass is no gate.
            lint_paths = changed
            lint_scope = "changed files clean"
            with_tree_rules = False
    findings, n_files = analysis.run_paths(
        lint_paths, with_tree_rules=with_tree_rules
    )
    lint_errors, _lint_warnings = analysis.split_severity(findings)
    if lint_errors:
        # Short-circuit: the gate already failed the batch, so do not spend
        # the probe's multi-attempt backoff budget (a wedged backend would
        # hold the prolog for minutes before reporting a typo lint catches
        # in milliseconds).
        report = preflight.PreflightReport()
        rules = ", ".join(sorted({f.rule for f in lint_errors}))
        report.add(
            "static-analysis", "fail",
            f"{len(lint_errors)} finding(s) [{rules}]; first: "
            f"{lint_errors[0].render()}",
        )
        report.add("backend_probe", "skip", "static-analysis failed — probe not attempted")
        report.add("config_validation", "skip", "static-analysis failed")
        print(report.render())  # noqa: STX002 — --preflight-only's stdout contract
        return 1

    configs = []
    report_extra = []
    for job in jobs:
        yaml_file = _default_yaml_for(job["module"])
        if yaml_file is None:
            report_extra.append(
                (f"config[{job['name']}]", "skip",
                 f"could not derive a default yaml for {job['module']}")
            )
            continue
        try:
            config = config_lib.compose(
                config_lib.default_config_dir(), yaml_file, job["overrides"]
            )
        except Exception as exc:  # noqa: BLE001 — a bad override IS a finding
            report_extra.append(
                (f"config[{job['name']}]", "fail",
                 f"compose failed: {type(exc).__name__}: {exc}")
            )
            continue
        configs.append((job["name"], config))

    report = preflight.run_preflight(configs if configs else None)
    for row in report_extra:
        report.add(*row)
    report.add(
        "static-analysis", "pass",
        f"{n_files} {lint_scope} ({len(analysis.get_rules())} rules)",
    )
    # Concurrency-model visibility (docs/DESIGN.md §2.5): the STX014-017
    # family is only as good as the threadmodel under it — a refactor that
    # renames the spawn idioms out from under the AST patterns would turn
    # the whole rule family into a permanent green no-op. Counting what the
    # model actually saw makes a silently-empty model a preflight FAILURE
    # on a full scan (a changed-only scan may legitimately see no threads).
    from stoix_tpu.analysis import threadmodel

    tstats = threadmodel.repo_summary(lint_paths or ["stoix_tpu"])
    t_detail = (
        f"{tstats['spawns']} thread spawn(s), {tstats['locks']} lock(s), "
        f"{tstats['obligations']} completion obligation(s) modeled"
    )
    if tstats["spawns"] == 0 and lint_paths is None:
        report.add(
            "concurrency-model", "fail",
            f"EMPTY model on a full scan ({t_detail}) — the STX014-017 "
            f"family is blind; the spawn-site patterns no longer match the "
            f"code",
        )
    else:
        report.add("concurrency-model", "pass", t_detail)
    # Ops-contract visibility (docs/DESIGN.md §2.5): same deal for the
    # STX019-022 family — it sees only what the opsmodel sees, and a
    # refactor that renamed `get_registry()`/the KV verbs/`os._exit` idioms
    # out from under the AST patterns would green the gate forever. An
    # empty model on a full scan is a preflight FAILURE.
    from stoix_tpu.analysis import opsmodel

    ostats = opsmodel.repo_summary(lint_paths or ["stoix_tpu"])
    o_detail = (
        f"{ostats['series']} metric series, {ostats['kv_writes']} KV "
        f"write(s)/{ostats['kv_reads']} read(s), {ostats['exit_sites']} "
        f"hard-exit site(s), {ostats['fault_sites']} fault-spec site(s) "
        f"modeled"
    )
    if (
        ostats["series"] == 0
        and ostats["exit_sites"] == 0
        and ostats["kv_writes"] == 0
        and lint_paths is None
    ):
        report.add(
            "ops-contracts", "fail",
            f"EMPTY model on a full scan ({o_detail}) — the STX019-022 "
            f"family is blind; the metric/KV/exit idioms no longer match "
            f"the code",
        )
    else:
        report.add("ops-contracts", "pass", o_detail)
    # The report IS this mode's output contract (CI / SLURM prolog logs
    # capture stdout), like bench.py's JSON lines.
    print(report.render())  # noqa: STX002 — --preflight-only's stdout contract
    return 0 if report.ok else 1


def _elastic_child_env(
    env: Optional[dict],
    platform: Optional[str] = None,
    device_count: Optional[int] = None,
) -> dict:
    """Child environment for an elastic relaunch: the armed fault is consumed
    (a `shrink:1` that re-fired every incarnation would relaunch-loop the
    budget away), and on the cpu backend the virtual device count is forced
    to the target so the relaunch actually RUNS the smaller/larger topology
    (the fault-injected soak's mechanism; real TPU backends ignore it)."""
    child = dict(env if env is not None else os.environ)
    child.pop("STOIX_TPU_FAULT", None)
    if platform == "cpu" and device_count:
        flags = [
            flag
            for flag in child.get("XLA_FLAGS", "").split()
            if not flag.startswith("--xla_force_host_platform_device_count")
        ]
        flags.append(f"--xla_force_host_platform_device_count={int(device_count)}")
        child["XLA_FLAGS"] = " ".join(flags)
    return child


def run_supervised(
    cmd: List[str],
    env: Optional[dict],
    max_relaunches: int,
    resume_overrides: List[str],
    quarantine_file: Optional[str] = None,
    elastic: bool = False,
    fleet_resume_path: Optional[str] = None,
    job_overrides: Optional[List[str]] = None,
) -> int:
    """Supervision loop for one job (docs/DESIGN.md §2.6 + §2.9 + §2.14).
    Two exit codes mean "the run is healthy, relaunch-and-resume":

      * 87 (fleet partition, resilience/fleet.py) — a peer died and the
        survivors secured a local-shard emergency checkpoint; relaunch with
        `resume_overrides` so topology-elastic restore resumes at whatever
        topology survived. With `elastic`, the backend is RE-PROBED first
        and the mesh re-derived for the devices actually present
        (resilience/elastic.survivor_overrides) instead of replaying the
        dead topology.
      * 88 (state corruption, resilience/integrity.py) — the integrity
        sentinel proved silent corruption (replica fingerprint mismatch or a
        failed determinism probe) and recorded the offending host(s) in the
        quarantine file; relaunch with the quarantine record's resume
        overrides so the run restores the newest DIGEST-VERIFIED checkpoint.
        The quarantine file is the scheduler/operator's drain list — this
        loop cannot evict a host from its own allocation, but it names the
        offender with proof and keeps the job moving.
      * 89 (elastic resize, resilience/elastic.py) — ONLY with `elastic`: the
        run deliberately vacated for a different topology, leaving a
        `resize_request.json` next to the emergency store naming the target
        device count and the relaunch overrides (re-derived mesh + population
        re-placement). The request is consumed one-shot and the relaunch
        restores through the emergency path at the requested topology.
        Without `elastic`, 89 is final — fixed-topology supervision is
        bit-identical to what it was before this flag existed.

    Every OTHER exit code (clean 0, watchdog 86, crash 1) is final. Returns
    the final exit code.

    One process per chip: the accelerator belongs to one process at a time,
    and here that process is the CHILD. This supervising parent must never
    make a backend call (`jax.devices()`, any array op) — it imports only
    host-side modules (observability, resilience, config; importing jax
    initialises no backend), and the elastic re-probe below runs in its own
    short-lived subprocess BETWEEN incarnations, when no child holds the
    chip. A parent that touched jax would hold the chip and every child
    would fail or hang at backend init."""
    from stoix_tpu.resilience import elastic as elastic_lib
    from stoix_tpu.resilience.exit_codes import (
        EXIT_CODE_ELASTIC_RESIZE,
        EXIT_CODE_FAILURE,
        EXIT_CODE_OK,
        EXIT_CODE_STALL,
        EXIT_CODE_USAGE,
        REGISTRY,
    )
    from stoix_tpu.resilience.fleet import EXIT_CODE_FLEET_PARTITION
    from stoix_tpu.resilience.integrity import (
        EXIT_CODE_STATE_CORRUPTION,
        corruption_resume_overrides,
        read_quarantine,
    )

    log = get_logger("stoix_tpu.launcher")
    handled = {EXIT_CODE_FLEET_PARTITION, EXIT_CODE_STATE_CORRUPTION}
    if elastic:
        handled.add(EXIT_CODE_ELASTIC_RESIZE)
    # Every registered code is dispatched here by NAME — relaunched (above)
    # or explicitly final (below) — so registering a new recovery code
    # without teaching this loop about it fails STX021's coverage check
    # instead of surfacing as an unexplained final exit. The runtime half
    # of the same contract: an rc in neither set can only be an
    # UNREGISTERED code (signal deaths, scheduler kills), logged as such.
    final_codes = {
        EXIT_CODE_OK: "clean finish",
        EXIT_CODE_FAILURE: "unrecoverable failure — a relaunch would replay it",
        EXIT_CODE_USAGE: "usage error — operator input, not run health",
        EXIT_CODE_STALL: "watchdog shot a wedged run — triage before retrying",
        EXIT_CODE_ELASTIC_RESIZE: "elastic resize without --elastic — final",
    }
    uncovered = set(REGISTRY) - set(final_codes) - handled
    assert not uncovered, f"unhandled registered exit codes: {sorted(uncovered)}"
    relaunches = 0
    extra: List[str] = []
    child_env = env
    while True:
        # Each relaunch is a FRESH subprocess, and within any process the
        # run start calls observability.configure(), which resets the
        # process-wide HealthMonitor and flight recorder — so a relaunched
        # incarnation never inherits stale heartbeat state that would read
        # as an instant stall (docs/DESIGN.md §2.13; pinned by
        # tests/test_opsplane.py).
        rc = subprocess.run(cmd + extra, env=child_env).returncode
        if rc not in handled:
            disposition = final_codes.get(
                rc,
                "unregistered code (signal death or scheduler kill?)"
                if rc not in REGISTRY
                else REGISTRY[rc].meaning,
            )
            if relaunches:
                log.info(
                    "[launcher] job finished (rc %d: %s) after %d supervised "
                    "relaunch(es)", rc, disposition, relaunches,
                )
            return rc
        reason = {
            EXIT_CODE_FLEET_PARTITION: "fleet partition",
            EXIT_CODE_STATE_CORRUPTION: "state corruption",
            EXIT_CODE_ELASTIC_RESIZE: "elastic resize",
        }[rc]
        if relaunches >= max_relaunches:
            log.error(
                "[launcher] %s exit (rc %d) with the relaunch budget (%d) "
                "exhausted — giving up", reason, rc, max_relaunches,
            )
            return rc
        relaunches += 1
        if rc == EXIT_CODE_ELASTIC_RESIZE:
            request = elastic_lib.consume_resize_request(
                fleet_resume_path or ""
            )
            if request is None:
                log.error(
                    "[launcher] elastic resize exit (rc %d) but no "
                    "%s under %s — giving up (the dying incarnation failed "
                    "before the hand-off was written)",
                    rc, elastic_lib.RESIZE_REQUEST_NAME, fleet_resume_path,
                )
                return rc
            target = int(request.get("target_devices") or 0)
            # The armed fault was consumed by this exit; `arch.fault_spec=~`
            # outranks any job-override spec so the relaunch trains instead
            # of re-firing the same resize every incarnation.
            extra = [
                *resume_overrides,
                *[str(o) for o in request.get("overrides") or []],
                "arch.fault_spec=~",
            ]
            child_env = _elastic_child_env(
                env, platform=request.get("platform"), device_count=target
            )
            log.warning(
                "[launcher] elastic %s: relaunching at %d device(s) "
                "(from %s, window %s)",
                request.get("action"), target,
                request.get("from_devices"), request.get("window"),
            )
        elif rc == EXIT_CODE_FLEET_PARTITION:
            extra = list(resume_overrides)
            if elastic:
                # Re-probe what actually survived the partition and re-derive
                # the mesh for it — never replay the dead topology.
                from stoix_tpu.resilience import preflight

                try:
                    probe = preflight.probe_backend()
                    extra = extra + elastic_lib.survivor_overrides(
                        probe.device_count, list(job_overrides or [])
                    )
                    child_env = _elastic_child_env(env)
                    log.warning(
                        "[launcher] elastic partition recovery: %d %s "
                        "device(s) survived; relaunching with re-derived mesh",
                        probe.device_count, probe.platform,
                    )
                except Exception as exc:  # noqa: STX003 — a failed re-probe degrades to the fixed-topology relaunch rather than killing a recoverable job
                    log.error(
                        "[launcher] elastic re-probe failed (%s); relaunching "
                        "at the configured topology", exc,
                    )
        else:
            quarantined = read_quarantine(quarantine_file or "").get("quarantined") or []
            if quarantined:
                latest = quarantined[-1]
                log.error(
                    "[launcher] QUARANTINE: process(es) %s (device(s) %s) "
                    "flagged for %s at step %s — recorded in %s; drain them "
                    "before the budget runs out",
                    latest.get("processes"), latest.get("devices"),
                    latest.get("kind"), latest.get("step"), quarantine_file,
                )
            extra = corruption_resume_overrides(quarantine_file or "")
            if not extra:
                log.warning(
                    "[launcher] corruption exit with no recorded resume "
                    "overrides (checkpointing was off?) — relaunching FRESH"
                )
        log.warning(
            "[launcher] %s (rc %d): relaunching (%d/%d)%s",
            reason, rc, relaunches, max_relaunches,
            f" with {' '.join(extra)}" if extra else "",
        )


def loop_main(argv: List[str]) -> int:
    """`launcher.py loop` (docs/DESIGN.md §2.15): run the closed
    train→serve→experience loop from a composed loop config and print ONE
    JSON report line. Returns the process exit code."""
    import json

    from stoix_tpu.utils import config as config_lib

    parser = argparse.ArgumentParser(
        prog="stoix_tpu.launcher loop",
        description="closed train→serve→experience loop (stoix_tpu/loop)",
    )
    parser.add_argument(
        "--config",
        default="default/loop.yaml",
        help="loop root yaml under stoix_tpu/configs (default: default/loop.yaml)",
    )
    parser.add_argument(
        "--frozen",
        action="store_true",
        help="control arm: identical traffic and ingest, learner never "
        "updates and nothing is published (the bench --loop baseline)",
    )
    parser.add_argument(
        "--duration",
        type=float,
        default=None,
        metavar="S",
        help="override arch.loop.traffic.duration_s",
    )
    parser.add_argument("overrides", nargs="*", help="key=value overrides")
    args = parser.parse_args(argv)

    overrides = list(args.overrides)
    if args.duration is not None:
        overrides.append(f"arch.loop.traffic.duration_s={args.duration}")
    config = config_lib.compose(
        config_lib.default_config_dir(), args.config, overrides
    )
    from stoix_tpu.loop import run_loop
    from stoix_tpu.resilience import faultinject

    # Arm the chaos plan exactly like the serve/train entry points (env var
    # wins over arch.fault_spec): the §2.15 drill arms
    # `replica_kill:N,replica_slow:S,feedback_stall:S` here.
    faultinject.configure((config.get("arch") or {}).get("fault_spec"))
    from stoix_tpu.observability import get_status_board, server_from_config

    ops_server = server_from_config(dict(config.arch.serve.get("http") or {}))
    get_status_board().update(
        {"run_id": "loop", "architecture": "loop", "system": "closed-loop"}
    )
    try:
        report = run_loop(config, frozen=args.frozen)
        # The JSON line IS this mode's output contract, like serve --loadgen.
        print(json.dumps(report), flush=True)  # noqa: STX002 — loop stdout contract
    finally:
        if ops_server is not None:
            ops_server.close()
    return 1 if report.get("silent_drops") else 0


def serve_main(argv: List[str]) -> int:
    """`launcher.py serve` (docs/DESIGN.md §2.8): run the policy server from
    a composed serve config. Returns the process exit code."""
    import json
    import signal
    import time

    from stoix_tpu.utils import config as config_lib

    parser = argparse.ArgumentParser(
        prog="stoix_tpu.launcher serve",
        description="serve a trained policy (stoix_tpu/serve)",
    )
    parser.add_argument(
        "--config",
        default="default/serve.yaml",
        help="serve root yaml under stoix_tpu/configs (default: default/serve.yaml)",
    )
    parser.add_argument(
        "--duration",
        type=float,
        default=None,
        metavar="S",
        help="serve for S seconds then exit cleanly (default: until SIGINT/SIGTERM)",
    )
    parser.add_argument(
        "--loadgen",
        action="store_true",
        help="drive the server with the arch.serve.loadgen open-loop load "
        "generator, print ONE JSON latency report line, and exit (CI smoke)",
    )
    parser.add_argument("overrides", nargs="*", help="key=value overrides")
    args = parser.parse_args(argv)

    config = config_lib.compose(
        config_lib.default_config_dir(), args.config, args.overrides
    )
    from stoix_tpu.resilience import faultinject
    from stoix_tpu.serve import PolicyServer, run_loadgen

    # Arm the chaos plan exactly like the training entry points do (env var
    # wins over arch.fault_spec): `STOIX_TPU_FAULT=swap_poison` must reach
    # the hot-swap canary (docs/DESIGN.md §2.9) when serving standalone.
    faultinject.configure((config.get("arch") or {}).get("fault_spec"))
    log = get_logger("stoix_tpu.launcher")
    serve_cfg = config.arch.serve
    # Ops plane (docs/DESIGN.md §2.13): start the endpoints BEFORE warmup so
    # /healthz and /statusz answer during the first compile. The serve config
    # has no `logger` block, so the switch lives at `arch.serve.http`.
    from stoix_tpu.observability import get_status_board, server_from_config

    ops_server = server_from_config(dict(serve_cfg.get("http") or {}))
    get_status_board().update(
        {"run_id": "serve", "architecture": "serve", "system": "policy-server"}
    )
    server = PolicyServer.from_config(config)
    stop_requested = {"flag": False}

    def _request_stop(_signum: int, _frame: Any) -> None:
        stop_requested["flag"] = True

    previous_handlers = {}
    for signum in (signal.SIGINT, signal.SIGTERM):
        try:
            previous_handlers[signum] = signal.signal(signum, _request_stop)
        except (ValueError, OSError):  # non-main thread / unsupported platform
            pass
    try:
        with server:
            if args.loadgen:
                loadgen_cfg = serve_cfg.loadgen
                report = run_loadgen(
                    server,
                    offered_qps=float(loadgen_cfg.offered_qps),
                    duration_s=float(loadgen_cfg.duration_s),
                )
                # The JSON line IS this mode's output contract (CI smoke),
                # like bench.py's payload lines.
                print(json.dumps(report), flush=True)  # noqa: STX002 — serve --loadgen stdout contract
            else:
                log.info(
                    "[serve] serving (step %d%s) — Ctrl-C to stop",
                    server.watcher.current_step if server.watcher else -1,
                    f", for {args.duration:.0f}s" if args.duration else "",
                )
                deadline = (
                    time.perf_counter() + args.duration if args.duration else None
                )
                while not stop_requested["flag"]:
                    if deadline is not None and time.perf_counter() >= deadline:
                        break
                    time.sleep(0.2)
                log.info(
                    "[serve] stopping: %s", server.telemetry.slo_snapshot()
                )
            telemetry_dir = serve_cfg.get("telemetry_dir")
            if telemetry_dir:
                path = server.telemetry.export(str(telemetry_dir))
                log.info("[serve] SLO metrics exported to %s", path)
    finally:
        if ops_server is not None:
            ops_server.close()
        for signum, handler in previous_handlers.items():
            signal.signal(signum, handler)
    return 0


def build_jobs(args: argparse.Namespace) -> List[dict]:
    jobs = []
    for module, env, seed in itertools.product(args.systems, args.envs, args.seeds):
        name = f"{module.rsplit('.', 1)[-1]}_{env}_s{seed}"
        overrides = [f"env={env}", f"arch.seed={seed}", *args.overrides]
        jobs.append({"name": name, "module": module, "overrides": overrides})
    return jobs


def main(argv: List[str] | None = None) -> None:
    argv = list(sys.argv[1:]) if argv is None else list(argv)
    if argv and argv[0] == "serve":
        # Subcommand dispatch: `launcher.py serve [...]` is the serving entry
        # point (docs/DESIGN.md §2.8); the batch-launch surface is unchanged.
        sys.exit(serve_main(argv[1:]))
    if argv and argv[0] == "loop":
        # `launcher.py loop [...]`: the closed train→serve→experience loop
        # (docs/DESIGN.md §2.15).
        sys.exit(loop_main(argv[1:]))
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--systems", nargs="+", required=True, help="module paths")
    parser.add_argument("--envs", nargs="+", required=True, help="env group names")
    parser.add_argument("--seeds", nargs="+", type=int, default=[0])
    parser.add_argument("--local", action="store_true", help="run sequentially here")
    parser.add_argument("--submit", action="store_true", help="sbatch immediately")
    parser.add_argument(
        "--preflight-only",
        action="store_true",
        help="run the launch-hardening preflight (subprocess backend probe + "
        "per-job config cross-validation) and exit 0/1 with a one-page "
        "report — no jobs are run or submitted (CI / SLURM prolog hook)",
    )
    parser.add_argument(
        "--changed-only",
        action="store_true",
        help="with --preflight-only: lint only the .py files git reports "
        "changed vs HEAD (the analysis CLI's --changed-only selection), so "
        "the prolog stays fast as the rule count grows; full scan when git "
        "is unavailable",
    )
    parser.add_argument(
        "--supervise",
        type=int,
        default=0,
        metavar="N",
        help="with --local: relaunch a job up to N times when it exits with "
        "the fleet-partition code (87 — a peer host died and a local-shard "
        "emergency checkpoint was secured; stoix_tpu/resilience/fleet.py) "
        "or the state-corruption code (88 — the integrity sentinel proved "
        "silent corruption and quarantined the offender; "
        "stoix_tpu/resilience/integrity.py), appending the matching resume "
        "overrides so the relaunch restores the right store. 0 (default) "
        "disables supervision.",
    )
    parser.add_argument(
        "--elastic",
        action="store_true",
        help="with --supervise: topology-elastic relaunch policy "
        "(stoix_tpu/resilience/elastic.py, docs/DESIGN.md §2.14). An "
        "elastic-resize exit (rc 89) consumes the run's resize_request.json "
        "and relaunches at the REQUESTED device count with re-derived mesh "
        "axes + population re-placement overrides; a fleet-partition exit "
        "(rc 87) re-probes the backend and relaunches at whatever topology "
        "actually survived instead of replaying the dead one. Off (default): "
        "rc 89 is final and supervision is bit-identical to fixed-topology "
        "behavior.",
    )
    parser.add_argument(
        "--fleet-resume-path",
        default=os.path.join("checkpoints", "fleet_emergency"),
        help="emergency-store path the supervised relaunch resumes from "
        "(must match arch.fleet.emergency_dir)",
    )
    parser.add_argument(
        "--quarantine-file",
        default=os.path.join("checkpoints", "quarantine.json"),
        help="quarantine record the integrity sentinel writes on a "
        "state-corruption exit (rc 88, stoix_tpu/resilience/integrity.py; "
        "must match arch.integrity.quarantine_file). --supervise reads the "
        "offender + resume overrides from it and relaunches restoring the "
        "newest digest-verified checkpoint",
    )
    parser.add_argument(
        "--compile-cache",
        default=None,
        metavar="DIR",
        help="share ONE persistent XLA compilation cache directory across "
        "every launched job: exports JAX_COMPILATION_CACHE_DIR=DIR to each "
        "job (the cache is always on — without this flag a job uses the "
        "variable it inherits, else <checkout>/xla_cache; "
        "utils/compilecache.py, docs/DESIGN.md §2.7). The first job/host "
        "pays each compile, the rest hit the cache — and a --supervise "
        "relaunch recompiles nothing",
    )
    parser.add_argument(
        "--aot-export",
        default=None,
        metavar="DIR",
        help="jax.export artifacts of the top-level learn function are "
        "serialized into DIR by the first job and loaded (skipping "
        "trace+lower) by every later one (appends "
        "arch.compile_cache.export_dir)",
    )
    parser.add_argument("--nodes", type=int, default=1)
    parser.add_argument("--time", default="04:00:00")
    parser.add_argument("--partition", default=None)
    parser.add_argument(
        "--preempt-grace",
        type=int,
        default=90,
        help="seconds of SIGTERM warning before SLURM kills the job "
        "(#SBATCH --signal=TERM@N — no B: prefix, so the signal reaches the "
        "srun'd training processes themselves, not just the batch shell). "
        "The in-process preemption handler "
        "(stoix_tpu/resilience/preemption.py) uses this window to drain the "
        "dispatcher and write an emergency checkpoint, so a preempted run "
        "resumes instead of losing up to a checkpoint interval of work.",
    )
    parser.add_argument("--sbatch-extra", nargs="*", default=[], help="raw #SBATCH lines")
    parser.add_argument("--script-dir", default="launcher_scripts")
    parser.add_argument("--log-dir", default="launcher_logs")
    parser.add_argument("overrides", nargs="*", help="shared key=value overrides")
    args = parser.parse_args(argv)
    if args.changed_only and not args.preflight_only:
        # Silently ignoring the flag would let a user believe their --submit
        # was gated on a changed-file lint that never ran.
        parser.error("--changed-only requires --preflight-only")
    if args.elastic and args.supervise <= 0:
        # An elastic policy with nothing supervising it would silently never
        # relaunch — exactly the surprise this pairing check prevents.
        parser.error("--elastic requires --supervise N (N > 0)")
    if args.aot_export:
        # Ride the ordinary override mechanism so the knob reaches SLURM
        # scripts, --local runs, and --supervise relaunches identically.
        args.overrides = [
            f"arch.compile_cache.export_dir={args.aot_export}", *args.overrides
        ]

    jobs = build_jobs(args)
    log = get_logger("stoix_tpu.launcher")
    log.info(
        "[launcher] %d jobs: %d systems x %d envs x %d seeds",
        len(jobs), len(args.systems), len(args.envs), len(args.seeds),
    )

    if args.preflight_only:
        sys.exit(run_preflight_only(jobs, changed_only=args.changed_only))

    if args.local:
        # Make the repo importable from any working directory.
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ)
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
        if args.compile_cache:
            env["JAX_COMPILATION_CACHE_DIR"] = args.compile_cache
        resume_overrides = [
            "logger.checkpointing.load_model=true",
            f"logger.checkpointing.load_args.load_path={args.fleet_resume_path}",
        ]
        for job in jobs:
            log.info("[launcher] running %s", job["name"])
            cmd = [sys.executable, "-m", job["module"], *job["overrides"]]
            if args.supervise > 0:
                rc = run_supervised(
                    cmd, env, args.supervise, resume_overrides,
                    quarantine_file=args.quarantine_file,
                    elastic=args.elastic,
                    fleet_resume_path=args.fleet_resume_path,
                    job_overrides=list(job["overrides"]),
                )
                if rc != 0:
                    sys.exit(rc)
            else:
                subprocess.run(cmd, check=True, env=env)
        return

    os.makedirs(args.script_dir, exist_ok=True)
    os.makedirs(args.log_dir, exist_ok=True)
    partition_line = f"#SBATCH --partition={args.partition}\n" if args.partition else ""
    extra_lines = "".join(f"#SBATCH {line}\n" for line in args.sbatch_extra)
    cache_line = (
        f"export JAX_COMPILATION_CACHE_DIR={shlex.quote(args.compile_cache)}\n"
        if args.compile_cache
        else ""
    )
    for job in jobs:
        script = SBATCH_TEMPLATE.format(
            job_name=job["name"],
            log_dir=args.log_dir,
            nodes=args.nodes,
            time=args.time,
            preempt_grace=args.preempt_grace,
            partition_line=partition_line,
            extra_lines=extra_lines,
            cache_line=cache_line,
            module=job["module"],
            overrides=" ".join(job["overrides"]),
        )
        path = os.path.join(args.script_dir, f"{job['name']}.sbatch")
        with open(path, "w") as f:
            f.write(script)
        if args.submit:
            subprocess.run(["sbatch", path], check=True)
            log.info("[launcher] submitted %s", path)
        else:
            log.info("[launcher] wrote %s (pass --submit to sbatch)", path)


if __name__ == "__main__":
    main()
