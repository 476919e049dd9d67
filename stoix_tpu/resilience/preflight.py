"""Launch preflight: fail fast, with a typed error, BEFORE committing the run.

Five rounds of benchmarking never captured a chip number because a wedged
PJRT runtime hangs backend init until a blanket timeout forces CPU fallback.
The root problem is that `import jax; jax.devices()` is an unbounded bet: once
the parent process touches a wedged runtime it is stuck inside a native RPC
that no Python-level timeout can interrupt. This module keeps every risky
probe OUT of the parent (docs/DESIGN.md §2.4):

  1. **Backend probe** (`probe_backend`): a SUBPROCESS imports jax, lists
     devices, runs a small matmul, and reports platform/device-count/HBM as
     one JSON line. The parent enforces a bounded timeout and retries with
     exponential backoff; exhaustion raises `BackendUnavailableError` naming
     attempts and deadline. A wedged runtime kills the child, never the
     parent.
  2. **Config cross-validation** (`validate_config`): arch × system ×
     network × env shape checks against the probed device count, BEFORE any
     device work. ALL findings are collected into one
     `ConfigValidationError`, so one preflight run fixes the whole config.
  3. **AOT memory check** (`check_device_memory`): the compiled learner's
     `memory_analysis()` against the device's HBM `bytes_limit`; a predicted
     OOM raises `ResourcePreflightError` before the first allocation instead
     of a RESOURCE_EXHAUSTED twenty minutes into the run. Backends that
     expose no limit (CPU) degrade to an informational skip.

`run_preflight` strings the stages into a `PreflightReport` (pass/fail/skip
per stage + a one-page render) for `launcher.py --preflight-only` and CI /
SLURM prolog scripts. Everything here is opt-in via the `arch.preflight`
config block — disabled, no subprocess is spawned and the host loop is
bit-identical.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Any, List, NamedTuple, Optional

from stoix_tpu.observability import get_logger, get_registry
from stoix_tpu.resilience.errors import (
    BackendUnavailableError,
    ConfigValidationError,
    ResourcePreflightError,
)

# Self-contained child source: no stoix_tpu import (keeps the child cheap and
# PYTHONPATH-independent). The `backend_wedge` chaos fault is honored HERE, in
# the child, before jax is touched — simulating a PJRT runtime that accepts
# the process and then never answers — so the parent-side timeout/retry path
# is deterministically drivable (resilience/faultinject.py).
_PROBE_SOURCE = r"""
import json, os, sys, time
for entry in os.environ.get("STOIX_TPU_FAULT", "").split(","):
    if entry.strip().partition(":")[0].strip() == "backend_wedge":
        time.sleep(3600)  # wedged runtime: alive, silent, never answers
import jax
import numpy as np
devices = jax.devices()
x = jax.numpy.ones((128, 128)) @ jax.numpy.ones((128, 128))
value = float(np.asarray(x[0, 0]))
if value != 128.0:
    raise SystemExit(f"probe matmul returned {value}, expected 128.0")
stats = devices[0].memory_stats() or {}
print(json.dumps({
    "platform": devices[0].platform,
    "device_kind": getattr(devices[0], "device_kind", devices[0].platform),
    "device_count": len(devices),
    "process_count": jax.process_count(),
    "hbm_bytes_limit": stats.get("bytes_limit"),
}))
"""


class BackendProbe(NamedTuple):
    """Healthy-backend report from the subprocess probe."""

    platform: str
    device_kind: str
    device_count: int
    process_count: int
    hbm_bytes_limit: Optional[int]
    attempts: int  # attempts consumed (1 = first try answered)
    elapsed_s: float


def probe_backend(
    timeout_s: float = 60.0,
    attempts: int = 3,
    backoff_base_s: float = 1.0,
    backoff_max_s: float = 30.0,
    env: Optional[dict] = None,
) -> BackendProbe:
    """Probe the device backend in a subprocess with a bounded per-attempt
    timeout and exponential-backoff retries.

    The parent never imports jax here and never blocks past
    `attempts * timeout_s + backoffs`: a wedged runtime wedges the CHILD,
    which the timeout kills. Raises BackendUnavailableError when every
    attempt fails.

    One process per chip: the child DOES touch the accelerator (it runs a
    matmul on it), and an accelerator belongs to one process at a time. So
    call this BEFORE the calling process's first backend call — a caller that
    already holds the chip makes the child fail or hang — and rely on the
    child having exited before this returns: `subprocess.run` waits for it,
    and kills and reaps it on timeout. Every caller keeps that order
    (bench.py main probes first; both runners probe in `run_host.RunHost`, built
    before `maybe_initialize_distributed`/mesh construction; launcher.py
    re-probes between child incarnations)."""
    log = get_logger("stoix_tpu.resilience")
    counter = get_registry().counter(
        "stoix_tpu_preflight_probe_attempts_total",
        "Backend probe subprocess attempts, by outcome",
    )
    child_env = {**os.environ, **(env or {})}
    # The child only reads STOIX_TPU_FAULT; a backend_wedge armed via the
    # CONFIG spec (arch.fault_spec) must still reach it, or the chaos plan
    # logs as active while the wedge silently never fires. (When the env var
    # is set it won at configure() time, so the armed plan and the inherited
    # var already agree.)
    from stoix_tpu.resilience import faultinject

    if faultinject.backend_wedge_armed() and not child_env.get(faultinject.ENV_VAR):
        child_env[faultinject.ENV_VAR] = "backend_wedge"
    start = time.monotonic()
    last_error = "never attempted"
    for attempt in range(1, int(attempts) + 1):
        try:
            proc = subprocess.run(
                [sys.executable, "-c", _PROBE_SOURCE],
                capture_output=True,
                text=True,
                timeout=float(timeout_s),
                env=child_env,
            )
        except subprocess.TimeoutExpired:
            counter.inc(labels={"outcome": "timeout"})
            last_error = f"probe timed out after {timeout_s:.0f}s (wedged backend init)"
        else:
            if proc.returncode == 0:
                for line in proc.stdout.strip().splitlines():
                    if not line.startswith("{"):
                        continue
                    payload = json.loads(line)
                    counter.inc(labels={"outcome": "ok"})
                    return BackendProbe(
                        platform=str(payload["platform"]),
                        device_kind=str(payload.get("device_kind", payload["platform"])),
                        device_count=int(payload["device_count"]),
                        process_count=int(payload.get("process_count", 1)),
                        hbm_bytes_limit=payload.get("hbm_bytes_limit"),
                        attempts=attempt,
                        elapsed_s=time.monotonic() - start,
                    )
                counter.inc(labels={"outcome": "bad_output"})
                last_error = f"probe exited 0 without a JSON report: {proc.stdout[-200:]!r}"
            else:
                counter.inc(labels={"outcome": "error"})
                tail = (proc.stderr or proc.stdout).strip().splitlines()
                last_error = (
                    f"probe exited {proc.returncode}: {tail[-1] if tail else 'no output'}"
                )
        if attempt < int(attempts):
            backoff = min(float(backoff_base_s) * (2 ** (attempt - 1)), float(backoff_max_s))
            log.warning(
                "[preflight] backend probe attempt %d/%d failed (%s) — retrying "
                "in %.1fs", attempt, attempts, last_error, backoff,
            )
            time.sleep(backoff)
    raise BackendUnavailableError(int(attempts), float(timeout_s), last_error)


def _check_mesh(findings: List[str], arch: Any, device_count: Optional[int]) -> int:
    """Resolve the mesh data-axis size (for divisibility checks below);
    appends findings for non-covering axes. Returns 1 when unresolvable."""
    axes = dict(arch.get("mesh") or {"data": -1})
    sizes = list(axes.values())
    if sizes.count(-1) > 1:
        findings.append(f"arch.mesh: at most one axis may be -1, got {axes}")
        return 1
    if device_count is not None:
        import numpy as np

        known = int(np.prod([s for s in sizes if s != -1])) if sizes else 1
        if -1 in sizes:
            if known <= 0 or device_count % known != 0:
                findings.append(
                    f"arch.mesh {axes}: fixed axes ({known}) do not divide the "
                    f"{device_count} probed devices"
                )
                return 1
            sizes[sizes.index(-1)] = device_count // known
        elif known != device_count:
            findings.append(
                f"arch.mesh {axes} covers {known} devices but the backend "
                f"probe reports {device_count}"
            )
    data = dict(zip(axes.keys(), sizes)).get("data", 1)
    return max(1, int(data) if data != -1 else 1)


def validate_config(config: Any, device_count: Optional[int] = None) -> None:
    """Cross-validate arch × system × network × env BEFORE any device work.

    `device_count` is the PROBED count (preflight must not touch jax in this
    process); None skips the device-dependent checks. Collects every finding
    and raises ONE ConfigValidationError, so a single preflight run reports
    the whole config's problems.

    Multi-process launches (JAX_COORDINATOR_ADDRESS / arch.distributed):
    the probe child sees only LOCAL devices while the mesh spans the global
    job, so the device-dependent checks are skipped — rejecting a valid
    32-device pod config against one host's 8 chips would be a preflight
    bug, not a catch."""
    findings: List[str] = []
    arch = config.get("arch") or {}
    system = config.get("system") or {}
    if device_count is not None and (
        os.environ.get("JAX_COORDINATOR_ADDRESS")
        or (arch.get("distributed") or {}).get("coordinator_address")
    ):
        get_logger("stoix_tpu.resilience").info(
            "[preflight] multi-process launch configured — the probed count "
            "(%d) is host-local; skipping device-count checks", device_count,
        )
        device_count = None

    # --- arch: env/batch shape ---------------------------------------------
    total_num_envs = arch.get("total_num_envs")
    if not isinstance(total_num_envs, int) or total_num_envs <= 0:
        findings.append(f"arch.total_num_envs must be a positive int, got {total_num_envs!r}")
        total_num_envs = None
    rollout_length = system.get("rollout_length")
    if not isinstance(rollout_length, int) or rollout_length <= 0:
        findings.append(f"system.rollout_length must be a positive int, got {rollout_length!r}")
    if arch.get("total_timesteps") in (None, "~") and arch.get("num_updates") in (None, "~"):
        findings.append("set either arch.total_timesteps or arch.num_updates (both are unset)")

    is_sebulba = str(arch.get("architecture_name", "anakin")) == "sebulba"
    if is_sebulba:
        # The actor/learner/evaluator split validates through the SAME
        # mesh-role resolution the run itself uses (parallel/roles.py,
        # docs/DESIGN.md §2.11) — id ranges, non-empty primary roles, and
        # partial act/learn overlaps all surface here as findings. The
        # resolution half is jax-free by design, so this stays safe before
        # any device work; imported lazily because the parallel package
        # itself pulls in jax.
        from stoix_tpu.parallel.roles import MeshRolesError, resolve_assignments

        # The env split must be checked against the ACT role's device count —
        # the run takes actor devices from the resolved roles, so an explicit
        # arch.roles.act overriding the legacy arch.actor.device_ids must be
        # honored here too (legacy keys only as a fallback when resolution
        # itself failed or the all-devices count is unknowable pre-probe).
        n_actor_devices = None
        try:
            assignments = resolve_assignments(config, device_count=device_count)
            act = assignments.get("act")
            if act is not None:
                if act.device_ids is not None:
                    n_actor_devices = len(act.device_ids)
                elif device_count is not None:
                    n_actor_devices = device_count
        except MeshRolesError as exc:
            findings.extend(exc.findings)
        if n_actor_devices is None:
            n_actor_devices = len(list((arch.get("actor") or {}).get("device_ids") or []))
        actors_per_device = int((arch.get("actor") or {}).get("actor_per_device", 1) or 1)
        num_actors = max(1, n_actor_devices) * max(1, actors_per_device)
        if total_num_envs is not None and total_num_envs % num_actors != 0:
            findings.append(
                f"arch.total_num_envs ({total_num_envs}) must be divisible by "
                f"num_actors ({n_actor_devices} device(s) x {actors_per_device} "
                f"actor(s)/device = {num_actors})"
            )
    else:
        data_shards = _check_mesh(findings, arch, device_count)
        update_batch_size = int(arch.get("update_batch_size", 1) or 1)
        if update_batch_size <= 0:
            findings.append(
                f"arch.update_batch_size must be positive, got {update_batch_size}"
            )
            update_batch_size = 1
        divisor = data_shards * update_batch_size
        if total_num_envs is not None and total_num_envs % divisor != 0:
            findings.append(
                f"arch.total_num_envs ({total_num_envs}) must be divisible by "
                f"data_shards * update_batch_size ({data_shards} * {update_batch_size})"
            )
        # PPO-family minibatching: the per-shard batch must split evenly.
        num_minibatches = system.get("num_minibatches")
        if (
            isinstance(num_minibatches, int)
            and num_minibatches > 0
            and total_num_envs is not None
            and isinstance(rollout_length, int)
            and rollout_length > 0
        ):
            per_shard = (rollout_length * total_num_envs) // divisor
            if per_shard % num_minibatches != 0:
                findings.append(
                    f"per-shard batch (rollout_length * envs_per_shard = {per_shard}) "
                    f"not divisible by system.num_minibatches ({num_minibatches})"
                )

    # --- system: guard mode / fault spec parse early, not mid-run ----------
    from stoix_tpu.resilience import faultinject, guards

    try:
        guards.resolve_mode(config)
    except ValueError as exc:
        findings.append(str(exc))
    try:
        faultinject.parse_spec(arch.get("fault_spec"))
    except ValueError as exc:
        findings.append(f"arch.fault_spec: {exc}")

    # --- env: the scenario must resolve to a registered constructor --------
    env_cfg = config.get("env") or {}
    scenario = env_cfg.get("scenario")
    scenario_name = scenario.get("name") if isinstance(scenario, dict) else scenario
    # Adapter-backed env groups (cvec pools, envpool, gymnasium) resolve their
    # ids against external catalogs — only first-party JAX suites are checked.
    first_party = str(env_cfg.get("env_name", "")) not in (
        "cvec", "envpool", "gymnasium",
    )
    if scenario_name and first_party:
        try:
            from stoix_tpu.envs.registry import ENV_REGISTRY

            if str(scenario_name) not in ENV_REGISTRY:
                findings.append(
                    f"env scenario '{scenario_name}' not in the first-party "
                    f"registry (known: {sorted(ENV_REGISTRY)}); a typo here "
                    f"otherwise surfaces as a KeyError after backend init"
                )
        except Exception as exc:  # noqa: BLE001 — registry probing is best-effort
            get_logger("stoix_tpu.resilience").info(
                "[preflight] env registry check skipped (%s)", exc
            )

    # --- network: layer sizes must be positive ints ------------------------
    network = config.get("network") or {}
    for net_name, net in network.items():
        if not isinstance(net, dict):
            continue
        for part_name, part in net.items():
            if not isinstance(part, dict):
                continue
            sizes = part.get("layer_sizes")
            if sizes is not None and (
                not isinstance(sizes, (list, tuple))
                or any(not isinstance(s, int) or s <= 0 for s in sizes)
            ):
                findings.append(
                    f"network.{net_name}.{part_name}.layer_sizes must be positive "
                    f"ints, got {sizes!r}"
                )

    if findings:
        raise ConfigValidationError(findings)


def estimate_compiled_memory(compiled: Any) -> Optional[dict]:
    """Predicted device-memory footprint of a compiled XLA executable, from
    `compiled.memory_analysis()`; None when the object is not a compiled
    executable or the backend exposes no analysis (then there is nothing to
    gate on)."""
    analysis = getattr(compiled, "memory_analysis", None)
    if analysis is None:
        return None
    try:
        stats = analysis()
    except Exception:  # noqa: BLE001 — absent analysis is a skip, not a failure
        return None
    if stats is None:
        return None
    fields = {}
    for name in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        value = getattr(stats, name, None)
        if value is not None:
            fields[name] = int(value)
    if not fields:
        return None
    # Aliased bytes (donated buffers) are counted in both arguments and
    # outputs but occupy HBM once.
    total = (
        fields.get("argument_size_in_bytes", 0)
        + fields.get("output_size_in_bytes", 0)
        + fields.get("temp_size_in_bytes", 0)
        + fields.get("generated_code_size_in_bytes", 0)
        - fields.get("alias_size_in_bytes", 0)
    )
    return {"predicted_bytes": max(0, total), **fields}


def check_device_memory(
    compiled: Any,
    headroom: float = 0.9,
    device: Any = None,
) -> Optional[dict]:
    """Gate a compiled learner on predicted HBM: raises ResourcePreflightError
    when memory_analysis predicts more than `headroom` of the device's
    bytes_limit. Returns the estimate dict (with 'limit_bytes' when known), or
    None when the backend exposes no analysis. CPU (no bytes_limit) logs the
    estimate and passes — there is no HBM to protect."""
    estimate = estimate_compiled_memory(compiled)
    if estimate is None:
        return None
    log = get_logger("stoix_tpu.resilience")
    if device is None:
        import jax

        device = jax.devices()[0]
    try:
        stats = device.memory_stats() or {}
    except Exception:  # noqa: BLE001 — CPU/older PJRT: no stats, nothing to gate
        stats = {}
    limit = stats.get("bytes_limit")
    gib = 1024.0 ** 3
    if not limit:
        log.info(
            "[preflight] predicted program memory %.2f GiB (device exposes no "
            "bytes_limit — HBM gate skipped)", estimate["predicted_bytes"] / gib,
        )
        return estimate
    estimate["limit_bytes"] = int(limit)
    get_registry().gauge(
        "stoix_tpu_preflight_predicted_memory_bytes",
        "memory_analysis() prediction for the compiled learner step",
    ).set(float(estimate["predicted_bytes"]))
    if estimate["predicted_bytes"] > float(headroom) * float(limit):
        raise ResourcePreflightError(
            estimate["predicted_bytes"],
            int(limit),
            float(headroom),
            getattr(device, "device_kind", getattr(device, "platform", "device")),
            detail=f"temp={estimate.get('temp_size_in_bytes', 0) / gib:.2f} GiB, "
            f"args={estimate.get('argument_size_in_bytes', 0) / gib:.2f} GiB",
        )
    log.info(
        "[preflight] predicted program memory %.2f GiB fits %.0f%% of %.2f GiB HBM",
        estimate["predicted_bytes"] / gib, headroom * 100, limit / gib,
    )
    return estimate


class PreflightSettings(NamedTuple):
    """Resolved `arch.preflight` block (all knobs with defaults applied)."""

    enabled: bool
    probe_timeout_s: float
    probe_attempts: int
    probe_backoff_base_s: float
    probe_backoff_max_s: float
    hbm_headroom: float
    compile_deadline_s: float
    first_window_deadline_s: float
    hard_exit_grace_s: float


def settings_from_config(config: Any) -> PreflightSettings:
    cfg = (config.get("arch") or {}).get("preflight") or {}
    return PreflightSettings(
        enabled=bool(cfg.get("enabled", False)),
        probe_timeout_s=float(cfg.get("probe_timeout_s", 60.0)),
        probe_attempts=int(cfg.get("probe_attempts", 3)),
        probe_backoff_base_s=float(cfg.get("probe_backoff_base_s", 1.0)),
        probe_backoff_max_s=float(cfg.get("probe_backoff_max_s", 30.0)),
        hbm_headroom=float(cfg.get("hbm_headroom", 0.9)),
        compile_deadline_s=float(cfg.get("compile_deadline_s", 1800.0)),
        first_window_deadline_s=float(cfg.get("first_window_deadline_s", 900.0)),
        hard_exit_grace_s=float(cfg.get("hard_exit_grace_s", 0.0)),
    )


class PreflightReport:
    """Stage-by-stage preflight outcome: (name, status, detail) rows where
    status is 'pass' | 'fail' | 'skip'. `ok` ignores skips; `render()` is the
    one-page text `launcher.py --preflight-only` prints for CI/prolog logs."""

    def __init__(self) -> None:
        self.stages: List[tuple] = []

    def add(self, name: str, status: str, detail: str = "") -> None:
        assert status in ("pass", "fail", "skip"), status
        self.stages.append((name, status, detail))

    @property
    def ok(self) -> bool:
        return all(status != "fail" for _name, status, _detail in self.stages)

    def render(self) -> str:
        mark = {"pass": "PASS", "fail": "FAIL", "skip": "skip"}
        width = max((len(n) for n, _s, _d in self.stages), default=8)
        lines = ["stoix_tpu preflight report", "=" * 40]
        for name, status, detail in self.stages:
            lines.append(f"{name.ljust(width)}  [{mark[status]}]  {detail}".rstrip())
        lines.append("=" * 40)
        lines.append(f"overall: {'PASS' if self.ok else 'FAIL'}")
        return "\n".join(lines)


def run_preflight(
    configs: Any = None,
    settings: Optional[PreflightSettings] = None,
) -> PreflightReport:
    """Probe the backend, then cross-validate each config against the probed
    topology. `configs` is one config, a list of (label, config) pairs, or
    None (probe only). Stages that cannot run (probe dead -> no device count;
    no configs) record as skip/fail rather than aborting the report."""
    settings = settings or PreflightSettings(
        True, 60.0, 3, 1.0, 30.0, 0.9, 1800.0, 900.0, 0.0
    )
    report = PreflightReport()
    device_count: Optional[int] = None
    try:
        probe = probe_backend(
            timeout_s=settings.probe_timeout_s,
            attempts=settings.probe_attempts,
            backoff_base_s=settings.probe_backoff_base_s,
            backoff_max_s=settings.probe_backoff_max_s,
        )
        device_count = probe.device_count
        report.add(
            "backend_probe", "pass",
            f"{probe.platform} x{probe.device_count} ({probe.device_kind}), "
            f"attempt {probe.attempts}, {probe.elapsed_s:.1f}s",
        )
    except BackendUnavailableError as exc:
        report.add("backend_probe", "fail", str(exc))

    if configs is None:
        report.add("config_validation", "skip", "no configs supplied")
        return report
    pairs = configs if isinstance(configs, list) else [("config", configs)]
    for label, config in pairs:
        try:
            validate_config(config, device_count=device_count)
            report.add(f"config[{label}]", "pass", "arch/system/network/env cross-checks")
        except ConfigValidationError as exc:
            report.add(f"config[{label}]", "fail", "; ".join(exc.findings))
    return report
