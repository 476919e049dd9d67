"""Benchmark harness for the tracked BASELINE configs.

Default invocation prints ONE JSON line (the north-star Anakin PPO/Ant
workload): {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}.
`--all` prints one line per tracked config (5 lines) so replay-buffer, MCTS,
and Sebulba hot paths are perf-tracked alongside the PPO path
(BASELINE.md "Tracked configs"):

    anakin_ppo_ant            — north star (vs_baseline = per-chip / 15,625)
    anakin_c51_snake          — ff_c51 on first-party Snake (sharded replay)
    anakin_sac_ant            — ff_sac on first-party Ant (off-policy continuous)
    anakin_mz_cartpole        — ff_mz on CartPole (on-device MCTS in the loop)
    sebulba_ppo_cartpole      — actor/learner split over the native C++ pool

Usage: python bench.py [--all] [--smoke] [--cartpole] [--large] [--sebulba]
                       [--serve] [--replay] [--population] [--gossip] [--cpu]
                       [--loop] [--reps N] [--integrity]
       python bench.py --check BASELINE.json --candidate CAND.json
                       [--check-threshold 0.05] [--check-require-all]
  --all       run all five tracked configs, one JSON line each
  --smoke     tiny budget for CI wiring checks
  --cartpole  the round-1 metric: tiny-MLP CartPole (VPU-bound; kept for
              continuity)
  --large     MXU-bound variant (1024x1024 bfloat16 torsos on Ant)
  --sebulba   actor/learner-disaggregated PPO on the native C++ env pool
              (CartPole); reports steady-state env-steps/sec (post-compile
              window measured inside the host loop)
  --serve     the latency frontier (docs/DESIGN.md §2.8): train a tiny
              ff_ppo checkpoint, serve it through the dynamic-batching
              PolicyServer (stoix_tpu/serve), drive the open-loop load
              generator, and report p99 request latency in ms. The payload
              carries direction=lower_is_better (the --check gate inverts
              its comparison), the full latency percentile set, offered vs
              achieved QPS, batch-fill ratio, shed count, and hot-swap count
  --replay    the device-resident sharded replay service microbench
              (docs/DESIGN.md §2.10): prioritized add/sample/set_priorities
              cycles against an 8-shard (on CPU: virtual-device) mesh,
              reporting sampled items/sec as the headline plus add
              throughput and the transport ledger — ingested_bytes_total
              (raw experience, never crosses shards) vs
              sampled_bytes_crossed (the sample psum's payload) — so the
              samples-not-experience claim is a measured number the --check
              gate can hold
  --population mesh-parallel population training (docs/DESIGN.md §2.11):
              TWO payload lines, P=1 (bit-identity anchor) and P=8 with
              live PBT, each carrying aggregate env-steps/sec
  --gossip    async learner groups (docs/DESIGN.md §2.12): TWO payload
              lines, G=1 (lockstep — the dense pmean spans every device,
              zero gossip rounds) and G=2 (ring gossip at window
              boundaries). Each measures a clean steady-state rate PLUS a
              twin run under an injected host_stall straggler, and carries
              throughput_retained = stalled/clean — the headline async
              claim: gossip groups keep stepping while lockstep waits on
              the slowest slice. On one host the stall taxes every group
              equally, so the single-host ratio is a harness check; the
              field earns its keep on real multi-slice meshes
  --loop      the closed production loop under chaos (docs/DESIGN.md §2.15):
              train a tiny ff_ppo checkpoint, then run the self-healing
              train→serve→experience loop twice at matched offered QPS — a
              frozen-policy control arm and a live arm with the full chaos
              drill armed (replica_kill + replica_slow + feedback_stall +
              swap_poison) — and report the end-return delta (live minus
              frozen) as the headline: the policy improves under live
              traffic WHILE replicas crash and a poisoned push rolls back
              fleet-wide. The payload enforces zero silent drops, >=1
              failover, and >=1 canary rollback outright, and carries the
              full resilience ledger (failovers/ejections/readmissions/
              restarts/rollbacks) plus p99 latency and shed counts
  --elastic   the elastic-relaunch recovery frontier (docs/DESIGN.md §2.14):
              drive fault-injected shrink->grow resize cycles through
              `launcher.run_supervised --elastic` semantics (scripts/soak.py
              legs on the forced-CPU backend) and report the emergency-
              restore recovery wall per relaunch. The payload carries
              direction=lower_is_better (the --check gate inverts its
              comparison), recovery_wall_s dispersion over the relaunch reps
              (reps/median/min/max/rel_spread), and cycles_survived — how
              many full cycles upheld the §2.14 contract (consumed request,
              schema-valid flight record, digest-identical survivors,
              recovery-phase attribution)
  --integrity arm the state-integrity sentinel (arch.integrity, docs/
              DESIGN.md §2.9) in the Anakin probe run so the payload's
              first-class `integrity` fields (enabled / fingerprint_checks /
              overhead_s / probe_runs) carry a measured per-window cost;
              without the flag the fields still ride every payload with the
              disabled shape, so a sentinel can never tax a number invisibly
  --cpu       run on the CPU backend (tests and wiring checks; a --cpu number
              is a CPU number and is never reported as a device measurement)
  --check     variance-aware regression gate (no benchmark is run, no jax is
              imported): compare the --candidate payload lines against the
              baseline file metric-by-metric, failing a metric only when its
              candidate median drops below baseline median by more than
              max(baseline rel_spread, candidate rel_spread,
              --check-threshold).
              Baseline metrics the candidate never measured get a visible
              skip verdict (--check-require-all promotes them to failures,
              for CI gates benching every tracked config). Exit 0 = every
              compared metric within band; 1 = regression / failed workload
              line; 2 = usage or file errors. One JSON verdict line per
              metric. Besides bench payload lines and BASELINE.json
              `published` mappings, both sides accept a
              MULTICHIP_r*.json dry-run record (ok -> 1.0/0.0 median under
              multichip_dryrun_ok_dN) and a scaling_bench.py summary
              (`{"scaling": [...]}` -> scaling_ppo_weak_dN_env_steps_per_sec
              + scaling_ppo_weak_eff_dN per mesh size), so weak-scaling
              efficiency and the multichip posture ride the SAME gate as
              throughput — `python scaling_bench.py | python bench.py
              --check SCALING_BASE.json --candidate -` composes directly.
  --reps N    how many times the steady-state window is re-measured
              (default 3 for the Anakin timed loop; Sebulba re-runs its
              whole experiment per rep, so it defaults to 1 unless --reps is
              explicit). Every payload carries the per-rep dispersion as
              FIRST-CLASS fields — reps/median/min/max/rel_spread — so a
              number whose reps disagree can never masquerade as a trend;
              `value` stays the best rep (today's semantics).

Exit codes: 0 = every workload measured and printed its line. Non-zero = the
backend probe failed, backend init failed, a watchdog fired or a workload
raised: NOTHING is re-run on another backend. A single-workload failure prints
no result line (the typed reason goes to stderr as one JSON object); `--all`
prints the lines of the workloads that ran plus a value-0 `WORKLOAD FAILED`
line per dead one, and still exits 1.
"""

from __future__ import annotations

import json
import sys
import time


def _parse_reps(argv: list) -> int | None:
    """The --reps N value, or None when absent (workloads apply their own
    default: 3 timed reps for Anakin — the historical non-smoke count, now
    also applied under --smoke so even CI payloads carry a real rel_spread
    (a smoke rep is a single tiny learn call) — and 1 full experiment for
    Sebulba, whose rep is a whole run)."""
    if "--reps" not in argv:
        return None
    idx = argv.index("--reps")
    try:
        reps = int(argv[idx + 1])
    except (IndexError, ValueError):
        sys.exit("--reps requires an integer, e.g. --reps 5")
    if reps < 1:
        sys.exit("--reps must be >= 1")
    return reps


# ---------------------------------------------------------------------------
# --check: the variance-aware regression gate (no jax import on this path)
# ---------------------------------------------------------------------------


def _multichip_payload(obj: dict) -> dict | None:
    """MULTICHIP_r*.json dry-run record -> a gate-composable payload.

    The fleet harness records `{"n_devices", "rc", "ok", ...}` per dry run;
    converting ok into a 1.0/0.0 median makes the record ride the SAME gate
    as every throughput line: a baseline or candidate with ok=false is a
    zero-median "failed workload" verdict (loud), ok=true vs ok=true passes
    trivially. A `skipped` record is no measurement at all -> None."""
    if not isinstance(obj, dict) or "n_devices" not in obj or "ok" not in obj:
        return None
    if obj.get("skipped"):
        return None
    ok = 1.0 if obj.get("ok") else 0.0
    return {
        "metric": "multichip_dryrun_ok_d%d" % int(obj["n_devices"]),
        "value": ok, "median": ok, "rel_spread": 0.0,
        "unit": "dry-run success (1.0 = ok)",
        "rc": obj.get("rc"),
    }


def _scaling_payloads(obj: dict) -> list | None:
    """scaling_bench.py summary (`{"scaling": [...]}`) -> per-size payloads.

    Each mesh size contributes a weak-scaling throughput line, and every size
    past the smallest contributes its efficiency-vs-smallest ratio as its own
    metric (ROADMAP item 4: >=80% efficiency is a NUMBER the gate can hold a
    band around, not a prose claim). The smallest size's efficiency is 1.0 by
    construction, so no line is emitted for it."""
    if not isinstance(obj, dict) or not isinstance(obj.get("scaling"), list):
        return None
    out = []
    for i, rec in enumerate(obj["scaling"]):
        if not isinstance(rec, dict) or "devices" not in rec:
            continue
        n = int(rec["devices"])
        sps = float(rec.get("env_steps_per_sec") or 0.0)
        out.append(
            {
                "metric": f"scaling_ppo_weak_d{n}_env_steps_per_sec",
                "value": sps, "median": sps, "rel_spread": 0.0,
                "unit": "env_steps/sec (weak scaling)",
                "devices": n,
            }
        )
        eff = rec.get("efficiency_vs_smallest")
        if i > 0 and eff is not None:
            eff = float(eff)
            out.append(
                {
                    "metric": f"scaling_ppo_weak_eff_d{n}",
                    "value": eff, "median": eff, "rel_spread": 0.0,
                    "unit": "per-device efficiency vs smallest mesh",
                    "devices": n,
                }
            )
    return out


def _parse_payload_lines(text: str) -> list:
    """Every JSON object line carrying a `metric` field, in file order —
    plus conversions for the two metric-less record shapes the repo's other
    harnesses emit (a scaling summary line, a multichip dry-run record), so
    `python scaling_bench.py | python bench.py --check ... --candidate -`
    composes directly. First occurrence of a metric wins (scaling_bench
    emits per-size payload lines AND the trailing summary; the summary's
    conversions must not double-count them)."""
    payloads = []
    seen = set()

    def _add(obj):
        if obj and obj.get("metric") and obj["metric"] not in seen:
            seen.add(obj["metric"])
            payloads.append(obj)

    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if not isinstance(obj, dict):
            continue
        if obj.get("metric"):
            _add(obj)
            continue
        for converted in _scaling_payloads(obj) or ():
            _add(converted)
        _add(_multichip_payload(obj))
    return payloads


def _payloads_from_text(text: str) -> list:
    """Payloads from any tracked format: a file of bench output (one JSON
    payload line per tracked metric), a BASELINE.json whose `published`
    mapping carries payload dicts keyed by metric name, a MULTICHIP_r*.json
    dry-run record (pretty-printed whole-file JSON — line parsing cannot see
    it), or a scaling_bench.py `{"scaling": [...]}` summary. Used for BOTH
    gate sides, so a fresh MULTICHIP record gates directly against a tracked
    one."""
    try:
        obj = json.loads(text)
    except ValueError:
        obj = None
    if isinstance(obj, dict) and isinstance(obj.get("published"), dict):
        out = []
        for metric, payload in obj["published"].items():
            if isinstance(payload, dict):
                out.append({"metric": metric, **payload})
        return out
    if isinstance(obj, dict) and obj.get("metric"):
        return [obj]
    if isinstance(obj, dict):
        scaling = _scaling_payloads(obj)
        if scaling is not None:
            return scaling
        multichip = _multichip_payload(obj)
        if multichip is not None:
            return [multichip]
    return _parse_payload_lines(text)


def _load_baseline_payloads(path: str) -> list:
    with open(path) as f:
        return _payloads_from_text(f.read())


def _median_of(payload: dict) -> float:
    """The dispersion-aware center: `median` when the payload carries the
    PR 7 rep fields, else the headline `value` (pre-reps payloads)."""
    if payload.get("median") is not None:
        return float(payload["median"])
    return float(payload.get("value") or 0.0)


def check_payloads(
    baselines: list, candidates: list, threshold: float = 0.05,
    require_all: bool = False,
) -> tuple:
    """Gate the candidate payloads against the baselines. Returns
    (exit_code, verdict_lines): one verdict dict per candidate metric with a
    baseline counterpart, plus a VISIBLE skip verdict for every baseline
    metric the candidate never measured (a truncated candidate run must not
    clear the gate silently; `require_all` promotes those skips to failures
    for CI gates that bench every tracked config). Exit 1 when any verdict
    failed.

    Comparison rule per metric:
      * a failed workload line (value/median 0) always fails;
      * otherwise fail iff candidate median < baseline median scaled by
        (1 - band), band = max(baseline rel_spread, candidate rel_spread,
        threshold) — a drop indistinguishable from the recorded run-to-run
        jitter is jitter, not a regression. Improvements never fail.
    """
    by_metric = {p["metric"]: p for p in baselines}
    verdicts = []
    failed = False
    for cand in candidates:
        base = by_metric.get(cand["metric"])
        if base is None:
            verdicts.append(
                {
                    "metric": cand["metric"],
                    "status": "skip",
                    "reason": "no baseline for this metric",
                }
            )
            continue
        base_median, cand_median = _median_of(base), _median_of(cand)
        verdict = {
            "metric": cand["metric"],
            "baseline_median": base_median,
            "candidate_median": cand_median,
        }
        if cand_median <= 0.0 or base_median <= 0.0:
            which = "candidate" if cand_median <= 0.0 else "baseline"
            verdict.update(
                status="fail",
                reason=f"{which} is a failed workload line (zero median)",
            )
        else:
            band = max(
                float(base.get("rel_spread") or 0.0),
                float(cand.get("rel_spread") or 0.0),
                float(threshold),
            )
            verdict["band"] = round(band, 4)
            # Latency metrics (the serve payloads) carry
            # direction=lower_is_better: a regression is a median RISE above
            # the baseline + band, the mirror of the throughput rule. The
            # baseline's direction wins on disagreement — the tracked
            # definition of the metric is the baseline's.
            direction = str(
                base.get("direction") or cand.get("direction") or "higher_is_better"
            )
            if direction == "lower_is_better":
                verdict["direction"] = direction
                ceiling = base_median * (1.0 + band)
                if cand_median > ceiling:
                    verdict.update(
                        status="fail",
                        reason=(
                            f"regression: median {cand_median:.1f} > "
                            f"{ceiling:.1f} (baseline {base_median:.1f} + "
                            f"{band:.1%} variance band; lower is better)"
                        ),
                    )
                else:
                    verdict.update(status="pass", reason="within variance band")
            else:
                floor = base_median * (1.0 - band)
                if cand_median < floor:
                    verdict.update(
                        status="fail",
                        reason=(
                            f"regression: median {cand_median:.1f} < "
                            f"{floor:.1f} (baseline {base_median:.1f} - "
                            f"{band:.1%} variance band)"
                        ),
                    )
                else:
                    verdict.update(status="pass", reason="within variance band")
        failed = failed or verdict["status"] == "fail"
        verdicts.append(verdict)
    candidate_metrics = {c["metric"] for c in candidates}
    for metric in by_metric:
        if metric not in candidate_metrics:
            # Never silent: a candidate that crashed after measuring a subset
            # of the tracked workloads would otherwise clear the gate.
            status = "fail" if require_all else "skip"
            verdicts.append(
                {
                    "metric": metric,
                    "status": status,
                    "reason": "baseline metric absent from the candidate run",
                }
            )
            failed = failed or status == "fail"
    if not any(v["status"] != "skip" for v in verdicts):
        # A gate that compared nothing passed nothing: make the empty
        # intersection loud instead of a vacuous green.
        verdicts.append(
            {
                "metric": None,
                "status": "fail",
                "reason": "no candidate metric had a baseline counterpart",
            }
        )
        failed = True
    return (1 if failed else 0), verdicts


def run_check(argv: list) -> int:
    """CLI half of the gate; never imports jax (CI/fleet prologs call this
    on machines with no accelerator runtime at all)."""

    def _flag_value(flag: str) -> str | None:
        if flag not in argv:
            return None
        idx = argv.index(flag)
        if idx + 1 >= len(argv):
            print(json.dumps({"error": f"{flag} requires a value"}))
            raise SystemExit(2)
        return argv[idx + 1]

    baseline_path = _flag_value("--check")
    candidate_path = _flag_value("--candidate")
    threshold_raw = _flag_value("--check-threshold")
    try:
        threshold = float(threshold_raw) if threshold_raw is not None else 0.05
    except ValueError:
        print(json.dumps({"error": f"bad --check-threshold {threshold_raw!r}"}))
        return 2
    try:
        baselines = _load_baseline_payloads(baseline_path)
        if candidate_path in (None, "-"):
            if sys.stdin.isatty():
                print(
                    json.dumps(
                        {"error": "--check needs --candidate FILE (or piped stdin)"}
                    )
                )
                return 2
            candidates = _payloads_from_text(sys.stdin.read())
        else:
            with open(candidate_path) as f:
                candidates = _payloads_from_text(f.read())
    except OSError as exc:
        print(json.dumps({"error": f"{type(exc).__name__}: {exc}"}))
        return 2
    if not baselines:
        print(json.dumps({"error": f"no baseline payloads in {baseline_path}"}))
        return 2
    code, verdicts = check_payloads(
        baselines, candidates, threshold,
        require_all="--check-require-all" in argv,
    )
    for verdict in verdicts:
        print(json.dumps(verdict), flush=True)
    return code


def _rep_stats(values: list) -> dict:
    """Dispersion of the per-rep steady-state measurements, as first-class
    payload fields (ROADMAP item 3: a bench number without its spread is not
    evidence). rel_spread = (max - min) / median; 0.0 for a single rep."""
    import statistics

    med = float(statistics.median(values))
    lo, hi = float(min(values)), float(max(values))
    return {
        "reps": len(values),
        "median": round(med, 1),
        "min": round(lo, 1),
        "max": round(hi, 1),
        "rel_spread": round((hi - lo) / med, 4) if med > 0 else 0.0,
    }


def main() -> None:
    if "--check" in sys.argv:
        # The regression gate is pure JSON arithmetic: no probe, no watchdog,
        # no jax import — exit before any of that machinery arms.
        sys.exit(run_check(sys.argv))
    smoke = "--smoke" in sys.argv
    reps = _parse_reps(sys.argv)
    large = "--large" in sys.argv  # MXU-bound variant: 1024x1024 bf16 torsos
    cartpole = "--cartpole" in sys.argv
    sebulba = "--sebulba" in sys.argv
    pixel = "--pixel" in sys.argv  # Sebulba on 84x84x4 frames + Nature CNN
    serve = "--serve" in sys.argv  # latency frontier: dynamic-batching policy serving
    replay = "--replay" in sys.argv  # sharded replay service microbench
    population = "--population" in sys.argv  # P agents as one jitted program
    gossip = "--gossip" in sys.argv  # grouped learners + gossip averaging
    elastic = "--elastic" in sys.argv  # fault-injected resize recovery wall
    loop = "--loop" in sys.argv  # closed train→serve→experience loop under chaos
    # Arm the state-integrity sentinel in the Anakin probe run so the payload's
    # integrity fields carry a MEASURED per-window fingerprint overhead
    # (docs/DESIGN.md §2.9) instead of the disabled zeros.
    integrity_on = "--integrity" in sys.argv
    run_all = "--all" in sys.argv
    if large and cartpole:
        sys.exit("--large is the MXU-bound Ant variant; it does not compose with --cartpole")
    if (sebulba or pixel) and (large or cartpole) or (sebulba and pixel):
        sys.exit("--sebulba/--pixel are their own workloads; they do not compose")
    if serve and (large or cartpole or sebulba or pixel):
        sys.exit("--serve is its own (latency-shaped) workload; it does not compose")
    if serve and integrity_on:
        # Refuse rather than silently measure nothing: the training sentinel
        # never runs in the serving workload (its integrity story is the
        # hot-swap canary, always on).
        sys.exit("--integrity arms the TRAINING sentinel; it does not compose with --serve")
    if replay and (large or cartpole or sebulba or pixel or serve):
        sys.exit("--replay is its own (transport-shaped) workload; it does not compose")
    if replay and integrity_on:
        sys.exit("--integrity arms the TRAINING sentinel; it does not compose with --replay")
    if population and (large or cartpole or sebulba or pixel or serve or replay):
        sys.exit("--population is its own workload family; it does not compose")
    if population and integrity_on:
        # The replica-fingerprint sentinel assumes replicated state; population
        # members are SHARDED over the pop axis (the runner itself refuses the
        # combination — docs/DESIGN.md §2.11), so refuse loudly here too.
        sys.exit("--integrity does not compose with --population "
                 "(use arch.population.member_fingerprints)")
    if gossip and (large or cartpole or sebulba or pixel or serve or replay or population):
        sys.exit("--gossip is its own workload family; it does not compose")
    if gossip and integrity_on:
        # Replica fingerprints assume ONE replicated state; gossip groups
        # intentionally diverge between rounds (the grouped learner setup
        # itself refuses the combination — docs/DESIGN.md §2.12).
        sys.exit("--integrity does not compose with --gossip "
                 "(groups diverge between gossip rounds by design)")
    if elastic and (large or cartpole or sebulba or pixel or serve or replay
                    or population or gossip):
        sys.exit("--elastic is its own (recovery-shaped) workload; it does not compose")
    if elastic and integrity_on:
        sys.exit("--integrity arms the TRAINING sentinel; it does not compose with --elastic")
    if loop and (large or cartpole or sebulba or pixel or serve or replay
                 or population or gossip or elastic):
        sys.exit("--loop is its own (closed-loop) workload; it does not compose")
    if loop and integrity_on:
        # The loop's integrity story is the hot-swap canary + fleet-wide
        # rollback (always on); the training sentinel never runs here.
        sys.exit("--integrity arms the TRAINING sentinel; it does not compose with --loop")
    if run_all and (large or cartpole or sebulba or pixel or serve or replay
                    or population or gossip or elastic or loop):
        sys.exit("--all runs the five tracked configs; it does not compose with variants")

    env_tag = "cartpole" if cartpole else "ant"
    if run_all:
        metric = "bench_all"
    elif replay:
        metric = "replay_sharded_sample_items_per_sec"
    elif serve:
        metric = "serve_ppo_identity_game_p99_latency_ms"
    elif loop:
        metric = "loop_policy_improvement_return_delta"
    elif pixel:
        metric = "sebulba_ppo_breakout_pixel_env_steps_per_sec"
    elif sebulba:
        metric = "sebulba_ppo_cartpole_env_steps_per_sec"
    elif population:
        metric = "population_ppo_identity_game_env_steps_per_sec"
    elif gossip:
        metric = "gossip_ppo_identity_game_env_steps_per_sec"
    elif elastic:
        metric = "elastic_recovery_wall_s"
    else:
        metric = f"anakin_ppo_{env_tag}_env_steps_per_sec" + ("_large_bf16" if large else "")

    # Watchdog: a device runtime can wedge indefinitely. A SIGALRM handler is
    # NOT enough — Python signal handlers only run between bytecodes, and a
    # wedged backend blocks the main thread inside a native PJRT call, so the
    # alarm never fires. A timer THREAD + os._exit works regardless of what
    # the main thread is stuck in.
    import os
    import threading

    # Exactly ONE exit path may ever own the process. Every exit path (success,
    # watchdog, probe failure) must first win this once-lock; losers park.
    # Without it a watchdog firing while the main thread is finishing could
    # emit a result line AND a failure.
    _once = threading.Lock()

    def _block_forever() -> None:
        # Lock loser: the winning exit path owns the process and will
        # os._exit when it is done. Returning instead would let the loser keep
        # running — a recovered main thread would hit later code (tracebacks /
        # second output lines).
        while True:
            time.sleep(3600)

    # `probe_attempts` (how many subprocess probes it took to get a verdict)
    # rides every payload and every failure: "chip wedged after N retries" is
    # a different event from a backend that answered first time.
    probe_attempts = 0
    # The device as jax reports it, set once the backend is up: every result
    # line says what it ran on, so a --cpu wiring-check number can never be
    # read as a device measurement (nor compared with the v5e baseline).
    device_stamp: dict | None = None

    def _stamp(payload: dict) -> dict:
        payload["probe_attempts"] = probe_attempts
        if device_stamp is not None:
            payload["device"] = device_stamp
            if device_stamp["platform"] != "tpu" and "vs_baseline" in payload:
                payload["vs_baseline"] = None
        return payload

    def _fail(reason: str) -> None:
        """The device runtime is unavailable or a workload died. There is no
        fallback: this process measured nothing, so it prints NO result line
        on stdout — only the typed reason on stderr — and exits non-zero
        (os._exit: the watchdog calls this from a timer thread while the main
        thread may be wedged inside a native PJRT call)."""
        if not _once.acquire(blocking=False):
            _block_forever()  # another exit path owns the process
        watchdog.cancel()
        print(
            json.dumps(_stamp({"metric": metric, "error": reason})),
            file=sys.stderr,
            flush=True,
        )
        os._exit(1)

    # The init watchdog is CREATED here (so every _fail path can cancel it)
    # but only STARTED after the probe: the probe is self-bounded (per-attempt
    # subprocess timeout + capped backoff), and a 180s timer racing a probe
    # budget that can legitimately exceed it (3 x 90s) would fire mid-probe
    # and report an untyped TIMEOUT with probe_attempts=0 — exactly the
    # ambiguity the probe fields exist to remove.
    watchdog = threading.Timer(180.0, _fail, args=("TIMEOUT: backend init unresponsive",))
    watchdog.daemon = True

    # Probe the device runtime in a SUBPROCESS with bounded timeout +
    # exponential-backoff retries (stoix_tpu/resilience/preflight.py) BEFORE
    # this process imports jax: a wedged PJRT runtime wedges the probe child
    # — which the timeout kills and the backoff retries — never this parent.
    # One process per chip: the child DOES touch the chip, and probe_backend
    # returns only after it has exited (subprocess.run waits, and kills on
    # timeout), so the chip is free again when this parent first calls
    # jax.devices() below.
    if "--cpu" not in sys.argv:
        from stoix_tpu.resilience.errors import BackendUnavailableError
        from stoix_tpu.resilience.preflight import probe_backend

        try:
            # Env-tunable so CI (and the chaos tests) can shrink the deadline.
            backend = probe_backend(
                timeout_s=float(os.environ.get("STOIX_BENCH_PROBE_TIMEOUT", "90")),
                attempts=int(os.environ.get("STOIX_BENCH_PROBE_ATTEMPTS", "3")),
                backoff_base_s=2.0,
                backoff_max_s=20.0,
            )
            probe_attempts = backend.attempts
        except BackendUnavailableError as exc:
            probe_attempts = exc.attempts
            _fail(
                f"BACKEND UNAVAILABLE: {exc.attempts} probe attempts failed "
                f"({exc.timeout_s:.0f}s deadline each); last: {exc.last_error}"
            )

    # Healthy probe verdict (or forced CPU): the watchdog now guards only
    # THIS process's own backend init, which the probe cannot fully vouch for.
    watchdog.start()

    if (replay or gossip) and "--cpu" in sys.argv:
        # The replay microbench measures CROSS-SHARD transport and the gossip
        # workload needs a group axis of 2: a 1-device CPU run would measure
        # nothing, so fan the host platform out to 8 virtual devices (the
        # tests/conftest harness) before jax imports.
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            )

    import jax

    if "--cpu" in sys.argv:
        jax.config.update("jax_platforms", "cpu")

    # Backend init can also fail outright in THIS process even after a healthy
    # probe: report it typed and exit non-zero, like every other failure.
    try:
        devices = jax.devices()
    except Exception as exc:  # noqa: BLE001 — any backend-init error is terminal here
        _fail(f"BACKEND INIT FAILED: {type(exc).__name__}: {exc}")
    n_devices = len(devices)
    device_stamp = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": n_devices,
    }

    # Compile economy (docs/DESIGN.md §2.7): the persistent cache goes on
    # before any workload compiles; workloads that compose a config re-apply
    # its admission knobs through the same call.
    from stoix_tpu.utils import compilecache

    compilecache.configure()

    # Healthy chip: swap in the long-deadline watchdog for the timed run(s).
    watchdog.cancel()
    watchdog = threading.Timer(
        3400.0 if run_all else 1800.0,
        _fail,
        args=("TIMEOUT: device runtime unresponsive",),
    )
    watchdog.daemon = True
    watchdog.start()

    def _finish(payloads: list, code: int = 0) -> None:
        # Success path competes for the same once-lock: if a failure handler
        # already owns the process (watchdog fired), park this thread and let
        # the owner finish.
        if not _once.acquire(blocking=False):
            _block_forever()
        watchdog.cancel()
        for payload in payloads:
            print(json.dumps(_stamp(payload)), flush=True)
        os._exit(code)

    if run_all:
        workloads = [
            ("anakin_ppo_ant_env_steps_per_sec",
             lambda: _run_anakin_ppo(smoke, False, False, n_devices, reps=reps,
                                     integrity_on=integrity_on)),
            ("anakin_c51_snake_env_steps_per_sec",
             lambda: _run_anakin_generic(
                 "anakin_c51_snake_env_steps_per_sec",
                 "default/anakin/default_ff_c51.yaml",
                 _c51_setup, ["env=snake"], smoke, n_devices,
                 "snake, sharded replay", reps=reps)),
            ("anakin_sac_ant_env_steps_per_sec",
             lambda: _run_anakin_generic(
                 "anakin_sac_ant_env_steps_per_sec",
                 "default/anakin/default_ff_sac.yaml",
                 "stoix_tpu.systems.sac.ff_sac", ["env=ant"], smoke, n_devices,
                 "ant, off-policy replay", reps=reps)),
            ("anakin_mz_cartpole_env_steps_per_sec",
             lambda: _run_anakin_generic(
                 "anakin_mz_cartpole_env_steps_per_sec",
                 "default/anakin/default_ff_mz.yaml",
                 "stoix_tpu.systems.search.ff_mz", [], smoke, n_devices,
                 "cartpole, on-device MCTS", reps=reps)),
            ("sebulba_ppo_cartpole_env_steps_per_sec",
             lambda: _run_sebulba(
                 "sebulba_ppo_cartpole_env_steps_per_sec", smoke, n_devices,
                 reps=reps, integrity_on=integrity_on)),
        ]
        payloads = []
        failed = False
        for name, workload in workloads:
            # One failing config must not cost the others their lines: report
            # it as a value-0 structured failure line — and exit non-zero, so
            # no caller can read a run with a dead workload as a clean one.
            try:
                payloads.append(workload())
            except Exception as exc:  # noqa: BLE001 — reported in-band, run exits 1
                failed = True
                payloads.append(
                    {
                        "metric": name,
                        "value": 0.0,
                        "unit": f"WORKLOAD FAILED: {type(exc).__name__}: {exc}",
                        "vs_baseline": None,
                    }
                )
        _finish(payloads, code=1 if failed else 0)
        return

    if pixel:
        # Pixel frames are ~113KB/env/step host->device; size the run so a
        # steady-state window closes within the watchdog.
        _finish([
            _run_sebulba(
                metric, smoke, n_devices,
                env_overrides=["env=breakout_pixel", "network=cnn_atari"],
                num_envs=16 if smoke else 128,
                num_updates=4 if smoke else 16,
                rollout_length=8 if smoke else 32,
                num_evaluation=2 if smoke else 4,
                pool_desc="84x84x4 C++ pixel pool, Nature CNN",
                reps=reps,
                integrity_on=integrity_on,
            )
        ])
        return

    if replay:
        _finish([_run_replay(metric, smoke, n_devices, reps=reps)])
        return

    if serve:
        _finish([_run_serve(metric, smoke, n_devices, reps=reps)])
        return

    if loop:
        _finish([_run_loop(metric, smoke, n_devices, reps=reps)])
        return

    if population:
        _finish(_run_population(smoke, n_devices, reps=reps))
        return

    if gossip:
        _finish(_run_gossip(smoke, n_devices, reps=reps))
        return

    if elastic:
        _finish([_run_elastic(metric, smoke, reps=reps)])
        return

    if sebulba:
        _finish([
            _run_sebulba(metric, smoke, n_devices, reps=reps, integrity_on=integrity_on)
        ])
        return

    _finish([
        _run_anakin_ppo(
            smoke, cartpole, large, n_devices, metric=metric, reps=reps,
            integrity_on=integrity_on,
        )
    ])


def _resilience_selfcheck(config, skipped_before: float) -> dict:
    """Resilience posture of the benched run (docs/DESIGN.md §2.3), recorded
    so a BENCH_*.json number can never silently hide an active divergence
    guard (guard selection adds ops) or a run that trained through skipped
    updates: guard mode, skipped-update count during this workload, and
    whether the config could emergency-checkpoint+resume on preemption."""
    from stoix_tpu.resilience import guards

    return {
        "update_guard": guards.resolve_mode(config),
        "skipped_updates": guards.skipped_counter().value() - skipped_before,
        "resume_capable": bool(config.logger.checkpointing.get("save_model", False)),
    }


def _skipped_updates_base() -> float:
    from stoix_tpu.resilience import guards

    return guards.skipped_counter().value()


def _integrity_report(stats_source) -> dict:
    """First-class integrity fields for a bench payload (docs/DESIGN.md
    §2.9): whether the state-integrity sentinel ran, how many fingerprint
    checks it performed, and its host-side overhead in seconds — so the
    sentinel's hot-path cost is VISIBLE next to the throughput number it
    taxes (and a BENCH_*.json line can never hide an active sentinel). The
    numbers come from the run's LAST_RUN_STATS (the probe run for Anakin
    payloads); a run without the sentinel reports the disabled shape."""
    from stoix_tpu.resilience import integrity as integrity_mod

    stats = dict((stats_source or {}).get("integrity") or {})
    if not stats:
        return integrity_mod.disabled_stats()
    return {
        "enabled": bool(stats.get("enabled", False)),
        "fingerprint_checks": int(stats.get("fingerprint_checks", 0)),
        "overhead_s": round(float(stats.get("overhead_s", 0.0)), 6),
        "probe_runs": int(stats.get("probe_runs", 0)),
    }


def _goodput_report(stats_source) -> dict:
    """First-class goodput ledger fields for a bench payload (docs/DESIGN.md
    §2.13): the compute fraction of wall time plus the badput components that
    taxed it (stall/recovery seconds) and the full per-phase fraction map,
    whose values sum to 1 (tests/test_bench_schema.py pins the shape). Runs
    that never opened a ledger report the schema-complete zero shape."""
    from stoix_tpu.observability import goodput as goodput_mod

    report = dict((stats_source or {}).get("goodput") or {})
    if not report:
        report = goodput_mod.disabled_report()
    return {
        "wall_s": round(float(report.get("wall_s", 0.0)), 6),
        "fraction": round(float(report.get("fraction", 0.0)), 6),
        "stall_s": round(float(report.get("stall_s", 0.0)), 6),
        "recovery_s": round(float(report.get("recovery_s", 0.0)), 6),
        "fractions": dict(report.get("fractions") or {}),
    }


def _timed_anakin_run(config, learner_setup, smoke: bool, reps: int | None = None):
    """Shared timed-loop core: compose -> setup -> warmup -> N timed reps of
    the steady-state window (`--reps`, default 3). Returns
    (best_steps_per_sec, per_rep_steps_per_sec, compile_info) — the headline
    stays the best rep; the full list feeds the dispersion fields, and
    compile_info carries the first-class compile economy fields (compile_s =
    the warmup call's wall time, cache_hits = persistent-cache hits during
    this workload; docs/DESIGN.md §2.7)."""
    import jax
    import numpy as np

    from stoix_tpu import envs
    from stoix_tpu.parallel import create_mesh
    from stoix_tpu.utils import compilecache
    from stoix_tpu.utils.timestep_checker import check_total_timesteps

    # Persistent cache + system.multistep_impl (the bench drives
    # learner_setup directly, not run_anakin_experiment, so it wires both
    # itself — otherwise a line claiming to measure the assoc kernel would
    # silently measure scan).
    from stoix_tpu.ops import scan_kernels

    compilecache.configure(config)
    scan_kernels.configure_from_config(config)
    mesh = create_mesh({"data": -1})
    updates_per_call = 2 if smoke else 8
    config.arch.num_updates = updates_per_call * (3 if not smoke else 1)
    config.arch.total_timesteps = None
    config.arch.num_evaluation = 3 if not smoke else 1
    config = check_total_timesteps(config, int(mesh.shape["data"]))

    env, _ = envs.make(config)
    key = jax.random.PRNGKey(0)
    setup = learner_setup(env, config, mesh, key)
    # Off-policy setups return (AnakinSetup, warmup): run the replay warmup
    # outside the timed window, exactly as the runner does. AnakinSetup is
    # itself a NamedTuple, so detect the pair by the missing .learn attribute.
    warmup = None
    if not hasattr(setup, "learn"):
        setup, warmup = setup
    learn, learner_state = setup.learn, setup.learner_state
    if warmup is not None:
        learner_state = warmup(learner_state)

    steps_per_call = (
        int(config.system.rollout_length)
        * int(config.arch.total_num_envs)
        * int(config.arch.num_updates_per_eval)
    )

    def force(out):
        # Materialize a scalar on the host: the timed region ends only when
        # the device has produced the value.
        leaf = jax.tree.leaves(out.learner_state.params)[0]
        return float(np.asarray(jax.numpy.sum(leaf)))

    # Warmup / compile. The wall time of this first call is the payload's
    # `compile_s` (XLA compile + one un-timed window); `cache_hits` records
    # how much of the compile the persistent cache absorbed.
    cache_before = compilecache.cache_stats()
    compile_start = time.perf_counter()
    out = learn(learner_state)
    force(out)
    compile_info = {
        "compile_s": round(time.perf_counter() - compile_start, 3),
        "cache_hits": compilecache.cache_stats()["hits"] - cache_before["hits"],
    }
    learner_state = out.learner_state

    times = []
    for _ in range(reps if reps is not None else 3):
        start = time.perf_counter()
        out = learn(learner_state)
        force(out)
        learner_state = out.learner_state
        times.append(time.perf_counter() - start)

    return (
        steps_per_call / min(times),
        [steps_per_call / t for t in times],
        compile_info,
    )


def _phase_breakdown_probe(
    default_yaml: str, setup_module: str, env_overrides: list, smoke: bool, n_devices: int
) -> tuple:
    """Run ONE tiny experiment through the pipelined Anakin runner to capture
    the per-phase host-loop breakdown (compile_s and every phase of the
    runner's clock: learn_s/snapshot_s/eval_s/fetch_dispatch_s/fetch_s/log_s/
    host_s/ckpt_s, forwarded as the runner reports them). The headline SPS stays the timed learn-loop measurement; this
    probe is what surfaces where host time goes per eval window. The probe
    runs with telemetry ENABLED (stoix_tpu/observability), so the payload
    also carries the telemetry self-check: span count, registry series
    count, and whether the exported trace validates against the Chrome
    trace-event schema. A probe that fails raises: a zeroed breakdown would
    read as "the phases took no time". Returns (phase_breakdown, telemetry)."""
    import importlib

    from stoix_tpu import observability
    from stoix_tpu.systems import runner as anakin_runner
    from stoix_tpu.utils import config as config_lib

    try:
        overrides = list(env_overrides) + [
            "arch.total_num_envs=%d" % (8 * n_devices),
            "system.rollout_length=8",
            "arch.num_updates=%d" % (2 * (2 if smoke else 8)),
            "arch.total_timesteps=~",
            "arch.num_evaluation=2",
            "arch.num_eval_episodes=%d" % n_devices,
            "arch.eval_max_steps=128",
            "arch.absolute_metric=False",
            "logger.use_console=False",
            "logger.telemetry.enabled=True",
        ]
        config = config_lib.compose(
            config_lib.default_config_dir(), default_yaml, overrides
        )
        module = importlib.import_module(setup_module)
        anakin_runner.run_anakin_experiment(config, module.learner_setup)
        stats = anakin_runner.LAST_RUN_STATS
        phases = {**stats["phase_breakdown"], "steady_state_sps": round(
            float(stats["steady_state_sps"]), 1
        )}
        telemetry = {
            "spans": observability.get_recorder().event_count(),
            "metric_series": observability.get_registry().series_count(),
            "trace_valid": not observability.validate_chrome_trace(
                observability.to_chrome_trace()
            ),
        }
        return phases, telemetry
    finally:
        # The TelemetrySink only shuts telemetry down on a CLEAN run end; a
        # probe crash must not leave span recording + the poller thread on.
        # Idempotent after a clean end.
        observability.shutdown()


def _run_anakin_ppo(
    smoke, cartpole, large, n_devices, metric=None, reps=None, integrity_on=False
) -> dict:
    from stoix_tpu.utils import config as config_lib

    env_tag = "cartpole" if cartpole else "ant"
    if metric is None:
        metric = f"anakin_ppo_{env_tag}_env_steps_per_sec" + ("_large_bf16" if large else "")
    overrides = [
        "arch.total_num_envs=%d" % (2048 * n_devices if not smoke else 8 * n_devices),
        "system.rollout_length=%d" % ((64 if cartpole else 16) if not smoke else 8),
        "arch.num_evaluation=1",
        "arch.num_eval_episodes=%d" % max(8, n_devices),
        "arch.absolute_metric=False",
        "logger.use_console=False",
    ]
    if not cartpole:
        overrides.append("env=ant")
    probe_overrides = [] if cartpole else ["env=ant"]
    if integrity_on:
        # --integrity: arm the state-integrity sentinel in the probe run so
        # its per-window fingerprint overhead is measured by the REAL
        # pipelined runner and surfaces in the payload's integrity fields.
        probe_overrides.append("arch.integrity.enabled=True")
    if large:
        large_overrides = [
            "network.actor_network.pre_torso.layer_sizes=[1024,1024]",
            "network.actor_network.pre_torso.compute_dtype=bfloat16",
            "network.critic_network.pre_torso.layer_sizes=[1024,1024]",
            "network.critic_network.pre_torso.compute_dtype=bfloat16",
        ]
        overrides += large_overrides
        probe_overrides += large_overrides  # phase attribution for the SAME regime
    default_yaml = (
        "default/anakin/default_ff_ppo.yaml"
        if cartpole
        else "default/anakin/default_ff_ppo_continuous.yaml"
    )
    config = config_lib.compose(config_lib.default_config_dir(), default_yaml, overrides)

    if cartpole:
        from stoix_tpu.systems.ppo.anakin.ff_ppo import learner_setup
    else:
        from stoix_tpu.systems.ppo.anakin.ff_ppo_continuous import learner_setup

    skipped_before = _skipped_updates_base()
    steps_per_sec, rep_values, compile_info = _timed_anakin_run(
        config, learner_setup, smoke, reps
    )
    per_chip = steps_per_sec / n_devices
    baseline_per_chip = 1_000_000 / 64  # BASELINE.json north star on v5e-64
    # Host-loop phase attribution + telemetry self-check from a tiny
    # pipelined-runner probe run (2 eval windows, telemetry enabled); see
    # systems/runner.py LAST_RUN_STATS and stoix_tpu/observability.
    phase_breakdown, telemetry = _phase_breakdown_probe(
        default_yaml, learner_setup.__module__, probe_overrides, smoke, n_devices,
    )
    from stoix_tpu.systems import runner as anakin_runner

    return {
        "metric": metric,
        "value": round(steps_per_sec, 1),
        "unit": f"env_steps/sec ({n_devices} devices, {env_tag})",
        # The baseline is defined for the tracked ant config only.
        "vs_baseline": (
            None if (large or cartpole) else round(per_chip / baseline_per_chip, 3)
        ),
        **_rep_stats(rep_values),
        **compile_info,
        "phase_breakdown": phase_breakdown,
        "telemetry": telemetry,
        "resilience": _resilience_selfcheck(config, skipped_before),
        # Sentinel posture of the probe run (the probe exercises the real
        # runner, fingerprints included when --integrity arms them).
        "integrity": _integrity_report(anakin_runner.LAST_RUN_STATS),
        # Goodput ledger of the probe run (same source as phase_breakdown).
        "goodput": _goodput_report(anakin_runner.LAST_RUN_STATS),
    }


def _run_replay(metric, smoke, n_devices, reps=None) -> dict:
    """Sharded replay service microbench (docs/DESIGN.md §2.10): prioritized
    add -> sample -> set_priorities cycles against a data mesh spanning every
    device, with a DQN-shaped transition row (64-float observations). The
    headline is sampled items/sec (best rep); the payload's transport ledger
    — ingested_bytes_total vs sampled_bytes_crossed — is the measured form
    of the samples-not-experience claim: raw experience is written to its
    owning shard and never moves, only sampled minibatches (plus index/
    priority vectors) ride the interconnect."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from stoix_tpu.replay import ShardedReplayService

    devices = jax.devices()
    mesh = Mesh(np.array(devices), ("data",))
    obs_dim = 64
    item = {
        "obs": jnp.zeros((obs_dim,), jnp.float32),
        "action": jnp.zeros((), jnp.int32),
        "reward": jnp.zeros((), jnp.float32),
        "done": jnp.zeros((), bool),
        "next_obs": jnp.zeros((obs_dim,), jnp.float32),
    }
    capacity = 512 if smoke else 4096
    batch = 128 if smoke else 512
    chunk = (256 if smoke else 2048) // n_devices * n_devices
    cycles = 8 if smoke else 64
    service = ShardedReplayService(
        mesh, item,
        capacity_per_shard=capacity,
        sample_batch_size=batch,
        prioritized=True,
        priority_exponent=0.6,
    )
    base = service.stats()
    sharded = NamedSharding(mesh, P("data"))
    rng = np.random.default_rng(0)
    host_chunk = {
        "obs": rng.normal(size=(chunk, obs_dim)).astype(np.float32),
        "action": rng.integers(0, 4, size=(chunk,)).astype(np.int32),
        "reward": rng.normal(size=(chunk,)).astype(np.float32),
        "done": np.zeros((chunk,), bool),
        "next_obs": rng.normal(size=(chunk, obs_dim)).astype(np.float32),
    }
    global_chunk = jax.device_put(host_chunk, sharded)
    key = jax.random.PRNGKey(0)

    def cycle(k):
        service.add(global_chunk)
        drawn = service.sample(k)
        service.set_priorities(drawn.indices, jnp.abs(drawn.probabilities) + 0.5)
        return drawn

    # Warmup: pay every op's compile outside the timed window.
    key, wk = jax.random.split(key)
    jax.block_until_ready(cycle(wk).probabilities)

    rep_sample_rates, rep_add_rates = [], []
    for _ in range(reps if reps is not None else 3):
        start = time.perf_counter()
        drawn = None
        for _ in range(cycles):
            key, ck = jax.random.split(key)
            drawn = cycle(ck)
        jax.block_until_ready(drawn.probabilities)
        wall = time.perf_counter() - start
        rep_sample_rates.append(cycles * batch / wall)
        rep_add_rates.append(cycles * chunk / wall)
    best_idx = max(range(len(rep_sample_rates)), key=lambda i: rep_sample_rates[i])
    stats = service.stats()
    delta = {k: stats[k] - base[k] for k in stats}
    occupancy = service.observe()
    return {
        "metric": metric,
        "value": round(rep_sample_rates[best_idx], 1),
        "unit": (
            f"sampled transitions/sec ({n_devices}-shard mesh, prioritized, "
            f"batch {batch}, {obs_dim}-float obs)"
        ),
        "vs_baseline": None,
        **_rep_stats(rep_sample_rates),
        "add_items_per_sec": round(rep_add_rates[best_idx], 1),
        "sample_items_per_sec": round(rep_sample_rates[best_idx], 1),
        "shards": n_devices,
        "ingested_bytes_total": delta["ingested_bytes_total"],
        "sampled_bytes_crossed": delta["sampled_bytes_crossed"],
        "sampled_to_ingested_ratio": round(
            delta["sampled_bytes_crossed"] / max(delta["ingested_bytes_total"], 1), 4
        ),
        "occupancy": occupancy["occupancy"],
        "priority_mass": occupancy["priority_mass"],
        # The microbench drives the service directly (no runner, no
        # sentinel): disabled shape, never a missing key.
        "integrity": _integrity_report(None),
        "goodput": _goodput_report(None),
    }


def _run_serve(metric, smoke, n_devices, reps=None) -> dict:
    """Latency-shaped serving workload (docs/DESIGN.md §2.8): train a tiny
    ff_ppo checkpoint, serve it through the dynamic-batching PolicyServer,
    drive the open-loop load generator for N windows, and report p99 request
    latency. Latency payloads carry direction=lower_is_better so the --check
    gate compares them the right way up, and `value` is the BEST (minimum)
    p99 rep — the mirror of the throughput payloads' best-rep maximum."""
    import os
    import shutil
    import tempfile

    from stoix_tpu.utils import config as config_lib

    tmp = tempfile.mkdtemp(prefix="stoix_serve_bench_")
    cwd = os.getcwd()
    os.chdir(tmp)
    try:
        from stoix_tpu.serve import PolicyServer, run_loadgen
        from stoix_tpu.systems.ppo.anakin import ff_ppo

        train_cfg = config_lib.compose(
            config_lib.default_config_dir(),
            "default/anakin/default_ff_ppo.yaml",
            [
                "env=identity_game",
                "arch.total_num_envs=16",
                "arch.total_timesteps=1024",
                "arch.num_evaluation=1",
                "arch.num_eval_episodes=8",
                "arch.absolute_metric=False",
                "system.rollout_length=8",
                "system.num_minibatches=2",
                "logger.use_console=False",
                f"logger.base_exp_path={tmp}/results",
                "logger.checkpointing.save_model=True",
                "logger.checkpointing.save_args.checkpoint_uid=serve-bench",
            ],
        )
        ff_ppo.run_experiment(train_cfg)
        store = os.path.join(tmp, "checkpoints", "serve-bench", "ff_ppo")

        offered_qps = 200.0 if smoke else 500.0
        duration_s = 2.0 if smoke else 10.0
        serve_cfg = config_lib.compose(
            config_lib.default_config_dir(),
            "default/serve.yaml",
            [
                f"arch.serve.checkpoint.path={store}",
                "arch.serve.batching.max_wait_ms=2.0",
                f"arch.serve.loadgen.offered_qps={offered_qps}",
                f"arch.serve.loadgen.duration_s={duration_s}",
            ],
        )
        server = PolicyServer.from_config(serve_cfg)
        reports = []
        with server:
            for _ in range(reps if reps is not None else 3):
                reports.append(
                    run_loadgen(
                        server, offered_qps=offered_qps, duration_s=duration_s
                    )
                )
        warmed = server.compile_count
        # A rep that completed zero requests has NO latency measurement —
        # exclude it rather than letting an empty-dict .get() default of 0
        # crown the broken rep as the best latency of the run. Every rep
        # empty means the workload failed: raise (the workload contract, like
        # any other failed bench config) instead of publishing value=0.
        p99s = [r["latency_ms"].get("p99") for r in reports]
        valid = [i for i, p in enumerate(p99s) if p]
        if not valid:
            raise RuntimeError(
                "load generator completed zero requests in every rep — no "
                "latency to report"
            )
        best_idx = min(valid, key=lambda i: p99s[i])
        best = reports[best_idx]
        return {
            "metric": metric,
            "value": round(p99s[best_idx], 3),
            "unit": (
                f"ms p99 request latency ({n_devices}-device host, "
                f"identity_game MLP policy, open-loop {offered_qps:g} qps)"
            ),
            "vs_baseline": None,
            "direction": "lower_is_better",
            **_rep_stats([p99s[i] for i in valid]),
            "offered_qps": best["offered_qps"],
            "achieved_qps": best["achieved_qps"],
            "requests": best["requests"],
            "shed": best["shed"],
            "errors": best["errors"],
            "latency_ms": best["latency_ms"],
            "batch_fill_ratio": best["batch_fill_ratio"],
            "hot_swaps": best["hot_swaps"],
            "compile_count": warmed,
            # Serving's integrity story is the hot-swap canary; the training
            # sentinel never runs here — disabled shape, never a missing key.
            "integrity": _integrity_report(None),
            "goodput": _goodput_report(None),
        }
    finally:
        os.chdir(cwd)
        shutil.rmtree(tmp, ignore_errors=True)


# The §2.15 chaos drill: a replica crash mid-traffic, one dragging replica,
# a wedged experience feeder, and one poisoned parameter push — the payload
# must show the loop rode ALL of them out (failover, re-admission, fleet-wide
# rollback) while still improving the policy.
LOOP_DRILL_FAULTS = "replica_kill:1,replica_slow:2,feedback_stall:3,swap_poison"


def _run_loop(metric, smoke, n_devices, reps=None) -> dict:
    """Closed-loop workload (docs/DESIGN.md §2.15): train a tiny ff_ppo
    checkpoint, then run the train→serve→experience loop TWICE at matched
    offered QPS — a frozen-policy control arm (no learning, no faults) and a
    live arm with the full chaos drill armed — and report the end-return
    delta (live minus frozen, episodes finishing in the last window). The
    delta is the paper claim in one number: the loop improves the policy
    under live traffic even while replicas crash, drag, the feedback path
    stalls, and a poisoned push is rolled back fleet-wide. The payload also
    enforces the resilience contract outright: non-zero silent drops, a
    drill with no failover, or no canary rollback FAIL the workload."""
    import os
    import shutil
    import tempfile

    from stoix_tpu.utils import config as config_lib

    tmp = tempfile.mkdtemp(prefix="stoix_loop_bench_")
    cwd = os.getcwd()
    os.chdir(tmp)
    try:
        from stoix_tpu.loop import run_loop
        from stoix_tpu.resilience import faultinject
        from stoix_tpu.systems.ppo.anakin import ff_ppo

        train_cfg = config_lib.compose(
            config_lib.default_config_dir(),
            "default/anakin/default_ff_ppo.yaml",
            [
                "env=identity_game",
                "arch.total_num_envs=16",
                "arch.total_timesteps=1024",
                "arch.num_evaluation=1",
                "arch.num_eval_episodes=8",
                "arch.absolute_metric=False",
                "system.rollout_length=8",
                "system.num_minibatches=2",
                "logger.use_console=False",
                f"logger.base_exp_path={tmp}/results",
                "logger.checkpointing.save_model=True",
                "logger.checkpointing.save_args.checkpoint_uid=loop-bench",
            ],
        )
        ff_ppo.run_experiment(train_cfg)
        store = os.path.join(tmp, "checkpoints", "loop-bench", "ff_ppo")

        offered_qps = 120.0
        duration_s = 6.0 if smoke else 12.0

        def _arm_config() -> object:
            return config_lib.compose(
                config_lib.default_config_dir(),
                "default/loop.yaml",
                [
                    f"arch.serve.checkpoint.path={store}",
                    f"arch.loop.traffic.offered_qps={offered_qps}",
                    f"arch.loop.traffic.duration_s={duration_s}",
                    "arch.loop.learner.publish_interval_s=1.0",
                ],
            )

        deltas, live_reports, frozen_reports = [], [], []
        for _ in range(reps if reps is not None else 1):
            # Control arm first: it only READS the store, so the live arm's
            # published steps never leak backwards into the baseline.
            faultinject.reset()
            frozen = run_loop(_arm_config(), frozen=True)
            faultinject.configure(LOOP_DRILL_FAULTS)
            try:
                live = run_loop(_arm_config(), frozen=False)
            finally:
                faultinject.reset()
            for arm, name in ((frozen, "frozen"), (live, "live")):
                if arm["silent_drops"]:
                    raise RuntimeError(
                        f"{name} arm silently dropped {arm['silent_drops']} "
                        "accepted request(s) — the zero-silent-drop contract "
                        "failed"
                    )
                if arm["return_mean_last_window"] is None:
                    raise RuntimeError(
                        f"{name} arm finished zero episodes — no return to "
                        "compare"
                    )
            router_stats = live["router_stats"]
            if not router_stats["failovers"]:
                raise RuntimeError(
                    "chaos drill observed no failover: the replica kill "
                    "never exercised the post-accept re-dispatch path"
                )
            if not live["publisher"]["rollbacks"]:
                raise RuntimeError(
                    "chaos drill observed no canary rollback: the poisoned "
                    "push never exercised the fleet-wide rollback path"
                )
            deltas.append(
                live["return_mean_last_window"] - frozen["return_mean_last_window"]
            )
            live_reports.append(live)
            frozen_reports.append(frozen)

        best_idx = max(range(len(deltas)), key=lambda i: deltas[i])
        best_live = live_reports[best_idx]
        best_frozen = frozen_reports[best_idx]
        # Return deltas live on an ~O(1) scale — _rep_stats' 0.1 rounding
        # (built for steps/sec) would crush them, so the dispersion fields
        # are computed inline at full precision (the _run_elastic pattern).
        lo, hi = min(deltas), max(deltas)
        med = sorted(deltas)[len(deltas) // 2]
        return {
            "metric": metric,
            "value": round(deltas[best_idx], 4),
            "unit": (
                f"end-return delta, live loop under chaos drill vs frozen "
                f"control ({n_devices}-device host, identity_game, matched "
                f"{offered_qps:g} qps)"
            ),
            "vs_baseline": None,
            "direction": "higher_is_better",
            "reps": len(deltas),
            "median": round(med, 4),
            "min": round(lo, 4),
            "max": round(hi, 4),
            "rel_spread": round((hi - lo) / med, 4) if med > 0 else 0.0,
            "fault_spec": LOOP_DRILL_FAULTS,
            "live_return": best_live["return_mean_last_window"],
            "frozen_return": best_frozen["return_mean_last_window"],
            "episodes": best_live["episodes"],
            "accepted": best_live["accepted"],
            "completed": best_live["completed"],
            "typed_failures": best_live["typed_failures"],
            "silent_drops": best_live["silent_drops"],
            "shed": best_live["router_stats"]["sheds"],
            "p99_latency_ms": best_live["latency_ms"].get("p99"),
            "latency_ms": best_live["latency_ms"],
            "failovers": best_live["router_stats"]["failovers"],
            "ejections": best_live["router_stats"]["ejections"],
            "readmissions": best_live["router_stats"]["readmissions"],
            "hedges": best_live["router_stats"]["hedges"],
            "replica_kills": best_live["replica_kills"],
            "replica_restarts": best_live["replica_restarts"],
            "canary_rollbacks": best_live["publisher"]["rollbacks"],
            "publishes": best_live["publisher"]["publishes"],
            "serving_step": best_live["serving_step"],
            "learner_updates": best_live["learner"]["updates"],
            "experience_dropped": best_live["recorder"]["dropped"],
            # The loop's integrity story is the hot-swap canary + rollback;
            # the training sentinel never runs here — disabled shape.
            "integrity": _integrity_report(None),
            "goodput": _goodput_report(None),
        }
    finally:
        os.chdir(cwd)
        shutil.rmtree(tmp, ignore_errors=True)


def _run_elastic(metric, smoke, reps=None) -> dict:
    """Recovery-shaped workload (docs/DESIGN.md §2.14): fault-injected
    shrink->grow resize cycles through the elastic supervision path
    (scripts/soak.py legs, forced-CPU children — the resize REQUIRES fresh
    processes, so the backend this parent probed is irrelevant to the
    measurement). The headline is the emergency-restore recovery wall per
    elastic relaunch — the seconds a resized incarnation spends re-reading
    and re-placing the rescue snapshot, exactly what the goodput ledger's
    recovery phase charges — with direction=lower_is_better so the --check
    gate compares it the right way up. cycles_survived counts cycles that
    upheld the full §2.14 contract, making a fast-but-broken relaunch
    (consumed nothing, restored nothing) impossible to publish as a win.

    One process per chip: by the time this runs the parent has called
    jax.devices() and holds the chip for its whole life. That is safe only
    because every child is pinned to the CPU backend (scripts/soak.py
    `_child_env` sets JAX_PLATFORMS=cpu and the child repeats it in code) and
    so never asks for the chip; a child that did would fail or hang."""
    import importlib.util
    import os
    import shutil
    import tempfile

    from stoix_tpu.resilience import fleet as fleet_lib

    spec = importlib.util.spec_from_file_location(
        "stoix_tpu_soak",
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "scripts", "soak.py"),
    )
    soak = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(soak)

    cycles = reps if reps is not None else (1 if smoke else 2)
    windows = 2 if smoke else 3
    devices = 8
    tmp = tempfile.mkdtemp(prefix="stoix_elastic_bench_")
    walls: list = []
    legs: list = []
    cycles_survived = 0
    last_stats = None
    try:
        for cycle in range(cycles):
            workdir = os.path.join(tmp, f"cycle{cycle}")
            cycle_problems: list = []
            start = devices
            for action in ("shrink", "grow"):
                leg = soak.run_leg(
                    workdir, action=action, devices=start, windows=windows
                )
                cycle_problems.extend(leg["problems"])
                report = fleet_lib.read_restore_report(
                    os.path.join(workdir, "fleet_emergency")
                )
                wall = float((report or {}).get("recovery_wall_s") or 0.0)
                if wall > 0.0:
                    walls.append(wall)
                legs.append(
                    {
                        "action": action,
                        "from_devices": start,
                        "to_devices": leg["target"],
                        "rc": leg["rc"],
                        "leg_wall_s": round(leg["wall_s"], 3),
                        "recovery_wall_s": round(wall, 6),
                        "problems": leg["problems"],
                    }
                )
                last_stats = leg["stats"] or last_stats
                start = leg["target"]
            if not cycle_problems:
                cycles_survived += 1
        if not walls:
            raise RuntimeError(
                "no elastic relaunch produced a restore report — no recovery "
                f"wall to report (legs: {legs})"
            )
        import statistics

        med = float(statistics.median(walls))
        lo, hi = float(min(walls)), float(max(walls))
        return {
            "metric": metric,
            "value": round(lo, 6),  # best rep (mirror of latency payloads)
            "unit": (
                f"s emergency-restore recovery wall per elastic relaunch "
                f"({devices}-device CPU shrink->grow cycles, identity_game "
                f"ff_ppo)"
            ),
            "vs_baseline": None,
            "direction": "lower_is_better",
            # recovery walls sit well under _rep_stats' 0.1s rounding grain,
            # so the dispersion fields are computed here at full precision.
            "reps": len(walls),
            "median": round(med, 6),
            "min": round(lo, 6),
            "max": round(hi, 6),
            "rel_spread": round((hi - lo) / med, 4) if med > 0 else 0.0,
            "cycles": cycles,
            "cycles_survived": cycles_survived,
            "legs": legs,
            "integrity": _integrity_report(None),
            "goodput": _goodput_report(last_stats),
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _c51_setup(env, config, mesh, key):
    from stoix_tpu.systems.q_learning.ff_c51 import _head_kwargs, c51_loss
    from stoix_tpu.systems.q_learning.q_family import q_learner_setup

    return q_learner_setup(env, config, mesh, key, c51_loss, _head_kwargs(config))


def _run_anakin_generic(
    metric: str,
    default_yaml: str,
    setup_fn,
    overrides: list,
    smoke: bool,
    n_devices: int,
    unit_tag: str,
    reps: int | None = None,
) -> dict:
    """One tracked non-PPO Anakin config: same timed loop, config-default run
    shape (the round-3 validated shapes live in the config defaults).
    `setup_fn` is a module path exposing learner_setup or the callable itself."""
    import importlib

    from stoix_tpu.utils import config as config_lib

    overrides = overrides + [
        "arch.num_evaluation=1",
        "arch.num_eval_episodes=%d" % max(8, n_devices),
        "arch.absolute_metric=False",
        "logger.use_console=False",
    ]
    if smoke:
        # rollout 8, not smaller: sequence-replay systems (MZ) need the first
        # buffer add to hold a full sample_sequence_length (6) sequence.
        overrides += ["arch.total_num_envs=%d" % (8 * n_devices), "system.rollout_length=8"]
    config = config_lib.compose(config_lib.default_config_dir(), default_yaml, overrides)
    if isinstance(setup_fn, str):
        setup_fn = importlib.import_module(setup_fn).learner_setup
    skipped_before = _skipped_updates_base()
    steps_per_sec, rep_values, compile_info = _timed_anakin_run(
        config, setup_fn, smoke, reps
    )
    return {
        "metric": metric,
        "value": round(steps_per_sec, 1),
        "unit": f"env_steps/sec ({n_devices} devices, {unit_tag})",
        # Only the PPO/ant north star has a numeric baseline.
        "vs_baseline": None,
        **_rep_stats(rep_values),
        **compile_info,
        "resilience": _resilience_selfcheck(config, skipped_before),
        # The generic timed loop drives the learner directly (no runner, no
        # sentinel): the integrity fields still ride with the disabled
        # shape, so consumers never see a missing key.
        "integrity": _integrity_report(None),
        "goodput": _goodput_report(None),
    }


def _run_population(smoke: bool, n_devices: int, reps: int | None = None) -> list:
    """`--population` (docs/DESIGN.md §2.11): P PPO agents trained as ONE
    jitted program on the ("pop", "data") mesh (stoix_tpu/population), at
    P=1 (the bit-identity anchor — population machinery at zero population)
    and P=8 with lifted ent_coef + on-device PBT. Two payload lines, one per
    P: value = AGGREGATE env-steps/sec (per-member steady-state SPS x P —
    the number that makes vmapped-population scaling visible), plus
    per-member fitness dispersion and the PBT exploit count."""
    from stoix_tpu.population import runner as pop_runner
    from stoix_tpu.systems import runner as anakin_runner
    from stoix_tpu.utils import config as config_lib

    payloads = []
    for pop_size in (1, 8):
        overrides = [
            "arch=population",
            "env=identity_game",
            "arch.total_num_envs=%d" % (8 if smoke else 64),
            "arch.num_updates=%d" % (4 if smoke else 32),
            "arch.total_timesteps=~",
            "arch.num_evaluation=2",
            "arch.num_eval_episodes=8",
            "arch.absolute_metric=False",
            "system.rollout_length=%d" % (8 if smoke else 16),
            "logger.use_console=False",
        ]
        config = config_lib.compose(
            config_lib.default_config_dir(), "default/anakin/default_ff_ppo.yaml",
            overrides,
        )
        config_lib._set_dotted(config, "arch.population.size", pop_size)
        if pop_size > 1:
            # A real sweep shape: per-member exploration coefficients, with
            # truncation selection live so the payload's exploit count is a
            # MEASURED number, not a config echo.
            config_lib._set_dotted(
                config, "arch.population.hparams",
                {"system.ent_coef": [round(0.001 * (i + 1), 4) for i in range(pop_size)]},
            )
            config_lib._set_dotted(
                config, "arch.population.pbt",
                {"enabled": True, "interval": 1, "quantile": 0.25,
                 "perturb_scale": 0.2},
            )
        skipped_before = _skipped_updates_base()
        aggregates = []
        for _ in range(reps if reps is not None else 1):
            pop_runner.run_population_experiment(config)
            steady = float(anakin_runner.LAST_RUN_STATS.get("steady_state_sps") or 0.0)
            if steady:
                # steady_state_sps counts PER-MEMBER env steps (the runner's
                # steps_per_eval is per member); the population executes P of
                # them simultaneously.
                aggregates.append(steady * pop_size)
        stats = dict(pop_runner.LAST_POPULATION_STATS)
        fitness = [float(f) for f in (stats.get("member_fitness") or [0.0])]
        member_dispersion = _rep_stats(fitness)
        member_dispersion["members"] = member_dispersion.pop("reps")
        payloads.append({
            "metric": f"population_ppo_identity_game_p{pop_size}_env_steps_per_sec",
            "value": round(max(aggregates), 1) if aggregates else 0.0,
            "unit": (
                f"aggregate env_steps/sec ({pop_size} members, "
                f"{n_devices} devices, identity_game)"
                if aggregates else "NO STEADY WINDOW: run ended before eval"
            ),
            "vs_baseline": None,
            **_rep_stats(aggregates if aggregates else [0.0]),
            "population_size": pop_size,
            "member_fitness_dispersion": member_dispersion,
            "pbt_enabled": bool(stats.get("pbt_enabled", False)),
            "pbt_exploits": int(stats.get("pbt_exploits", 0)),
            "compile_s": (anakin_runner.LAST_RUN_STATS.get("compile") or {}).get(
                "compile_s"
            ),
            "cache_hits": (anakin_runner.LAST_RUN_STATS.get("compile") or {}).get(
                "cache_hits", 0
            ),
            "resilience": _resilience_selfcheck(config, skipped_before)
            if not anakin_runner.LAST_RUN_STATS.get("resilience")
            else dict(anakin_runner.LAST_RUN_STATS.get("resilience")),
            "integrity": _integrity_report(anakin_runner.LAST_RUN_STATS),
            "goodput": _goodput_report(anakin_runner.LAST_RUN_STATS),
        })
    return payloads


def _run_gossip(smoke: bool, n_devices: int, reps: int | None = None) -> list:
    """`--gossip` (docs/DESIGN.md §2.12): grouped Anakin PPO on the
    ("group", "data") mesh (stoix_tpu/parallel/gossip.py). Two payload lines
    — lockstep (G=1: the bit-identity anchor, gossip machinery at zero
    groups, no mixing dispatched) and G=2 gossip groups (ring topology,
    params averaged every window). Each shape is measured CLEAN and again
    under an injected `host_stall:1` straggler window (faultinject), and
    `throughput_retained` = stalled/clean steady-state SPS rides along. On
    one host the stall taxes every group equally — the field exists so
    multi-slice runs can record how much of the lockstep all-reduce tax the
    gossip groups remove (the headline: lockstep pays the straggler on every
    dense window; a group only pays it at its own gossip edges)."""
    from stoix_tpu.resilience import faultinject
    from stoix_tpu.systems import runner as anakin_runner
    from stoix_tpu.utils import config as config_lib

    stall_s = 1
    payloads = []
    for num_groups in (1, 2):
        def _compose_run(fault: bool):
            overrides = [
                "arch=gossip",
                "env=identity_game",
                "arch.total_num_envs=%d" % (8 if smoke else 64),
                "arch.num_updates=%d" % (4 if smoke else 32),
                "arch.total_timesteps=~",
                "arch.num_evaluation=2",
                "arch.num_eval_episodes=8",
                "arch.absolute_metric=False",
                "system.rollout_length=%d" % (8 if smoke else 16),
                "logger.use_console=False",
            ]
            config = config_lib.compose(
                config_lib.default_config_dir(), "default/anakin/default_ff_ppo.yaml",
                overrides,
            )
            config_lib._set_dotted(config, "arch.mesh.group", num_groups)
            if fault:
                config_lib._set_dotted(
                    config, "arch.fault_spec", "host_stall:%d" % stall_s
                )
            return config

        def _run_once(config) -> float:
            faultinject.reset()
            try:
                from stoix_tpu.systems.ppo.anakin import ff_ppo as anakin_ppo

                anakin_ppo.run_experiment(config)
            finally:
                faultinject.reset()
            return float(anakin_runner.LAST_RUN_STATS.get("steady_state_sps") or 0.0)

        skipped_before = _skipped_updates_base()
        clean_config = _compose_run(False)
        clean = [s for s in (_run_once(clean_config) for _ in range(reps or 1)) if s]
        gossip_stats = dict(anakin_runner.LAST_RUN_STATS.get("gossip") or {})
        stalled = _run_once(_compose_run(True))
        resilience = (
            dict(anakin_runner.LAST_RUN_STATS.get("resilience") or {})
            or _resilience_selfcheck(clean_config, skipped_before)
        )
        tag = "lockstep" if num_groups == 1 else "g%d" % num_groups
        clean_best = max(clean) if clean else 0.0
        payloads.append({
            "metric": f"gossip_ppo_identity_game_{tag}_env_steps_per_sec",
            "value": round(clean_best, 1),
            "unit": (
                f"steady env_steps/sec ({num_groups} group(s), {n_devices} "
                f"devices, identity_game; stalled twin under host_stall:{stall_s})"
                if clean_best else "NO STEADY WINDOW: run ended before eval"
            ),
            "vs_baseline": None,
            **_rep_stats(clean if clean else [0.0]),
            "num_groups": num_groups,
            "topology": gossip_stats.get("topology"),
            "gossip_interval": gossip_stats.get("interval"),
            "gossip_rounds": gossip_stats.get("rounds", 0),
            "stall_s": stall_s,
            "stalled_env_steps_per_sec": round(stalled, 1),
            "throughput_retained": (
                round(stalled / clean_best, 4) if clean_best and stalled else None
            ),
            "resilience": resilience,
        })
    return payloads


def _run_sebulba(
    metric: str,
    smoke: bool,
    n_devices: int,
    env_overrides: list | None = None,
    num_envs: int | None = None,
    num_updates: int | None = None,
    rollout_length: int | None = None,
    num_evaluation: int | None = None,
    pool_desc: str = "C++ pool",
    reps: int | None = None,
    integrity_on: bool = False,
) -> dict:
    """Sebulba PPO on the native C++ pool; steady-state SPS. Default workload
    is the CartPole pool; `--pixel` swaps in the full-resolution 84x84x4
    Breakout-atari frames + Nature-DQN CNN (the EnvPool-Atari-shaped config).

    Device split: with 1 device everything shares it; with 2+ devices actors
    get device 0, the learner the rest (mirrors the validated CI split).
    """
    from stoix_tpu.systems.ppo.sebulba import ff_ppo as sebulba_ppo
    from stoix_tpu.utils import config as config_lib

    learner_ids = [0] if n_devices == 1 else list(range(1, n_devices))
    overrides = [
        *(env_overrides or ["env=cartpole", "env.backend=cvec"]),
        "arch.total_num_envs=%d"
        % (num_envs if num_envs is not None else (16 if smoke else 512)),
        "arch.actor.device_ids=[0]",
        "arch.actor.actor_per_device=%d" % (1 if smoke else 2),
        "arch.learner.device_ids=%s" % str(learner_ids).replace(" ", ""),
        "arch.evaluator_device_id=0",
        "arch.num_updates=%d"
        % (num_updates if num_updates is not None else (4 if smoke else 64)),
        "arch.total_timesteps=~",
        "arch.num_evaluation=%d"
        % (num_evaluation if num_evaluation is not None else (2 if smoke else 8)),
        "arch.num_eval_episodes=8",
        "arch.absolute_metric=False",
        "system.rollout_length=%d"
        % (rollout_length if rollout_length is not None else (8 if smoke else 64)),
        "logger.use_console=False",
    ]
    if integrity_on:
        # --integrity: Sebulba checks fingerprints at eval boundaries
        # (docs/DESIGN.md §2.9); the cost lands in the payload's integrity
        # fields via LAST_RUN_STATS.
        overrides.append("arch.integrity.enabled=True")
    config = config_lib.compose(
        config_lib.default_config_dir(), "default/sebulba/default_ff_ppo.yaml", overrides
    )
    # Queue health from the metrics registry (stoix_tpu/observability):
    # learner-side rollout get-wait is THE Sebulba backpressure signal —
    # near-zero means actors keep the learner fed. The registry is
    # process-cumulative, so report THIS run's delta (count/sum are
    # monotonic); shutdown-drain gets are uninstrumented by construction
    # (OnPolicyPipeline.drain), so they cannot deflate the mean.
    from stoix_tpu.observability import get_registry
    from stoix_tpu.utils import compilecache

    wait_hist = get_registry().histogram("stoix_tpu_sebulba_queue_get_wait_seconds")
    wait_labels = {"queue": "rollout", "actor": "0"}
    before = wait_hist.summary(wait_labels)
    cache_before = compilecache.cache_stats()
    skipped_before = _skipped_updates_base()
    # A Sebulba "rep" is a whole experiment (the steady window lives inside
    # the run), so re-measurement defaults to 1 and scales only on an
    # explicit --reps; `value` stays the best rep, like the Anakin loop.
    steadies = []
    fps_reps = []
    for _ in range(reps if reps is not None else 1):
        sebulba_ppo.run_experiment(config)
        rep_steady = sebulba_ppo.LAST_RUN_STATS.get("steps_per_sec_steady")
        if rep_steady:
            steadies.append(float(rep_steady))
        rep_fps = sebulba_ppo.LAST_RUN_STATS.get("fps")
        if rep_fps:
            fps_reps.append(float(rep_fps))
    steady = max(steadies) if steadies else None
    after = wait_hist.summary(wait_labels)
    d_count = int(after.get("count", 0)) - int(before.get("count", 0))
    d_sum = float(after.get("sum", 0.0)) - float(before.get("sum", 0.0))
    telemetry = {
        "rollout_get_wait_mean_s": round(d_sum / d_count, 6) if d_count else 0.0,
        "rollout_get_wait_count": d_count,
    }
    if steady:
        unit = "env_steps/sec (steady-state, %d devices, %s)" % (n_devices, pool_desc)
    else:
        # Zero values must carry their failure reason in `unit` (the bench
        # output contract): a missing steady window means the run ended before
        # the first eval block opened/closed it.
        unit = "NO STEADY WINDOW: first eval block never reached"
    # The run records its own resilience posture (guard mode, skipped count,
    # supervisor restarts — a restart mid-bench means the number was measured
    # through a recovery, which must be visible); fall back to the config
    # view only if the run never got far enough to publish it.
    resilience = dict(
        sebulba_ppo.LAST_RUN_STATS.get("resilience")
        or _resilience_selfcheck(config, skipped_before)
    )
    return {
        "metric": metric,
        "value": round(float(steady), 1) if steady else 0.0,
        "unit": unit,
        # Sebulba has no tracked numeric baseline (reference publishes
        # none for its sebulba arch); report the raw number.
        "vs_baseline": None,
        **_rep_stats(steadies if steadies else [0.0]),
        # Whole-run env frames per second, first-class (ROADMAP item-1
        # leftover): value = best rep, dispersion across reps. Distinct from
        # `value` (the post-compile steady-state window): fps includes the
        # first-rollout compile, so it is the fleet-provisioning number.
        "fps": {
            "value": round(max(fps_reps), 1) if fps_reps else 0.0,
            **_rep_stats(fps_reps if fps_reps else [0.0]),
        },
        # Sebulba pays its compiles inside the run (no separate AOT warmup
        # call to time), so compile_s is not separable here; cache_hits still
        # shows whether the persistent cache absorbed them.
        "compile_s": None,
        "cache_hits": compilecache.cache_stats()["hits"] - cache_before["hits"],
        "telemetry": telemetry,
        "resilience": resilience,
        "integrity": _integrity_report(sebulba_ppo.LAST_RUN_STATS),
        "goodput": _goodput_report(sebulba_ppo.LAST_RUN_STATS),
    }


if __name__ == "__main__":
    main()
