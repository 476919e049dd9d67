"""State-integrity sentinel: silent-corruption detection (docs/DESIGN.md §2.9).

Anakin's correctness story rests on an invariant the Podracer design gives us
by construction but nothing ever checked: after every gradient `pmean`, the
replicated learner state (params, optimizer moments) is **bit-identical on
every device and host**. PR 3's guards catch non-finite updates and PR 7's
fleet layer catches dead/frozen hosts — but a flaky core or an HBM bit-flip
produces *finite-but-wrong* values that train silently to garbage. The
invariant makes this the cheapest failure class to detect: ANY cross-replica
disagreement is a proof of corruption. Three mechanisms:

  * **In-jit replica fingerprints** — a tiny shard_mapped program folds each
    replicated state group (params, opt state, ...) to a per-device uint32
    fingerprint (bitcast to words + a murmur-style position-salted mix),
    emitted as a `[num_devices]` vector that rides the runner's EXISTING
    coalesced metric fetch exactly like the fleet flag vector: the reduction
    is local to each device, so the check costs zero extra collectives. The
    host compares all entries once the window materializes; a mismatch
    raises a typed `StateCorruptionError` naming the deviating device(s),
    process(es), and state group(s). Because the materialized vector is
    REPLICATED data, every host computes the same verdict at the same
    window — corruption agreement falls out of the transport.
  * **Corruption agreement + quarantine** — `FLAG_CORRUPT` joins the fleet
    flag byte (resilience/fleet.py) so the stop reason is visible in votes
    and stop-request telemetry; the sentinel's excepthook translates an
    uncaught StateCorruptionError into `EXIT_CODE_STATE_CORRUPTION` (88),
    distinct from the fleet-partition 87, and records the offending host in
    a quarantine file together with the resume overrides a supervising
    launcher needs (`launcher.py --supervise` relaunches on 88 and restores
    the newest digest-verified checkpoint).
  * **Determinism probe** (optional) — records one (state, minibatch-stream)
    input at the first window plus the fingerprint of the learn step's
    output, then periodically replays the SAME input through the SAME
    compiled program and compares fingerprints bitwise. A wrong-math core
    is caught even at replica count 1, where no cross-replica disagreement
    can exist. Costs one held state copy plus one learn execution per probe.

This module is also the shared home of the per-leaf sha256 **digest
manifest** the fleet emergency store introduced (PR 7): `leaf_digest` /
`digest_arrays` / `verify_digests` are used by the emergency store, by every
orbax save (utils/checkpointing.py writes a `_digests.json` sidecar and
`restore` verifies it, rejecting on-disk bit-rot instead of resuming it),
and by the serving loader's hot-swap canary (serve/).

Everything sits behind `arch.integrity` (off — the default — adds zero ops,
zero host work: the host loops are bit-identical, pinned by
tests/test_integrity.py). jax is imported lazily so digest helpers stay
usable from no-jax paths (bench --check).
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
import threading
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from stoix_tpu.observability import flightrec, get_logger, get_registry
from stoix_tpu.resilience.errors import StateCorruptionError

# Exit code of the corruption path: distinct from the watchdog's 86 and the
# fleet partition's 87 so `launcher.py --supervise` can tell "this host's
# STATE is corrupt — restore a digest-verified checkpoint and quarantine the
# offender" apart from "a peer died" (docs/DESIGN.md §2.6 exit-code table).
# Declared in the canonical registry (resilience/exit_codes.py, STX018);
# re-exported here because this module has owned the name since PR 12.
from stoix_tpu.resilience.exit_codes import EXIT_CODE_STATE_CORRUPTION

_GOLDEN = 0x9E3779B9  # 32-bit golden-ratio constant (position/group salt)


# ---------------------------------------------------------------------------
# Digest manifest helpers (shared: fleet emergency store, orbax sidecar,
# serving canary). sha256 over the raw host bytes — dtype-exact, so a single
# flipped bit anywhere in a leaf fails verification.
# ---------------------------------------------------------------------------


def leaf_digest(arr: np.ndarray) -> str:
    """sha256 hex digest of a host array's raw bytes (C-contiguous view)."""
    return hashlib.sha256(np.ascontiguousarray(arr).tobytes()).hexdigest()


def digest_arrays(arrays: Dict[str, np.ndarray]) -> Dict[str, str]:
    """Per-leaf digest record for a {key: host array} mapping."""
    return {key: leaf_digest(arr) for key, arr in arrays.items()}


def verify_digests(
    arrays: Dict[str, np.ndarray], record: Dict[str, str]
) -> List[str]:
    """Keys present in BOTH `arrays` and `record` whose bytes no longer match
    the recorded digest (empty list = verified). Keys absent from either side
    are not this function's verdict — the caller decides whether a missing
    leaf is corruption (orbax restore: yes) or topology (emergency store)."""
    return sorted(
        key
        for key, want in record.items()
        if key in arrays and leaf_digest(np.asarray(arrays[key])) != want
    )


# ---------------------------------------------------------------------------
# Settings
# ---------------------------------------------------------------------------


class IntegritySettings(NamedTuple):
    """Resolved `arch.integrity` config block (defaults applied)."""

    enabled: bool
    determinism_probe_interval: int
    quarantine_file: str


def settings_from_config(config: Any) -> IntegritySettings:
    cfg = (config.get("arch") or {}).get("integrity") or {}
    return IntegritySettings(
        enabled=bool(cfg.get("enabled", False)),
        determinism_probe_interval=int(cfg.get("determinism_probe_interval", 0) or 0),
        quarantine_file=str(
            cfg.get("quarantine_file") or os.path.join("checkpoints", "quarantine.json")
        ),
    )


# ---------------------------------------------------------------------------
# In-jit fingerprints
# ---------------------------------------------------------------------------


def _fmix32(x: Any) -> Any:
    """murmur3's 32-bit finalizer: a bijective avalanche mix, so any change
    to any input word changes the mixed word (uint32 arithmetic wraps)."""
    import jax.numpy as jnp

    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def _leaf_words(leaf: Any) -> Any:
    """A leaf's raw bits as a flat uint32 word vector: bool widens to uint8,
    multi-byte dtypes BITCAST to uint8 (exact bytes — a mantissa flip is a
    word change, never rounded away), then widen to uint32 for the mix."""
    import jax
    import jax.numpy as jnp

    x = jnp.asarray(leaf)
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.uint8)
    if x.dtype.itemsize > 1:
        x = jax.lax.bitcast_convert_type(x, jnp.uint8)
    return x.reshape(-1).astype(jnp.uint32)


def fingerprint_leaves(leaves: Sequence[Any], salt: int = 0) -> Any:
    """Fold a list of array leaves to ONE uint32 fingerprint (traceable,
    collective-free — safe to call per-device inside shard_map). Each word is
    salted by its position and its leaf's index before the avalanche mix, so
    a flip is detected wherever it lands and two identical flips at
    different positions cannot cancel."""
    import jax
    import jax.numpy as jnp

    acc = jnp.uint32(salt & 0xFFFFFFFF)
    for leaf_idx, leaf in enumerate(leaves):
        words = _leaf_words(leaf)
        position = jax.lax.iota(jnp.uint32, words.size)
        leaf_salt = jnp.uint32(((leaf_idx + 1) * _GOLDEN) & 0xFFFFFFFF)
        mixed = _fmix32(words ^ _fmix32(position + leaf_salt))
        acc = _fmix32(
            (acc + jnp.sum(mixed, dtype=jnp.uint32)) ^ jnp.uint32(words.size & 0xFFFFFFFF)
        )
    return acc


def _is_fingerprintable(leaf: Any) -> bool:
    """Template-side gate: a fully-replicated device array with a standard
    (bitcastable) dtype. Sharded leaves (per-shard keys, env state) are NOT
    replicas — disagreement there is data parallelism, not corruption."""
    import jax

    if not isinstance(leaf, jax.Array):
        return False
    try:
        if not leaf.sharding.is_fully_replicated:
            return False
    except Exception:  # noqa: BLE001 — deleted/donated arrays have no sharding
        return False
    return not jax.dtypes.issubdtype(leaf.dtype, jax.dtypes.extended)


def replicated_group_specs(template: Any) -> List[Tuple[str, List[int]]]:
    """The replicated state groups of a learner state: each top-level field
    (NamedTuple) or key (dict) whose subtree holds at least one fully
    replicated array leaf, with the flat indices of those leaves. Non-record
    states fold into a single 'state' group."""
    import jax

    if hasattr(template, "_fields"):
        named = [(name, getattr(template, name)) for name in template._fields]
    elif isinstance(template, dict):
        named = sorted(template.items())
    else:
        named = [("state", template)]
    groups: List[Tuple[str, List[int]]] = []
    for name, subtree in named:
        idxs = [
            i for i, leaf in enumerate(jax.tree.leaves(subtree))
            if _is_fingerprintable(leaf)
        ]
        if idxs:
            groups.append((str(name), idxs))
    return groups


def _group_subtree(state: Any, name: str) -> Any:
    if hasattr(state, "_fields"):
        return getattr(state, name)
    if isinstance(state, dict):
        return state[name]
    return state


def build_fingerprint_fn(
    mesh: Any, template: Any
) -> Tuple[Callable[[Any], Dict[str, Any]], List[str]]:
    """ONE jitted shard_mapped fingerprint program for `template`'s
    replicated groups (built once — never in a loop, STX012). Returns
    (fn, group_names); fn(state) -> {group: [num_devices] uint32 vector},
    entry i belonging to mesh.devices.flatten()[i] (the same decode
    convention as the fleet flag vector).

    Inputs enter with in_specs P() — they ARE replicated, so no resharding
    and no collective happens; each device folds ITS OWN copy of the bytes,
    which is exactly what makes a single-replica HBM flip visible. Outputs
    leave with the [1]-per-device block sharded over every mesh axis.
    check_vma=False: the output genuinely varies per device (that is the
    point), which the replication validator cannot express for replicated
    inputs."""
    import jax
    from jax.sharding import PartitionSpec


    groups = replicated_group_specs(template)
    if not groups:
        raise ValueError(
            "state has no fully-replicated array leaves to fingerprint — "
            "arch.integrity cannot guard a state with no replicated groups"
        )
    axes = tuple(mesh.axis_names)

    def extract(state: Any) -> Dict[str, Tuple[Any, ...]]:
        out: Dict[str, Tuple[Any, ...]] = {}
        for name, idxs in groups:
            leaves = jax.tree.leaves(_group_subtree(state, name))
            out[name] = tuple(leaves[i] for i in idxs)
        return out

    def per_device(grouped: Dict[str, Tuple[Any, ...]]) -> Dict[str, Any]:
        out = {}
        for group_idx, (name, _) in enumerate(groups):
            fp = fingerprint_leaves(
                grouped[name], salt=((group_idx + 1) * _GOLDEN) & 0xFFFFFFFF
            )
            out[name] = fp[None]  # [1] per device -> [num_devices] global
        return out

    program = jax.jit(
        jax.shard_map(
            per_device,
            mesh=mesh,
            in_specs=(PartitionSpec(),),
            out_specs=PartitionSpec(axes),
            check_vma=False,
        )
    )
    return (lambda state: program(extract(state))), [name for name, _ in groups]


# ---------------------------------------------------------------------------
# Sentinel
# ---------------------------------------------------------------------------


class StateIntegritySentinel:
    """Owns one run's integrity checking: the fingerprint program, the
    host-side verdicts, the determinism probe, the quarantine record, and
    the exit-code excepthook. Construct via `sentinel_from_config`; `bind`
    once the mesh + state template exist, `deactivate` in the host loop's
    finally."""

    def __init__(self, settings: IntegritySettings):
        self.settings = settings
        self._fp_fn: Optional[Callable[[Any], Dict[str, Any]]] = None
        self.group_names: List[str] = []
        self._device_order: List[Tuple[int, int]] = []  # (device_id, process)
        self._lock = threading.Lock()
        self._checks = 0
        self._overhead_s = 0.0
        self._probe_runs = 0
        self._probe_input: Optional[Any] = None
        self._probe_ref: Optional[Dict[str, np.ndarray]] = None
        self._resume_overrides: List[str] = []
        self._corruption: Optional[StateCorruptionError] = None
        self._prev_excepthook: Optional[Callable] = None
        self._log = get_logger("stoix_tpu.resilience")

    # -- lifecycle -----------------------------------------------------------
    def bind(self, mesh: Any, state_template: Any) -> "StateIntegritySentinel":
        """Build the fingerprint program for this mesh + state structure and
        record the device->process decode order."""
        self._fp_fn, self.group_names = build_fingerprint_fn(mesh, state_template)
        self._device_order = [
            (int(d.id), int(d.process_index)) for d in mesh.devices.flatten()
        ]
        probe_note = (
            f", determinism probe every "
            f"{self.settings.determinism_probe_interval} window(s)"
            if self.probe_enabled
            else ""
        )
        self._log.info(
            "[integrity] sentinel armed: fingerprinting %s across %d device(s)%s",
            "+".join(self.group_names), len(self._device_order), probe_note,
        )
        return self

    def install_excepthook(self) -> None:
        """Translate an uncaught StateCorruptionError into the corruption
        exit code for the supervising launcher (chains with — and takes
        precedence over — the fleet hook's FleetError->87, which a
        StateCorruptionError never matches)."""
        prev = sys.excepthook
        self._prev_excepthook = prev

        def hook(exc_type, exc, tb):
            prev(exc_type, exc, tb)
            if isinstance(exc, StateCorruptionError):
                # The quarantine path already dumped next to its record, but
                # THIS is the one place that actually dies with rc 88, and
                # os._exit skips every finally — so the exit path itself
                # must leave the evidence (STX021). A re-dump only
                # refreshes the ring snapshot.
                flightrec.dump_flight_record(
                    None,
                    reason=f"state corruption: uncaught {exc_type.__name__}",
                    exit_code=EXIT_CODE_STATE_CORRUPTION,
                )
                sys.stderr.flush()
                os._exit(EXIT_CODE_STATE_CORRUPTION)

        self._hook = hook
        sys.excepthook = hook

    def deactivate(self) -> None:
        """Restore the excepthook UNLESS a corruption verdict was recorded —
        the StateCorruptionError propagating out of the host loop after its
        finally is exactly what the hook must translate to exit code 88.
        Restores only when the installed hook is still OURS (another layer
        may have chained on top since install)."""
        if (
            self._corruption is None
            and self._prev_excepthook is not None
            and sys.excepthook is getattr(self, "_hook", None)
        ):
            sys.excepthook = self._prev_excepthook
            self._prev_excepthook = None

    # -- resume/quarantine ----------------------------------------------------
    def set_resume_info(self, store_directory: str) -> None:
        """Record the overrides a relaunch needs to restore the newest
        digest-verified checkpoint of THIS run's orbax store
        (`<rel_dir>/<uid>/<model>` — Checkpointer.directory)."""
        directory = os.path.abspath(str(store_directory))
        uid_dir = os.path.dirname(directory)
        self._resume_overrides = [
            "logger.checkpointing.load_model=true",
            f"logger.checkpointing.load_args.load_path={os.path.dirname(uid_dir)}",
            f"logger.checkpointing.load_args.checkpoint_uid={os.path.basename(uid_dir)}",
        ]

    def _record_quarantine(self, err: StateCorruptionError) -> None:
        """Append the verdict to the quarantine file (read-modify-write):
        which process(es)/device(s) deviated, at which window/step, plus the
        resume overrides for `launcher.py --supervise`'s rc-88 relaunch. The
        scheduler (or operator) drains quarantined hosts; this repo's job is
        to NAME them with proof."""
        path = self.settings.quarantine_file
        entry = {
            "kind": err.kind,
            "groups": err.groups,
            "devices": err.devices,
            "processes": err.processes,
            "window": err.window,
            "step": err.step,
            "detail": err.detail,
            "unix_time": time.time(),
        }
        try:
            record = {"quarantined": [], "resume_overrides": []}
            if os.path.isfile(path):
                with open(path) as f:
                    loaded = json.load(f)
                if isinstance(loaded, dict):
                    record.update(loaded)
            record.setdefault("quarantined", []).append(entry)
            record["resume_overrides"] = list(self._resume_overrides)
            os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(record, f, indent=1)
            os.replace(tmp, path)
            self._log.error(
                "[integrity] quarantine record written to %s (process(es) %s, "
                "device(s) %s)", path, err.processes, err.devices,
            )
        except (OSError, ValueError) as exc:
            self._log.error(
                "[integrity] could not write quarantine record to %s: %s",
                path, exc,
            )
        # rc-88 flight record, next to the quarantine file (dumped even when
        # the quarantine write itself failed — the ring is all evidence then).
        recorder = flightrec.get_flight_recorder()
        recorder.record(
            "quarantine", corruption=err.kind, window=err.window, step=err.step,
            processes=list(err.processes), devices=list(err.devices),
        )
        flightrec.dump_flight_record(
            os.path.dirname(os.path.abspath(path)),
            reason=f"state corruption: {err.kind} at window {err.window}",
            exit_code=EXIT_CODE_STATE_CORRUPTION,
        )

    # -- fingerprints ---------------------------------------------------------
    @property
    def enabled(self) -> bool:
        return self.settings.enabled

    @property
    def probe_enabled(self) -> bool:
        return self.settings.determinism_probe_interval > 0

    def fingerprints(self, state: Any) -> Dict[str, Any]:
        """Dispatch the fingerprint program on `state` (device tree, to merge
        into the coalesced metric fetch). Host cost is dispatch only."""
        t0 = time.perf_counter()
        out = self._fp_fn(state)
        with self._lock:
            self._overhead_s += time.perf_counter() - t0
        return out

    def verify(
        self, payload: Dict[str, Any], window_idx: int, step: int
    ) -> Optional[StateCorruptionError]:
        """Compare a MATERIALIZED fingerprint payload's per-device entries.
        All equal -> None. Any disagreement -> the typed error naming the
        deviating device(s) (minority vs the majority fingerprint), with the
        quarantine record written. A pure function of replicated data, so
        every host reaches the same verdict at the same window."""
        t0 = time.perf_counter()
        bad_groups: List[str] = []
        deviant_positions: set = set()
        details: List[str] = []
        for name in self.group_names:
            vec = np.asarray(payload[name]).reshape(-1)
            values, counts = np.unique(vec, return_counts=True)
            if len(values) <= 1:
                continue
            bad_groups.append(name)
            if int(counts.max()) * 2 <= vec.size:
                # No STRICT majority (the 2-replica 1-vs-1 case, or worse):
                # corruption is still PROVEN — the replicas disagree — but
                # attribution is undecidable, and confidently quarantining
                # the numerically-smaller fingerprint would drain the
                # healthy host half the time. Name every device.
                deviant_positions.update(range(vec.size))
                details.append(
                    f"{name}: no majority fingerprint ("
                    + ", ".join(
                        f"device {self._device_order[i][0]}={int(vec[i]):#010x}"
                        for i in range(vec.size)
                    )
                    + ") — replicas disagree but the corrupt one is "
                    "undecidable at this replica count"
                )
                continue
            majority = values[int(np.argmax(counts))]
            deviants = np.nonzero(vec != majority)[0]
            deviant_positions.update(int(i) for i in deviants)
            details.append(
                f"{name}: majority fingerprint {int(majority):#010x} on "
                f"{int(counts.max())}/{vec.size} device(s), deviating "
                + ", ".join(
                    f"device {self._device_order[i][0]}={int(vec[i]):#010x}"
                    for i in deviants
                )
            )
        with self._lock:
            self._checks += 1
            self._overhead_s += time.perf_counter() - t0
        if not bad_groups:
            return None
        devices = sorted({self._device_order[i][0] for i in deviant_positions})
        processes = sorted({self._device_order[i][1] for i in deviant_positions})
        err = StateCorruptionError(
            kind="replica_mismatch",
            groups=bad_groups,
            devices=devices,
            processes=processes,
            window=window_idx,
            step=step,
            detail="; ".join(details),
        )
        self._corruption = err
        get_registry().counter(
            "stoix_tpu_integrity_corruptions_total",
            "Silent-corruption verdicts raised by the state-integrity sentinel",
        ).inc(labels={"kind": "replica_mismatch"})
        self._record_quarantine(err)
        self._log.error("[integrity] %s", err)
        return err

    def check_state(
        self, state: Any, window_idx: int, step: int
    ) -> Optional[StateCorruptionError]:
        """Synchronous fingerprint + verify (the Sebulba eval-boundary path,
        where there is no coalesced device fetch to piggyback on)."""
        payload = {
            name: np.asarray(value)
            for name, value in self.fingerprints(state).items()
        }
        return self.verify(payload, window_idx, step)

    # -- determinism probe ----------------------------------------------------
    def capture_probe_input(self, state_copy: Any) -> None:
        """Record the replay input (an on-device COPY the caller owns — the
        learn step donates its argument, so every replay runs on a fresh copy
        of this one). First capture wins."""
        if self.probe_enabled and self._probe_input is None:
            self._probe_input = state_copy

    def record_probe_reference(self, payload: Dict[str, Any]) -> None:
        """Record the reference output fingerprint — the FIRST window's own
        materialized fingerprint vector, which by construction is
        fingerprint(learn(probe_input)): the recording costs nothing."""
        if self.probe_enabled and self._probe_ref is None:
            self._probe_ref = {
                name: np.array(np.asarray(payload[name]), copy=True)
                for name in self.group_names
            }

    def should_probe(self, window_idx: int) -> bool:
        interval = self.settings.determinism_probe_interval
        return (
            self.probe_enabled
            and window_idx > 0
            and window_idx % interval == 0
            and self._probe_input is not None
            and self._probe_ref is not None
        )

    def run_probe(
        self, learn_fn: Callable[[Any], Any], tree_copy: Callable[[Any], Any]
    ) -> Optional[StateCorruptionError]:
        """Replay the recorded input through the learn step and compare the
        output fingerprint vector BITWISE against the recorded reference. A
        divergence means the same program on the same input computed a
        different answer — a wrong-math core, caught even at replica count 1.
        Synchronous (one extra learn execution); returns the typed error or
        None."""
        replay = learn_fn(tree_copy(self._probe_input))
        state = getattr(replay, "learner_state", replay)
        got = {
            name: np.asarray(value)
            for name, value in self.fingerprints(state).items()
        }
        with self._lock:
            self._probe_runs += 1
        mismatched = [
            name for name in self.group_names
            if not np.array_equal(got[name], self._probe_ref[name])
        ]
        if not mismatched:
            return None
        err = StateCorruptionError(
            kind="determinism",
            groups=mismatched,
            devices=[d for d, _ in self._device_order],
            processes=sorted({p for _, p in self._device_order}),
            window=-1,
            step=-1,
            detail="; ".join(
                f"{name}: replay {got[name].tolist()} != recorded "
                f"{self._probe_ref[name].tolist()}"
                for name in mismatched
            ),
        )
        self._corruption = err
        get_registry().counter(
            "stoix_tpu_integrity_corruptions_total",
            "Silent-corruption verdicts raised by the state-integrity sentinel",
        ).inc(labels={"kind": "determinism"})
        self._record_quarantine(err)
        self._log.error("[integrity] %s", err)
        return err

    # -- reporting ------------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        """The bench/LAST_RUN_STATS view of this run's sentinel activity."""
        with self._lock:
            return {
                "enabled": True,
                "fingerprint_checks": self._checks,
                "overhead_s": round(self._overhead_s, 6),
                "probe_runs": self._probe_runs,
            }


def disabled_stats() -> Dict[str, Any]:
    """The stats dict shape when the sentinel is off (bench schema parity)."""
    return {
        "enabled": False,
        "fingerprint_checks": 0,
        "overhead_s": 0.0,
        "probe_runs": 0,
    }


def sentinel_from_config(config: Any) -> Optional[StateIntegritySentinel]:
    """A bind-able sentinel when `arch.integrity.enabled`, else None (zero
    work, bit-identical host loops)."""
    settings = settings_from_config(config)
    if not settings.enabled:
        return None
    return StateIntegritySentinel(settings)


# ---------------------------------------------------------------------------
# Launcher-side helpers (no jax import)
# ---------------------------------------------------------------------------


def read_quarantine(path: str) -> Dict[str, Any]:
    """The quarantine record at `path` ({} when absent/unreadable)."""
    try:
        with open(path) as f:
            loaded = json.load(f)
        return loaded if isinstance(loaded, dict) else {}
    except (OSError, ValueError):
        return {}


def corruption_resume_overrides(quarantine_file: str) -> List[str]:
    """The resume overrides the latest corruption verdict recorded for a
    supervised relaunch ([] when the run had no checkpoint store — the
    relaunch then starts fresh)."""
    return [str(o) for o in read_quarantine(quarantine_file).get("resume_overrides") or []]
