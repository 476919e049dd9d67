"""Sebulba host-side plumbing (reference stoix/utils/sebulba_utils.py, 394 LoC).

Threads + bounded queues connect actor devices to learner devices:
  - ThreadLifetime: cooperative stop signal (:20-45)
  - OnPolicyPipeline: one queue.Queue(maxsize=1) per actor; the learner must
    collect from ALL actors each update — backpressure by construction (:48-96)
  - ParameterServer: pushes fresh params to per-actor queues, device_put onto
    each actor's device; `None` is the shutdown sentinel (:99-259)
  - AsyncEvaluator: background evaluation requests with best-params tracking
    (:262-367)

TPU-native difference (SURVEY.md §7.1.3): trajectory hand-off builds GLOBAL
arrays with jax.make_array_from_single_device_arrays via
parallel.assemble_global_array, so the learner's jit consumes a correctly
sharded batch with no host concat.

Telemetry (docs/DESIGN.md §2.2): every queue hand-off records depth and
put/get wait series (`stoix_tpu_sebulba_queue_*`), every component beats a
HeartbeatBoard, and a collect timeout surfaces as ActorStarvationError naming
the starved side (actor dead vs pipeline wedged vs params stale) instead of
an anonymous `queue.Empty`. All instruments are host-memory only — no device
syncs — and span recording is a no-op unless telemetry is enabled.

Fault tolerance (docs/DESIGN.md §2.3): both queue layers carry typed
`ComponentFailure` poison-pills — the supervisor injects one when an actor is
unrecoverable (crash budget exhausted, or wedged), and the peer RAISES it on
its next get instead of burning a full collect timeout against a dead
producer. `ParameterServer.reprime` re-feeds the latest params to a
supervisor-restarted actor so the restart can never deadlock against a
learner already blocked in collect. `AsyncEvaluator.wait_until_idle` raises
EvaluatorStallError on timeout instead of silently letting shutdown proceed
with dangling evaluation work.
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Dict, List, NamedTuple, Optional

import jax

from stoix_tpu.observability import (
    ActorStarvationError,
    HeartbeatBoard,
    StallDetector,
    get_registry,
    span,
)
from stoix_tpu.resilience.errors import ComponentFailure, EvaluatorStallError
from stoix_tpu.utils.timing import TimingTracker


def _replace_nowait(q: "queue.Queue", item: Any) -> None:
    """Best-effort freshest-wins replacement on a maxsize-1 queue: drop a
    stale entry if present, then put without blocking (a concurrent producer
    winning the slot is fine — its item is at least as fresh)."""
    try:
        q.get_nowait()
    except queue.Empty:
        pass
    try:
        q.put_nowait(item)
    except queue.Full:
        pass


def _queue_instruments():
    registry = get_registry()
    return (
        registry.gauge(
            "stoix_tpu_sebulba_queue_depth",
            "Items currently buffered per Sebulba queue",
        ),
        registry.histogram(
            "stoix_tpu_sebulba_queue_put_wait_seconds",
            "Producer-side blocking time per queue put",
        ),
        registry.histogram(
            "stoix_tpu_sebulba_queue_get_wait_seconds",
            "Consumer-side blocking time per queue get",
        ),
    )


class ThreadLifetime:
    def __init__(self) -> None:
        self._stop = threading.Event()

    def should_stop(self) -> bool:
        return self._stop.is_set()

    def stop(self) -> None:
        self._stop.set()


class OnPolicyPipeline:
    """Bounded rollout queues, one per actor thread.

    `fleet` (optional, a resilience.fleet.FleetCoordinator) makes the
    learner-side collect fleet-aware: a cross-host partition declared by the
    fleet monitor fails the collect IMMEDIATELY with the typed
    FleetPartitionError instead of burning the collect timeout against
    actors that are healthy while the POD is dead (docs/DESIGN.md §2.6)."""

    def __init__(self, num_actors: int, max_size: int = 1, fleet: Optional[Any] = None):
        self._queues: List[queue.Queue] = [queue.Queue(maxsize=max_size) for _ in range(num_actors)]
        self.heartbeats = HeartbeatBoard()
        self._depth, self._put_wait, self._get_wait = _queue_instruments()
        self._failures: Dict[int, ComponentFailure] = {}
        self._failure_lock = threading.Lock()
        self._fleet = fleet

    def fail(self, actor_id: int, failure: ComponentFailure) -> None:
        """Poison-pill injection (supervisor path): record the failure and
        wake a learner blocked on this actor's queue. A payload already
        buffered may be dropped to make room — on the failure path the batch
        is lost anyway."""
        with self._failure_lock:
            self._failures[actor_id] = failure
        # Best-effort wake; collect_rollouts consults _failures before
        # blocking, so a lost put is not a lost failure.
        _replace_nowait(self._queues[actor_id], failure)

    def send_rollout(self, actor_id: int, payload: Any, timeout: Optional[float] = None) -> None:
        labels = {"queue": "rollout", "actor": str(actor_id)}
        try:
            # The span observes its seconds into the put-wait histogram when
            # it closes, a queue.Full timeout included: the worst-case
            # backpressure sample is the one this histogram exists to capture.
            with span("pipeline_put", clock=self._put_wait, phase=labels, actor=actor_id):
                self._queues[actor_id].put(payload, timeout=timeout)
        finally:
            self._depth.set(self._queues[actor_id].qsize(), labels)
        self.heartbeats.beat(f"actor-{actor_id}")

    def collect_rollouts(self, timeout: float = 180.0) -> List[Any]:
        """Blocks until every actor has contributed one rollout. A timeout
        names the starved actor and its last-heartbeat age (reference
        sebulba_utils.py:85 surfaced a bare queue.Empty here)."""
        detector = StallDetector(self.heartbeats, stale_after_s=max(1.0, timeout / 4))
        payloads = []
        for actor_id, q in enumerate(self._queues):
            if self._fleet is not None:
                self._fleet.check_partition()
            with self._failure_lock:
                failure = self._failures.get(actor_id)
            if failure is not None:
                raise failure
            labels = {"queue": "rollout", "actor": str(actor_id)}
            try:
                # Feeds the get-wait histogram on close (a starved timeout's
                # wait too, like the put side's queue.Full).
                with span("pipeline_get", clock=self._get_wait, phase=labels,
                          actor=actor_id):
                    payload = q.get(timeout=timeout)
            except queue.Empty:
                raise ActorStarvationError(
                    actor_id,
                    timeout,
                    detector.diagnose(waiting_on=f"actor-{actor_id}"),
                    self.heartbeats.age(f"actor-{actor_id}"),
                ) from None
            if isinstance(payload, ComponentFailure):
                raise payload
            payloads.append(payload)
            self._depth.set(q.qsize(), labels)
        self.heartbeats.beat("learner")
        return payloads

    def drain(self, timeout: float = 0.5) -> int:
        """Shutdown-path drain: unblock producers stuck in put() WITHOUT
        recording wait/depth series or heartbeats — drain gets are teardown
        artifacts, not backpressure signal. Returns items drained; stops at
        the first empty queue (matching the old best-effort loop)."""
        drained = 0
        for q in self._queues:
            try:
                q.get(timeout=timeout)
                drained += 1
            except queue.Empty:
                break
        return drained


class OffPolicyPipeline:
    """Off-policy ingestion (docs/DESIGN.md §2.10): actor devices PUSH
    transition shards whenever a rollout chunk is ready; the learner POLLS
    whatever has arrived and samples its replay service independently — no
    lockstep collect, so one slow/restarting actor never stalls the learner
    (the on-policy pipeline's must-hear-from-every-actor rule is exactly
    what an off-policy learner does not need).

    A single bounded queue carries (actor_id, payload) pairs from every
    actor; a full queue back-pressures producers (put blocks), an empty one
    never blocks the learner past its chosen timeout. Failure semantics
    mirror OnPolicyPipeline: the supervisor injects a typed ComponentFailure
    poison-pill for an unrecoverable actor; the learner raises it on its
    next poll instead of sampling forever against a quietly dead fleet."""

    def __init__(self, num_actors: int, depth_per_actor: int = 2, fleet: Optional[Any] = None):
        self.num_actors = num_actors
        self._queue: queue.Queue = queue.Queue(maxsize=max(1, num_actors * depth_per_actor))
        self.heartbeats = HeartbeatBoard()
        self._depth, self._put_wait, self._get_wait = _queue_instruments()
        self._poll_timer = TimingTracker(maxlen=1)  # the newest poll's seconds
        self._failures: Dict[int, ComponentFailure] = {}
        self._failure_lock = threading.Lock()
        self._fleet = fleet

    def _check_failures(self) -> None:
        if self._fleet is not None:
            self._fleet.check_partition()
        with self._failure_lock:
            for failure in self._failures.values():
                raise failure

    def fail(self, actor_id: int, failure: ComponentFailure) -> None:
        """Poison-pill injection (supervisor path): record the failure and
        wake a learner blocked in wait_for_data. The shared queue may be
        full of healthy payloads — drop one to make room for the pill (the
        learner consults _failures before blocking, so a lost put is never
        a lost failure)."""
        with self._failure_lock:
            self._failures[actor_id] = failure
        try:
            self._queue.put_nowait(failure)
        except queue.Full:
            try:
                self._queue.get_nowait()
                self._queue.put_nowait(failure)
            except (queue.Empty, queue.Full):
                pass

    def push(self, actor_id: int, payload: Any, timeout: Optional[float] = None) -> None:
        labels = {"queue": "transitions", "actor": str(actor_id)}
        try:
            # As in OnPolicyPipeline.send_rollout: the span feeds the put-wait
            # histogram on close, a queue.Full timeout included.
            with span("offpolicy_push", clock=self._put_wait, phase=labels,
                      actor=actor_id):
                self._queue.put((actor_id, payload), timeout=timeout)
        finally:
            self._depth.set(self._queue.qsize(), labels)
        self.heartbeats.beat(f"actor-{actor_id}")

    def poll(self, max_items: int = 64, timeout: float = 0.0) -> List[Any]:
        """Drain up to `max_items` pending (actor_id, payload) pairs. Only
        the FIRST get may block (up to `timeout`); the rest are non-blocking
        — the learner ingests what exists and goes back to sampling. Raises
        the typed ComponentFailure if any actor is unrecoverably gone."""
        self._check_failures()
        labels = {"queue": "transitions", "actor": "learner"}
        items: List[Any] = []
        with span("offpolicy_poll", clock=self._poll_timer, phase="poll"):
            while len(items) < max_items:
                try:
                    got = self._queue.get(timeout=timeout if not items else 0.0)
                except queue.Empty:
                    break
                if isinstance(got, ComponentFailure):
                    raise got
                items.append(got)
        if items:
            # Only a poll that got something is a wait sample: the learner's
            # empty timeout-0 polls between updates are not.
            self._get_wait.observe(self._poll_timer.latest("poll"), labels)
            self._depth.set(self._queue.qsize(), labels)
            self.heartbeats.beat("learner")
        return items

    def wait_for_data(self, timeout: float = 180.0) -> List[Any]:
        """Block until at least one payload arrives (warmup / starved-replay
        path). A timeout names the stalest actor and its last-heartbeat age
        instead of surfacing a bare queue.Empty."""
        detector = StallDetector(self.heartbeats, stale_after_s=max(1.0, timeout / 4))
        items = self.poll(timeout=timeout)
        if not items:
            # Name the most-starved producer: a never-beat actor outranks
            # any stale one; otherwise the oldest heartbeat wins.
            stalest, stalest_age = 0, -1.0
            for actor_id in range(self.num_actors):
                actor_age = self.heartbeats.age(f"actor-{actor_id}")
                if actor_age is None:
                    stalest, stalest_age = actor_id, None
                    break
                if stalest_age is not None and actor_age > stalest_age:
                    stalest, stalest_age = actor_id, actor_age
            raise ActorStarvationError(
                stalest,
                timeout,
                detector.diagnose(waiting_on=f"actor-{stalest}"),
                stalest_age,
            )
        return items

    def drain(self, timeout: float = 0.5) -> int:
        """Shutdown-path drain: unblock producers stuck in put() WITHOUT
        recording wait/depth series or heartbeats (teardown artifacts, not
        backpressure signal)."""
        drained = 0
        while True:
            try:
                self._queue.get(timeout=timeout)
                drained += 1
            except queue.Empty:
                return drained


class VersionedParams(NamedTuple):
    """Queue entry the ParameterServer feeds actors: the placed params plus
    the monotone version (distribute_params call count) they came from. The
    IMPACT stale-reuse path (docs/DESIGN.md §2.12) tags every pushed
    trajectory with the behavior version so the learner can compute per-batch
    staleness; the version travels WITH the params through the queue (not as
    a separate attribute read) so an actor can never pair params vN with
    version vN+1."""

    version: int
    params: Any


class ParameterServer:
    """Latest-params distribution to actor devices.

    Transfer economy: params are device_put ONCE PER DEVICE per version, not
    once per actor — actors sharing a device receive the same placed copy
    through their own queues (re-transferring identical bytes for every
    co-located actor scaled the push cost with actors_per_device for no
    reason). `reprime` reuses the version's placed copy the same way.

    Versioning: every distribute_params bumps a monotone version counter;
    queue entries are VersionedParams, and `get_params_versioned` returns
    (version, params) so that an actor can report which policy collected a
    trajectory (policy lag; IMPACT, arXiv:1912.00167)."""

    def __init__(
        self,
        actor_devices: List[jax.Device],
        actors_per_device: int,
        heartbeats: Optional[HeartbeatBoard] = None,
    ):
        self._devices = [d for d in actor_devices for _ in range(actors_per_device)]
        self._queues: List[queue.Queue] = [queue.Queue(maxsize=1) for _ in self._devices]
        self._version = 0  # bumped once per distribute_params (learner thread)
        self._latest: Any = None  # last distributed params, for reprime()
        # (params, {device: placed copy}) of the most recently COMPLETED
        # push, identity-tagged so reprime can tell whether the placed
        # copies belong to self._latest or to an older version a concurrent
        # distribute is in the middle of replacing.
        self._placed_entry: Optional[tuple] = None
        self.heartbeats = heartbeats if heartbeats is not None else HeartbeatBoard()
        self._depth, self._put_wait, self._get_wait = _queue_instruments()
        self._pushes = get_registry().counter(
            "stoix_tpu_sebulba_param_pushes_total",
            "Parameter versions pushed to each actor queue",
        )
        self._transfer = get_registry().histogram(
            "stoix_tpu_sebulba_param_transfer_seconds",
            "Host-side device_put time per param placement (once per DEVICE "
            "per version, not per actor; NOT queue blocking)",
        )
        self._policy_lag = get_registry().histogram(
            "stoix_tpu_sebulba_policy_lag_updates",
            "Learner's newest param version minus the version a consumed "
            "rollout was collected with (updates of staleness; on-policy path)",
            buckets=(0, 1, 2, 3, 4, 6, 8, 16),
        )

    @property
    def num_actors(self) -> int:
        return len(self._queues)

    def _place(self, params: Any, device: jax.Device, placed: Dict[Any, Any]) -> Any:
        """device_put once per device; later actors on the device reuse it."""
        local = placed.get(device)
        if local is None:
            with span("param_transfer", clock=self._transfer,
                      phase={"queue": "params", "device": str(device)}):
                local = jax.device_put(params, device)
            placed[device] = local
        return local

    @property
    def version(self) -> int:
        """Monotone count of completed/started distribute_params calls — the
        learner's CURRENT policy version (0 before the first push)."""
        return self._version

    def observe_policy_lag(self, behavior_version: int) -> int:
        """The learner calls this for every rollout it consumes: its newest
        version minus the version the actor acted with, into the histogram
        `stoix_tpu_sebulba_policy_lag_updates`. 0 = trained on at the version
        it was collected with; the skip-fetch pipelining makes 1 the norm."""
        lag = self._version - int(behavior_version)
        self._policy_lag.observe(lag)
        return lag

    def distribute_params(self, params: Any) -> None:
        self._version += 1
        version = self._version
        self._latest = params
        placed: Dict[Any, Any] = {}
        with span("param_push", actors=len(self._queues)):
            for actor_id, (device, q) in enumerate(zip(self._devices, self._queues)):
                labels = {"queue": "params", "actor": str(actor_id)}
                # Transfer cost and queue blocking are separate series: a
                # slow push must be attributable to the right cause (large
                # params vs an actor not draining its queue).
                local = self._place(params, device, placed)
                with span("param_put", clock=self._put_wait, phase=labels):
                    # Keep only the freshest params: drop a stale entry if present.
                    try:
                        q.get_nowait()
                    except queue.Empty:
                        pass
                    q.put(VersionedParams(version, local))
                self._depth.set(q.qsize(), labels)
                self._pushes.inc(labels={"actor": str(actor_id)})
        self._placed_entry = (params, placed, version)
        self.heartbeats.beat("param-server")

    def reprime(self, actor_id: int) -> bool:
        """Re-feed the LATEST distributed params to one actor queue (the
        supervisor calls this before starting a replacement actor). Never
        blocks: a concurrent learner push wins the maxsize-1 slot, which is
        at least as fresh. Reuses the latest COMPLETED version's placed copy
        for the actor's device when one exists — no redundant transfer; a
        version still mid-push places fresh (its dict may hold older copies)."""
        latest = self._latest
        if latest is None:
            return False
        entry = self._placed_entry
        if entry is not None and entry[0] is latest:
            placed, version = entry[1], entry[2]
        else:
            # Mid-push race: its dict may hold older copies; place fresh and
            # tag with the in-flight version (the one being distributed).
            placed, version = {}, self._version
        local = self._place(latest, self._devices[actor_id], placed)
        _replace_nowait(self._queues[actor_id], VersionedParams(version, local))
        return True

    def fail(self, failure: ComponentFailure, actor_id: int) -> None:
        """Poison one actor's param queue: an actor blocked in its param get
        raises `failure` instead of waiting on params that will never come.
        The supervisor uses this for the failed actor itself — a wedge
        blocked there dies with a typed error instead of lingering
        forever. (Orderly teardown of HEALTHY actors stays shutdown()'s
        None-sentinel job.)"""
        _replace_nowait(self._queues[actor_id], failure)

    def get_params_versioned(
        self, actor_id: int, timeout: Optional[float] = None
    ) -> Optional[VersionedParams]:
        """Fresh params with the version they were distributed under,
        (version, params), or None (shutdown sentinel); raises a
        ComponentFailure poison-pill if the learner failed unrecoverably.
        Actors tag their trajectories with the version (policy lag, IMPACT
        staleness)."""
        labels = {"queue": "params", "actor": str(actor_id)}
        with span("param_get", clock=self._get_wait, phase=labels, actor=actor_id):
            entry = self._queues[actor_id].get(timeout=timeout)
        self._depth.set(self._queues[actor_id].qsize(), labels)
        if isinstance(entry, ComponentFailure):
            raise entry
        return entry

    def shutdown(self) -> None:
        for q in self._queues:
            try:
                q.get_nowait()
            except queue.Empty:
                pass
            q.put(None)


class AsyncEvaluator:
    """Runs evaluations off the critical path on a dedicated device."""

    def __init__(
        self,
        evaluate: Callable[[Any, jax.Array], dict],
        lifetime: ThreadLifetime,
        on_result: Callable[[dict, Any, int], None],
        heartbeats: Optional[HeartbeatBoard] = None,
    ):
        self._evaluate = evaluate
        self._lifetime = lifetime
        self._on_result = on_result
        self._requests: queue.Queue = queue.Queue()
        self._idle = threading.Event()
        self._idle.set()
        # Guards the (queue-state, _idle) pair: submit makes the queue
        # non-empty and clears _idle atomically, _maybe_set_idle only sets
        # _idle while the queue is observably empty — without it a submit
        # racing the evaluator's own empty-check could leave _idle set with a
        # request queued, and wait_until_idle would return with dangling work.
        self._idle_lock = threading.Lock()
        self.heartbeats = heartbeats if heartbeats is not None else HeartbeatBoard()
        self._depth = get_registry().gauge(
            "stoix_tpu_sebulba_queue_depth",
            "Items currently buffered per Sebulba queue",
        )
        # Evaluations that raised, in order (appended by the evaluator thread,
        # read by wait_until_idle after the queue drained).
        self._failures: list = []
        self._failures_lock = threading.Lock()
        self.thread = threading.Thread(target=self._run, name="async-evaluator", daemon=True)

    def submit(self, params: Any, key: jax.Array, t: int) -> None:
        with self._idle_lock:
            self._idle.clear()
            self._requests.put((params, key, t))
        self._depth.set(self._requests.qsize(), {"queue": "eval_requests"})

    def _maybe_set_idle(self) -> None:
        with self._idle_lock:
            if self._requests.empty():
                self._idle.set()

    def _run(self) -> None:
        # Drain-on-stop: a lifetime stop with requests still queued finishes
        # them first — shutdown must not DROP submitted evaluation work (the
        # final eval of a run is submitted right before the learner loop
        # ends, and wait_until_idle now treats dangling work as an error).
        while not (self._lifetime.should_stop() and self._requests.empty()):
            try:
                params, key, t = self._requests.get(timeout=1.0)
            except queue.Empty:
                self._maybe_set_idle()
                continue
            self._depth.set(self._requests.qsize(), {"queue": "eval_requests"})
            try:
                with span("async_eval", t=t):
                    metrics = self._evaluate(params, key)
                    self._on_result(metrics, params, t)
                self.heartbeats.beat("evaluator")
            except Exception as exc:  # noqa: BLE001 — a lost eval window must
                # not kill the thread silently nor wedge shutdown on a cleared
                # _idle flag (mirrors the actor thread's crash telemetry). The
                # thread lives on; the RUN still fails — wait_until_idle
                # raises for every recorded failure.
                import traceback

                with self._failures_lock:
                    self._failures.append(exc)

                get_registry().counter(
                    "stoix_tpu_sebulba_evaluator_errors_total",
                    "Async evaluation requests that raised",
                ).inc()
                from stoix_tpu.observability import get_logger

                get_logger("stoix_tpu.sebulba").error(
                    "[async-evaluator] eval at t=%d FAILED:\n%s",
                    t, traceback.format_exc(),
                )
            self._maybe_set_idle()
        self._maybe_set_idle()

    def wait_until_idle(self, timeout: float = 600.0) -> None:
        """Block until all submitted evaluations completed. A timeout RAISES
        (EvaluatorStallError with the evaluator's last-heartbeat age) instead
        of silently returning — shutdown must not proceed while evaluation
        work is still dangling (it would be dropped unreported). So does a
        FAILED evaluation: once idle, any request that raised on the evaluator
        thread surfaces here as a ComponentFailure, so a run whose evaluations
        died cannot return as if it had been evaluated."""
        if not self._idle.wait(timeout=timeout):
            raise EvaluatorStallError(
                timeout, self.heartbeats.age("evaluator"), self._requests.qsize()
            )
        with self._failures_lock:
            failures = list(self._failures)
        if failures:
            raise ComponentFailure(
                "async-evaluator",
                f"{len(failures)} evaluation request(s) raised; first",
                cause=failures[0],
            )
