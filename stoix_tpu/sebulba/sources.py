"""How a Sebulba learner gets its next batch (docs/DESIGN.md §3).

One object stands between the actor threads and the learner loop of
`sebulba/runner.py`, and everything that differs between the ways of feeding
a learner is inside it: the queue the actors push into, how an actor's stored
rollout is cut (`storage`), whether an actor waits for parameters
(`actor_fetch_from`, `actor_fetch_timeout`), what one update's batch is
(`next_batch` -> `Batch`: the data, whether it is fresh, the new env steps it
consumed), the learn step's other operands (`step`), and how often parameters
go back (`param_sync_interval`). Three of them:

  * `OnPolicySource` — one rollout of every actor, every update
    (`OnPolicyPipeline`): PPO, IMPALA.
  * `ImpactSource` — a full fresh set when there is one, else the newest
    buffered batch again (`ImpactIngest`; arXiv:1912.00167, §2.12).
  * `ReplaySource` — whatever has arrived goes into the sharded replay
    service, which the learn step samples (§2.10): DQN.
"""

from __future__ import annotations

import collections
from typing import Any, Callable, List, NamedTuple

import jax
import jax.numpy as jnp

from stoix_tpu.observability import get_registry, goodput, span
from stoix_tpu.parallel import assemble_global_array
from stoix_tpu.sebulba.core import OffPolicyPipeline, OnPolicyPipeline
from stoix_tpu.sebulba.rollout_storage import FlatRolloutStorage, RolloutStorage


class SourceContext(NamedTuple):
    """What the runner gives a source when it makes it."""

    num_actors: int
    learner_devices: List[jax.Device]
    learner_mesh: Any
    fleet: Any  # resilience.fleet.FleetCoordinator or None
    timer: Any  # the learner's TimingTracker (MISC `learner_*`)
    ledger: Any  # the run's GoodputLedger
    steps_per_update: int  # rollout_length * total_num_envs


class Batch(NamedTuple):
    """One learner update's input."""

    data: Any
    fresh: bool  # False when a buffered batch is stepped again
    env_steps: int  # new env steps it consumed (0 when not fresh)


class ImpactSettings(NamedTuple):
    """Validated `system.impact` knobs (IMPACT stale-trajectory reuse,
    arXiv:1912.00167; docs/DESIGN.md §2.12)."""

    target_update_interval: int
    rho_clip: float
    max_staleness: int
    max_reuse: int
    buffer_size: int


def impact_settings_from_config(config: Any) -> "ImpactSettings | None":
    """None unless system.impact.enabled — the disabled path constructs the
    unchanged on-policy objects (OnPolicySource + get_learn_step)."""
    raw = dict(config.system.get("impact") or {})
    if not bool(raw.get("enabled", False)):
        return None
    settings = ImpactSettings(
        target_update_interval=int(raw.get("target_update_interval", 4)),
        rho_clip=float(raw.get("rho_clip", 2.0)),
        max_staleness=int(raw.get("max_staleness", 4)),
        max_reuse=int(raw.get("max_reuse", 2)),
        buffer_size=int(raw.get("buffer_size", 4)),
    )
    if settings.target_update_interval < 1:
        raise ValueError(
            "system.impact.target_update_interval must be >= 1 "
            f"(got {settings.target_update_interval})"
        )
    if settings.rho_clip < 1.0:
        raise ValueError(
            "system.impact.rho_clip must be >= 1.0 — clipping the IS ratio "
            f"below 1 would down-weight FRESH data (got {settings.rho_clip})"
        )
    if settings.max_staleness < 1 or settings.max_reuse < 0 or settings.buffer_size < 1:
        raise ValueError(
            "system.impact: max_staleness/buffer_size must be >= 1 and "
            f"max_reuse >= 0 (got {settings})"
        )
    return settings


def _is_shards(x: Any) -> bool:
    return isinstance(x, list)


def assemble_on_env_axis(
    payloads: List[Any], learner_devices: List[jax.Device], learner_mesh: Any
) -> Any:
    """Actors' payloads -> one global array a leaf."""

    # Per learner device: concat all payloads' shards, then build one
    # global array per leaf. The shards are [T, E/n] slices of the ENV
    # axis, so they tile array_axis=1 — assembling on the leading axis
    # would stack trajectories along TIME and let GAE bootstrap across
    # the device seam. (IMPACT note: any num_actors payloads tile to the
    # same global shape, so fresh and reused batches share one compile.)
    def to_global(*leaves):
        per_device = []
        for d in range(len(learner_devices)):
            shards = [leaf[d] for leaf in leaves]
            with jax.default_device(learner_devices[d]):
                per_device.append(jnp.concatenate(shards, axis=1))
        return assemble_global_array(
            per_device, learner_mesh, axis="data", array_axis=1
        ) if len(per_device) > 1 else per_device[0]

    # leaves are lists of per-device arrays; traverse manually.
    flat_payloads = [jax.tree.flatten(p, is_leaf=_is_shards) for p in payloads]
    treedef = flat_payloads[0][1]
    merged_leaves = [
        to_global(*(fp[0][i] for fp in flat_payloads))
        for i in range(len(flat_payloads[0][0]))
    ]
    return jax.tree.unflatten(treedef, merged_leaves)


class _Source:
    """What the three sources share: the context, and the answers of a
    source with nothing of its own to say."""

    param_sync_interval = 1  # learner updates between parameter pushes

    def __init__(self, ctx: SourceContext):
        self._ctx = ctx
        # What the actors push into (and: heartbeats, fail, drain).
        self.pipeline = self.pipeline_class(ctx.num_actors, fleet=ctx.fleet)

    def _note(self, phase: str) -> None:
        self._ctx.ledger.note(goodput.SEBULBA_PHASE_MAP[phase], self._ctx.timer.latest(phase))

    def step(self, learn_step: Callable, state: Any, batch: Batch) -> Any:
        """`(new state, train metrics)` of one update on `batch`."""
        return learn_step(state, batch.data)

    def after_update(self, state: Any) -> None:
        """Called once an update's parameters are out."""

    def observe(self) -> dict:
        """Scalars of the source's own for the MISC event."""
        return {}


class OnPolicySource(_Source):
    """One rollout of every actor, every update: backpressure by
    construction (`OnPolicyPipeline`, one queue of one an actor)."""

    pipeline_class = OnPolicyPipeline
    storage = RolloutStorage
    # Pipelining: an actor skips the parameter fetch on its second rollout so
    # that it runs ahead while the learner computes (reference :202-214), and
    # waits for fresh parameters from the third on.
    actor_fetch_from = 2
    actor_fetch_timeout = None
    wait_phase = "rollout_get"  # the timer key of the learner's wait for data

    def _assemble(self, payloads: List[Any]) -> Any:
        return assemble_on_env_axis(payloads, self._ctx.learner_devices, self._ctx.learner_mesh)

    def push(self, actor_id: int, behavior_version: int, payload: Any, timeout: float) -> None:
        """Actor side. Every rollout is tagged with the version of the
        params that collected it: the learner gauges policy lag from it (its
        newest version minus this one), IMPACT per-batch staleness."""
        self.pipeline.send_rollout(actor_id, (behavior_version, payload), timeout=timeout)

    def next_batch(self, update_idx: int, param_server: Any) -> Batch:
        timer = self._ctx.timer
        with span("learner_rollout_wait", clock=timer, phase="rollout_get", update=update_idx):
            tagged = self.pipeline.collect_rollouts()
        self._note("rollout_get")
        with span("learner_assemble", clock=timer, phase="assemble", update=update_idx):
            # Policy lag of every rollout consumed: the learner's
            # newest version minus the one the actor acted with.
            for behavior_version, _ in tagged:
                param_server.observe_policy_lag(behavior_version)
            data = self._assemble([payload for _, payload in tagged])
        self._note("assemble")
        return Batch(data, True, self._ctx.steps_per_update)

    def run_stats(self) -> dict:
        # None when IMPACT is off (the pin tests/test_impact.py asserts): the
        # default config must report the untouched on-policy path, not a
        # zeroed dict.
        return {"impact": None}


class ImpactBatch(NamedTuple):
    """One learner step's worth of data on the IMPACT path."""

    batch: Any  # assembled global-array trajectory batch
    behavior_version: int  # oldest param version that collected it
    fresh: bool  # False when re-stepping a buffered stale batch


class ImpactIngest:
    """Host-side fresh/stale scheduling for the IMPACT learner
    (docs/DESIGN.md §2.12).

    The learner prefers a FULL set of fresh payloads (`need` of them — any
    actor mix, shapes are identical, so one compiled learn step serves both
    paths). When fresh data is late it re-steps the newest eligible buffered
    batch instead of blocking in collect; only with an empty buffer does it
    block in wait_for_data (warmup, or reuse budget exhausted). Buffered
    entries retire on a reuse budget and are dropped once their version lag
    exceeds max_staleness."""

    def __init__(self, pipeline: OffPolicyPipeline, need: int, settings: ImpactSettings):
        self._pipeline = pipeline
        self._need = need
        self._settings = settings
        self._pending: List[Any] = []  # (behavior_version, payload) FIFO
        # [behavior_version, batch, reuse_left]; bounded — an append past
        # capacity retires the OLDEST (stalest) entry.
        self._buffer = collections.deque(maxlen=settings.buffer_size)
        registry = get_registry()
        self._reused = registry.counter(
            "stoix_tpu_impact_reused_batches_total",
            "Learner updates that re-stepped a buffered stale batch because "
            "fresh rollouts were late",
        )
        self._dropped = registry.counter(
            "stoix_tpu_impact_dropped_batches_total",
            "Buffered batches retired for exceeding system.impact.max_staleness",
        )

    def _ingest(self, items: List[Any]) -> None:
        for _actor_id, (version, payload) in items:
            self._pending.append((version, payload))

    def _pop_reusable(self, current_version: int) -> "ImpactBatch | None":
        max_lag = self._settings.max_staleness
        while self._buffer:
            # Newest entry first: it has the smallest lag, so if IT is too
            # stale everything behind it is too.
            version, batch, reuse_left = self._buffer[-1]
            if current_version - version > max_lag:
                self._dropped.inc(len(self._buffer))
                self._buffer.clear()
                return None
            if reuse_left <= 0:
                self._buffer.pop()
                continue
            self._buffer[-1][2] = reuse_left - 1
            self._reused.inc()
            return ImpactBatch(batch, version, fresh=False)
        return None

    def next_batch(
        self, assemble: Callable[[List[Any]], Any], current_version: int,
        timeout: float = 180.0,
    ) -> ImpactBatch:
        """One update's batch: fresh when a full payload set is available (or
        arrives while the buffer is empty), else a buffered stale batch."""
        self._ingest(self._pipeline.poll(max_items=4 * self._need, timeout=0.0))
        if len(self._pending) < self._need:
            reusable = self._pop_reusable(current_version)
            if reusable is not None:
                return reusable
            while len(self._pending) < self._need:
                self._ingest(self._pipeline.wait_for_data(timeout=timeout))
        take, self._pending = self._pending[: self._need], self._pending[self._need:]
        version = min(v for v, _ in take)
        batch = assemble([p for _, p in take])
        if self._settings.max_reuse > 0:
            self._buffer.append([version, batch, self._settings.max_reuse])
        return ImpactBatch(batch, version, fresh=True)


class ImpactSource(OnPolicySource):
    """Push/poll ingestion: a slow actor no longer gates every update — the
    learner re-steps buffered stale batches instead (`ImpactIngest`). Its
    learn step takes the slow-moving target params as a second operand.
    Actors store and wait as on the on-policy path."""

    pipeline_class = OffPolicyPipeline

    def __init__(self, ctx: SourceContext, settings: ImpactSettings):
        super().__init__(ctx)
        self._settings = settings
        self._ingest = ImpactIngest(self.pipeline, ctx.num_actors, settings)
        # Target network = device-side alias of a recent online version (the
        # initial one at the first step), refreshed on the host every
        # target_update_interval updates.
        self._target_params = None
        self._staleness_gauge = get_registry().gauge(
            "stoix_tpu_impact_batch_staleness",
            "Param-version lag (learner version minus behavior version) of "
            "the batch consumed by the most recent IMPACT update",
        )
        self._refreshes = get_registry().counter(
            "stoix_tpu_impact_target_refreshes_total",
            "IMPACT target-network refreshes from the online params",
        )
        self._staleness_sum = 0
        self._stats = dict.fromkeys(
            ("updates", "fresh_updates", "reused_updates", "max_staleness_seen",
             "target_refreshes"), 0,
        )

    def push(self, actor_id: int, behavior_version: int, payload: Any, timeout: float) -> None:
        self.pipeline.push(actor_id, (behavior_version, payload), timeout=timeout)

    def next_batch(self, update_idx: int, param_server: Any) -> Batch:
        with span("impact_next_batch", clock=self._ctx.timer, phase="rollout_get",
                  update=update_idx):
            got = self._ingest.next_batch(self._assemble, param_server.version)
        self._note("rollout_get")
        # First-class staleness: the learner's current version (=
        # completed distributes, i.e. the params it just trained)
        # minus the OLDEST behavior version in the batch; grows on
        # every re-step of the same buffered batch.
        staleness = param_server.version - got.behavior_version
        self._staleness_gauge.set(staleness)
        stats = self._stats
        stats["updates"] += 1
        stats["fresh_updates" if got.fresh else "reused_updates"] += 1
        self._staleness_sum += staleness
        stats["max_staleness_seen"] = max(stats["max_staleness_seen"], staleness)
        # Re-stepping a buffered batch consumes no NEW env frames: the
        # learner's step count stays an env-frame count (fps denominators,
        # eval t axis) rather than a gradient-step count.
        return Batch(got.batch, got.fresh, self._ctx.steps_per_update if got.fresh else 0)

    def step(self, learn_step: Callable, state: Any, batch: Batch) -> Any:
        if self._target_params is None:
            self._target_params = state.params
        return learn_step(state, self._target_params, batch.data)

    def after_update(self, state: Any) -> None:
        if self._stats["updates"] % self._settings.target_update_interval == 0:
            self._target_params = state.params
            self._stats["target_refreshes"] += 1
            self._refreshes.inc()

    def run_stats(self) -> dict:
        mean_staleness = self._staleness_sum / max(1, self._stats["updates"])
        return {"impact": {
            **self._settings._asdict(), **self._stats, "mean_staleness": mean_staleness,
        }}


class ReplaySource(_Source):
    """Actors push transition shards whenever a rollout is ready and the
    learner adds what has arrived to the sharded replay service
    (stoix_tpu/replay), which its learn step samples: no lockstep collect, so
    a slow or restarting actor never stalls the learner. The learn step takes
    the service's state (donated) and returns the new one."""

    pipeline_class = OffPolicyPipeline
    # [T, E] -> [T*E] transitions -> one shard per learner device, placed
    # directly on its owner for global-array assembly (leading-axis sharding,
    # no host concat).
    storage = FlatRolloutStorage
    # Off-policy actors NEVER wait for params: they take a fresh version when
    # one is queued, otherwise keep acting on the current one (staleness is
    # the architecture's contract).
    actor_fetch_from = 1
    actor_fetch_timeout = 0.0
    wait_phase = "ingest"

    def __init__(self, ctx: SourceContext, service: Any, epochs: int, param_sync_interval: int):
        super().__init__(ctx)
        self._service = service
        self._epochs = epochs
        self.param_sync_interval = param_sync_interval
        self._base = service.stats()
        self._warmed = False

    def push(self, actor_id: int, behavior_version: int, payload: Any, timeout: float) -> None:
        # Episode metrics travel via the actors' metrics sink, not through
        # replay HBM.
        self.pipeline.push(actor_id, payload._replace(info={}), timeout=timeout)

    def _add(self, items: List[Any]) -> None:
        """Assemble each pushed payload into ONE global array per leaf
        (shards already sit on their owning learner devices) and add."""
        for _actor_id, payload in items:
            flat, treedef = jax.tree.flatten(payload, is_leaf=_is_shards)
            merged = [
                assemble_global_array(leaf, self._ctx.learner_mesh, axis="data")
                if len(leaf) > 1
                else leaf[0]
                for leaf in flat
            ]
            self._service.add(jax.tree.unflatten(treedef, merged))

    def next_batch(self, update_idx: int, param_server: Any) -> Batch:
        added_before = self._service.stats()["added_items"]
        with self._ctx.timer.time("ingest"):
            self._add(self.pipeline.poll(timeout=0.0))
            # can_sample is monotonic (fill only grows), so the jitted
            # psum + host fetch runs only until the first True.
            while not self._warmed and not self._service.can_sample():
                # Warmup/starvation path: block for more experience (a
                # dead actor fleet raises typed starvation here).
                self._add(self.pipeline.wait_for_data(timeout=180.0))
            self._warmed = True
        self._note("ingest")
        added = self._service.stats()["added_items"] - added_before
        return Batch(self._service.state, True, added)

    def step(self, learn_step: Callable, state: Any, batch: Batch) -> Any:
        state, new_replay, train_metrics = learn_step(state, batch.data)
        self._service.commit(new_replay)
        self._service.note_embedded_samples(self._epochs)
        return state, train_metrics

    def observe(self) -> dict:
        return {
            f"replay_{k}": v for k, v in self._service.observe().items()
            if not isinstance(v, list)
        }

    def run_stats(self) -> dict:
        stats = self._service.stats()
        return {"replay": {k: stats[k] - self._base[k] for k in stats}}
