"""PolicyServer: the serving subsystem's composition root (docs/DESIGN.md
§2.8).

Wires checkpoint loading (serve/checkpoint.py), the dynamic batcher
(serve/batcher.py), the jitted engine (serve/engine.py), SLO telemetry
(serve/telemetry.py), and the hot-swap watcher (serve/hotswap.py) into one
lifecycle:

    server = PolicyServer.from_config(compose(dir, "default/serve.yaml", ov))
    with server:                      # start(): watchdog-guarded warmup
        result = server.infer(obs)    # or submit() for async callers

One worker thread owns the device: it drains the batcher, pads to a bucket,
runs the jitted forward pass, and completes each request's future. Caller
threads never touch jax — submit/result are pure host-side queue operations,
so ANY number of concurrent callers share the one engine.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

import jax
import numpy as np

from stoix_tpu.observability import (
    get_health_monitor,
    get_logger,
    get_status_board,
    span,
)
from stoix_tpu.parallel import MeshRoles
from stoix_tpu.resilience import faultinject
from stoix_tpu.serve import checkpoint as serve_checkpoint
from stoix_tpu.serve.batcher import DEFAULT_BUCKETS, DynamicBatcher, PendingRequest
from stoix_tpu.serve.engine import InferenceEngine
from stoix_tpu.serve.errors import ServerClosedError, ServerOverloadError
from stoix_tpu.serve.hotswap import ParameterWatcher
from stoix_tpu.serve.telemetry import ServeTelemetry
from stoix_tpu.utils import compilecache


class ServeResult(NamedTuple):
    """One request's answer: the action plus distribution extras (logits for
    categorical heads) as host numpy arrays."""

    action: np.ndarray
    extras: Dict[str, np.ndarray]


class PolicyServer:
    def __init__(
        self,
        apply_fn: Any,
        params: Any,
        obs_template: Any,
        buckets: Any = DEFAULT_BUCKETS,
        max_wait_s: float = 0.005,
        max_queue: int = 256,
        greedy: bool = True,
        key: Optional[jax.Array] = None,
        source: Any = None,
        initial_step: int = 0,
        hot_swap_poll_s: float = 0.0,
        hot_swap_canary: bool = True,
        compile_deadline_s: float = 600.0,
        device: Optional[jax.Device] = None,
        name: str = "serve",
        replica_id: Optional[int] = None,
    ):
        # `name` namespaces the global status/health registrations so N
        # replicas can coexist in one process (the loop fleet,
        # docs/DESIGN.md §2.15). The default reproduces the original keys
        # ("serve_slo" / "serve-worker") exactly, so the single-server path
        # registers bit-identically to before. `replica_id` is the fleet
        # ordinal — only the replica_slow fault injection reads it.
        self.name = str(name)
        self._replica_id = replica_id
        self.telemetry = ServeTelemetry()
        self.obs_template = obs_template
        self._engine = InferenceEngine(
            apply_fn, params, obs_template, buckets=buckets, greedy=greedy, key=key,
            device=device,
        )
        self._batcher = DynamicBatcher(
            buckets=buckets, max_wait_s=max_wait_s, max_queue=max_queue
        )
        self._compile_deadline_s = float(compile_deadline_s)
        self._stop = threading.Event()
        self._killed = threading.Event()
        self._worker = threading.Thread(
            target=self._worker_loop, name=self._worker_name(), daemon=True
        )
        self._started = False
        self._log = get_logger("stoix_tpu.serve")
        self.watcher: Optional[ParameterWatcher] = None
        if source is not None and hot_swap_poll_s > 0:
            self.watcher = ParameterWatcher(
                source,
                self._engine,
                self.telemetry,
                current_step=initial_step,
                poll_interval_s=hot_swap_poll_s,
                canary=hot_swap_canary,
            )

    @classmethod
    def from_config(cls, config: Any, roles: Optional[MeshRoles] = None) -> "PolicyServer":
        """Build from a composed serve config (the `default/serve.yaml` root
        with the configs/arch/serve.yaml block under config.arch.serve).

        Device assignment rides the unified mesh-role abstraction
        (parallel/roles.py, docs/DESIGN.md §2.11): the `serve` role names the
        device the engine owns (default: device 0 — jax's default device,
        i.e. the pre-MeshRoles placement). Pass `roles` to share one
        MeshRoles object across subsystems (e.g. a colocated train+serve
        deployment)."""
        # Compile economy (docs/DESIGN.md §2.7): the persistent cache goes on
        # before the policy is rebuilt and the buckets are warmed.
        compilecache.configure(config)
        bundle = serve_checkpoint.load_policy(config)
        serve_cfg = config.arch.serve
        if roles is None:
            roles = MeshRoles.from_config(config)
        batching = serve_cfg.batching
        hot_swap = serve_cfg.hot_swap
        seed = int(serve_cfg.get("seed", 0))
        return cls(
            apply_fn=bundle.apply_fn,
            params=bundle.params,
            obs_template=bundle.obs_template,
            buckets=[int(b) for b in batching.buckets],
            max_wait_s=float(batching.max_wait_ms) / 1000.0,
            max_queue=int(batching.max_queue),
            greedy=bool(serve_cfg.greedy),
            key=jax.random.PRNGKey(seed),
            source=bundle.source,
            initial_step=bundle.step,
            hot_swap_poll_s=(
                float(hot_swap.poll_interval_s) if bool(hot_swap.enabled) else 0.0
            ),
            hot_swap_canary=bool(hot_swap.get("canary", True)),
            compile_deadline_s=float(serve_cfg.compile_deadline_s),
            device=roles.device("serve"),
        )

    # -- naming ---------------------------------------------------------------
    def _worker_name(self) -> str:
        # "serve" -> "serve-worker" (the historical thread/check name);
        # "loop_replica0" -> "loop_replica0-worker".
        return f"{self.name}-worker"

    def _status_key(self) -> str:
        return f"{self.name}_slo"

    # -- lifecycle ------------------------------------------------------------
    def start(self) -> "PolicyServer":
        """Warm every bucket under a first-compile watchdog (a wedged backend
        raises CompileStallError with a stack dump instead of hanging the
        server forever — docs/DESIGN.md §2.4 discipline), then start the
        worker and the hot-swap watcher."""
        if self._started:
            return self
        from stoix_tpu.resilience.watchdog import Watchdog

        with Watchdog("serve_warmup", deadline_s=self._compile_deadline_s):
            compiled = self._engine.warmup()
        self._log.info(
            "[serve] warmed %d bucket specialization(s) %s — serving",
            compiled, list(self._engine.buckets),
        )
        self._worker.start()
        if self.watcher is not None:
            self.watcher.start()
        # Ops plane (docs/DESIGN.md §2.13): /statusz renders the SLO ladder
        # live (the provider is called at render time, not snapshotted here)
        # and /healthz turns 503 if the batch worker thread dies.
        get_status_board().register_provider(
            self._status_key(), self.telemetry.slo_snapshot
        )
        get_health_monitor().register_check(
            self._worker_name(),
            lambda: None if self._worker.is_alive() else "serve worker thread dead",
        )
        self._started = True
        return self

    def close(self, join_timeout: float = 10.0) -> None:
        get_status_board().unregister_provider(self._status_key())
        get_health_monitor().unregister(self._worker_name())
        if self.watcher is not None:
            self.watcher.stop()
        self._stop.set()
        if self._worker.is_alive():
            self._worker.join(timeout=join_timeout)
        dropped = self._batcher.close()
        if dropped:
            self._log.warning(
                "[serve] shutdown dropped %d still-pending request(s) "
                "(completed with ServerClosedError)", dropped,
            )

    def kill(self, join_timeout: float = 10.0) -> None:
        """Crash-style shutdown (the `replica_kill` chaos drill,
        docs/DESIGN.md §2.15). Unlike close()'s graceful drain, the worker
        dies WITHOUT completing its current batch: every queued and in-batch
        request completes with ServerClosedError — exactly what a powered-off
        replica looks like to the FleetRouter, whose failover path must
        re-dispatch the accepted requests."""
        self._killed.set()
        self.close(join_timeout=join_timeout)

    def __enter__(self) -> "PolicyServer":
        return self.start()

    def __exit__(self, *exc_info: Any) -> None:
        self.close()

    # -- request path ---------------------------------------------------------
    @property
    def engine(self) -> InferenceEngine:
        """The replica's engine — the fleet publisher drives check_now /
        rollback against it (docs/DESIGN.md §2.15)."""
        return self._engine

    def healthy(self) -> bool:
        """Liveness probe the FleetRouter polls for ejection/re-admission:
        started, not closing, and the batch worker thread still running."""
        return self._started and not self._stop.is_set() and self._worker.is_alive()

    @property
    def compile_count(self) -> int:
        return self._engine.compile_count

    @property
    def params_version(self) -> int:
        return self._engine.params_version

    def submit(self, observation: Any) -> PendingRequest:
        """Async path: enqueue one unbatched observation pytree (shaped like
        `obs_template`); returns the request future. Raises
        ServerOverloadError when shedding and ServerClosedError after
        close() — both typed, both counted."""
        if not self._started:
            raise ServerClosedError("server not started — call start() first")
        try:
            request = self._batcher.submit(observation)
        except ServerOverloadError:
            self.telemetry.request_shed()
            raise
        self.telemetry.queue_depth(self._batcher.depth())
        return request

    def infer(self, observation: Any, timeout: float = 30.0) -> ServeResult:
        """Sync convenience: submit + wait."""
        return self.submit(observation).result(timeout=timeout)

    # -- worker ---------------------------------------------------------------
    def _complete(self, batch: List[PendingRequest], action: Any, extras: Any) -> None:
        action_np = np.asarray(action)
        extras_np = {k: np.asarray(v) for k, v in extras.items()}
        for i, request in enumerate(batch):
            request.set_result(
                ServeResult(
                    action=action_np[i],
                    extras={k: v[i] for k, v in extras_np.items()},
                )
            )
            self.telemetry.request_ok(request.latency_s)

    def _worker_loop(self) -> None:
        while not self._stop.is_set():
            batch = self._batcher.next_batch(idle_timeout=0.05)
            if not batch:
                continue
            try:
                if self._replica_id is not None:
                    faultinject.maybe_slow_replica(self._replica_id)
                with span("serve_batch", n=len(batch)):
                    start = time.perf_counter()
                    action, extras, bucket = self._engine.infer(
                        [request.observation for request in batch]
                    )
                    if self._killed.is_set():
                        # Crash-style kill(): the batch dies WITH the worker
                        # — callers see ServerClosedError and fail over.
                        raise ServerClosedError(f"{self.name} killed mid-batch")
                    self._complete(batch, action, extras)
                self.telemetry.batch_done(
                    len(batch), bucket, time.perf_counter() - start
                )
                self.telemetry.queue_depth(self._batcher.depth())
            except Exception as exc:  # noqa: BLE001 — one malformed
                # observation must fail ITS batch with a typed result, not
                # kill the worker and wedge every later caller.
                self.telemetry.request_error(len(batch))
                for request in batch:
                    request.set_error(exc)
                self._log.error(
                    "[serve] batch of %d failed: %s: %s",
                    len(batch), type(exc).__name__, exc,
                )
