"""Host spans and jitted-code scopes: ONE primitive each, one clock.

`span(name)` is the only way a host phase is marked. It always opens a
`jax.profiler.TraceAnnotation(name)` (name only, so the event keeps exactly
that name in the XSpace): every profiler session — the benchmark's traced
run, an operator's `STOIX_TPU_PROFILE_DIR=<dir>` — carries every host span,
each on its own thread's line, on the clock the device ops are on. That
profiler trace is where host spans and device ops line up. With no session
open a TraceAnnotation is a few hundred nanoseconds (PERF.md §6, PR 23).

Besides, a span can
  * feed a phase counter: `span(name, clock=c, phase=p)` calls
    `c.record(p, seconds)` when it closes (the Anakin runner's phase clock,
    a Sebulba `TimingTracker`, a labelled wait `Histogram`, `SetupClock`),
    so no call site keeps a `perf_counter()` pair beside its span;
  * be recorded, when `logger.telemetry.enabled`, as one complete event
    (Chrome trace `"ph": "X"`) in the process-wide `TraceRecorder`, which
    trace_export.py writes as Perfetto-loadable JSON. Those timestamps are
    `time.perf_counter_ns()` against the recorder's OWN epoch, in
    microseconds: that file shows the host threads against each other, not
    against the device (it shares no epoch with the profiler trace).

For code under `jax.jit`, use `annotate(name)` — a `jax.named_scope`, trace-
time metadata only — with a name from `SCOPES`: it tags the XLA ops, so the
device trace can be cut by scope. `SCOPES` and `HOST_SPANS` are the name
tables the systems import and the benchmark's readers and tests read.
"""

from __future__ import annotations

import contextlib
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

import stoix_tpu

# Scopes inside the jitted programs (path components of the ops' framework
# path in the device trace). One table for both architectures.
SCOPES = {
    "rollout": "rollout",  # one env-step scan body (Anakin `_env_step`)
    "rollout_policy": "rollout_policy",  # actor+critic apply, sampling (also Sebulba `act_fn`)
    "rollout_env": "rollout_env",  # `env.step`
    "gae": "gae",  # bootstrap values and the `ops/multistep` call
    "update_epoch": "ppo_epoch",  # one epoch: shuffle + minibatch scan
    "update_minibatch": "ppo_minibatch",  # one SGD step
    "minibatch_shuffle": "minibatch_shuffle",  # permutation + `take` over the trajectory
    # The token policy's block (networks/olmoe.py), under `rollout` (cached
    # decode) and under `ppo_epoch` (teacher-forced update) alike.
    "attention": "attention",  # input norm, q/k/v/o projections, RoPE, cache write, softmax
    "moe": "moe",  # the sparse-expert layer: the three scopes below
    "moe_router": "moe_router",  # router matmul, float32 softmax, top-k
    "moe_dispatch": "moe_dispatch",  # sort by expert, gather, un-permute, weighted combine
    "moe_experts": "moe_experts",  # the three grouped matmuls and the SwiGLU between
    "lm_head": "lm_head",  # final norm, head matmul, log-prob / entropy / sampling over the vocabulary
    # Generation by diffusion over blocks (systems/ppo/anakin/ff_sdar_ppo.py,
    # networks/sdar.py): the two kinds of pass under `rollout` (and in the
    # evaluator), and the score/softmax/value products inside `attention`.
    "denoise": "denoise",  # the policy's pass over a block: nothing is written to the cache
    "block_commit": "block_commit",  # the finished block's pass that writes its keys and values
    "attention_scores": "attention_scores",  # q k^T, the masked softmax, p v (both entry points)
    # The hybrid stack (networks/lfm2.py): a mixer that is not attention and a
    # feed-forward that is not routed, under `rollout`, `ppo_epoch` and in the
    # evaluator alike.
    # operator norm, W_in, the two gates, the 3-tap conv, W_out, the tail's write
    "conv_mixer": "conv_mixer",
    "conv_mixer_conv": "conv_mixer_conv",  # inside it: the gates and the conv, all that is no matmul
    "dense_mlp": "dense_mlp",  # the dense SwiGLU feed-forward of the leading layers
    # Multi-head latent attention (networks/mla.py) inside `attention`, and the
    # always-on expert beside the routed ones, in both entry points alike.
    # down-projection W_kva, the latent's norm, the partial rotation, the cache row's write
    "latent_project": "latent_project",
    # decode: W_uk into the query, scores and values against the latent rows, W_uv;
    # update: the expansion W_kvb and the attention kernel, forward and backward
    "latent_attend": "latent_attend",
    "shared_expert": "shared_expert",  # the SwiGLU every token passes, beside the routed experts
    # The delta-rule linear-attention layer (networks/kda.py), under `rollout`,
    # `ppo_epoch` and in the evaluator alike.
    # operator norm, W_q W_k W_v W_f and the gates, the convolutions, the recurrence, the
    # output norm and gate, W_o
    "delta_mixer": "delta_mixer",
    "delta_conv": "delta_conv",  # inside it: the three 4-tap convolutions, SiLU, the q/k norms, the tails
    # inside it: the recurrence alone (ops/delta_rule.py) — the chunked form in the update,
    # one token against its matrix state in the decode
    "delta_rule": "delta_rule",
    # A window layer (networks/lfm2.py, `sliding_attention`), under `rollout`,
    # `ppo_epoch` and in the evaluator alike; a full layer beside it keeps `attention`,
    # its attend under `attention_scores`.
    # operator norm, q/k/v projections, q/k norm, rotation, the ring's write, the attend,
    # the gate, W_o
    "window_mixer": "window_mixer",
    # inside it: the attend alone — the banded flash kernel pair in the update, the
    # ring's read in the decode
    "window_attend": "window_attend",
    # A rollout that starts from a prompt (systems/ppo/anakin/ff_lm_ppo.py with
    # `env.prompt_length` > 0): the teacher-forced pass over the prefix that writes
    # every layer's decode state, BESIDE `rollout` in the learner (not under it:
    # `rollout` is decode steps alone) and in the evaluator; the mixers' and the
    # feed-forwards' scopes lie inside it as they do inside `ppo_epoch`.
    "prefill": "prefill",
}

# The scopes of the token policy's block: only the systems built on
# networks/olmoe.py carry them.
BLOCK_SCOPES = ("attention", "moe", "moe_router", "moe_dispatch", "moe_experts", "lm_head")
# What generation by diffusion over blocks adds to them: only the system
# built on networks/sdar.py carries these.
DIFFUSION_SCOPES = ("denoise", "block_commit", "attention_scores")
# What a stack of convolution and attention layers adds: only the systems
# built on networks/lfm2.py carry these.
HYBRID_SCOPES = ("conv_mixer", "conv_mixer_conv", "dense_mlp")
# What latent attention and a shared expert add: only a stack with a
# `latent_attention` layer and `n_shared_experts` carries these.
LATENT_SCOPES = ("latent_project", "latent_attend", "shared_expert")
# What a delta-rule linear-attention layer adds: only a stack with a
# `delta_attention` layer carries these.
DELTA_SCOPES = ("delta_mixer", "delta_conv", "delta_rule")
# What a window layer adds: only a stack with a `sliding_attention` layer
# carries these.
WINDOW_SCOPES = ("window_mixer", "window_attend")
# What a rollout from a prompt adds: only a token system whose env has a
# `prompt_length` carries it.
PROMPT_SCOPES = ("prefill",)

# Host spans that recur in steady state, by the thread that opens them. A
# trace reduction attributes a device-idle gap to the innermost of these open
# when the gap began (`host_annotations` of a benchmark config).
HOST_SPANS = {
    "anakin": (
        "learn_dispatch", "gossip_dispatch", "snapshot_dispatch", "eval_dispatch",
        "fetch_dispatch", "fetch_materialize", "window_bookkeeping", "log", "ckpt_save",
    ),
    "sebulba_actor": (
        "actor_rollout", "actor_inference", "actor_env_step", "actor_prepare_data",
        "pipeline_put", "param_get",
    ),
    "sebulba_learner": (
        "learner_rollout_wait", "pipeline_get", "learner_assemble", "learner_update",
        "param_push", "learner_log",
    ),
    "sebulba_evaluator": ("async_eval",),
}

_trace_annotation: Any = None


def _annotation(name: str) -> Any:
    """`jax.profiler.TraceAnnotation(name)`; jax is looked up on first use so
    that importing the telemetry package stays free of it. A process that has
    not imported jax (a tool that only composes a config) has no profiler
    session to annotate, and is not made to import it."""
    global _trace_annotation
    if _trace_annotation is None:
        if "jax" not in sys.modules:
            return contextlib.nullcontext()
        import jax

        _trace_annotation = jax.profiler.TraceAnnotation
    return _trace_annotation(name)


class _Span:
    """A TraceAnnotation that also times itself, for a phase clock and/or a
    recorder."""

    __slots__ = ("_recorder", "_name", "_args", "_clock", "_phase", "_annotation", "_start")

    def __init__(
        self, recorder: Optional["TraceRecorder"], name: str, args: Dict[str, Any],
        clock: Any, phase: Any,
    ):
        self._recorder = recorder
        self._name = name
        self._args = args
        self._clock = clock
        self._phase = phase

    def __enter__(self) -> "_Span":
        self._annotation = _annotation(self._name)
        self._annotation.__enter__()
        self._start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc: Any) -> None:
        end = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        if self._clock is not None:
            self._clock.record(self._phase, (end - self._start) / 1e9)
        if self._recorder is not None:
            self._recorder._record(self._name, self._start, end, self._args)


class TraceRecorder:
    """Bounded in-memory buffer of complete span events.

    `max_events` caps memory for long runs (drops record a counter so the
    export can say how many were lost — silent truncation would read as
    "nothing else happened")."""

    def __init__(self, max_events: int = 200_000):
        self._lock = threading.Lock()
        self._events: List[Dict[str, Any]] = []
        self._thread_names: Dict[int, str] = {}
        self._epoch_ns = time.perf_counter_ns()
        self._max_events = max_events
        self.dropped = 0
        self.enabled = False

    def span(self, name: str, clock: Any = None, phase: Any = None, **args: Any):
        if not self.enabled and clock is None:
            return _annotation(name)
        return _Span(self if self.enabled else None, name, args, clock, phase)

    def _record(self, name: str, start_ns: int, end_ns: int, args: Dict[str, Any]) -> None:
        thread = threading.current_thread()
        tid = thread.ident or 0
        with self._lock:
            if tid not in self._thread_names:
                self._thread_names[tid] = thread.name
            if len(self._events) >= self._max_events:
                self.dropped += 1
                return
            self._events.append(
                {
                    "name": name,
                    "ts": (start_ns - self._epoch_ns) / 1e3,  # microseconds
                    "dur": (end_ns - start_ns) / 1e3,
                    "tid": tid,
                    "args": {k: _jsonable(v) for k, v in args.items()},
                }
            )

    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def thread_names(self) -> Dict[int, str]:
        with self._lock:
            return dict(self._thread_names)

    def event_count(self) -> int:
        with self._lock:
            return len(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._thread_names.clear()
            self.dropped = 0
            self._epoch_ns = time.perf_counter_ns()


def _jsonable(v: Any) -> Any:
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)


_RECORDER = TraceRecorder()


def get_recorder() -> TraceRecorder:
    return _RECORDER


def span(name: str, clock: Any = None, phase: Any = None, **args: Any):
    """Context manager marking one host-side phase: a TraceAnnotation always;
    `clock.record(phase, seconds)` on close when a clock is given; a recorded
    event (with `args`) when telemetry is enabled (observability.configure)."""
    return _RECORDER.span(name, clock, phase, **args)


def set_enabled(enabled: bool) -> None:
    _RECORDER.enabled = bool(enabled)


def is_enabled() -> bool:
    return _RECORDER.enabled


def annotate(name: str):
    """Taxonomy tag for code under jit: a `jax.named_scope` (context manager
    and decorator). Trace-time only — the compiled program is the same — and
    the name becomes a component of the ops' path in the device trace. Under
    `vmap`/`grad` JAX wraps the outermost component (`vmap(gae)`)."""
    import jax

    return jax.named_scope(name)


def process_started_at() -> Optional[float]:
    """When the OS started this process, on the `perf_counter` clock: the
    start time of `/proc/self/stat` (field 22, clock ticks since boot) against
    `/proc/uptime`, both good to a hundredth of a second. None where the OS
    keeps no such files: the phase `process_boot` is then left out, not guessed."""
    try:
        with open("/proc/self/stat", "r", encoding="ascii") as handle:
            # The command's name (field 2) may hold spaces: count from its ")".
            start_ticks = float(handle.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime", "r", encoding="ascii") as handle:
            uptime = float(handle.read().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None
    return time.perf_counter() - age


class _Launch:
    """What passes between the package's import and the first `run_experiment`
    of a process, as the sites that know report it: the two runner modules the
    seconds of their own import blocks (two plain floats each: no span can be
    open before the package exists; neither module imports the other, so the
    blocks do not overlap), `config_lib.compose` its span. `take`
    hands it to the first `SetupClock` and closes the book: a later run in the
    same process was not launched by what launched the first."""

    def __init__(self) -> None:
        self._imports = 0.0
        self._compose = 0.0
        self._open = True

    def note_imports(self, began: float, ended: float) -> None:
        if self._open:
            self._imports += ended - began

    def record(self, phase: str, seconds: float) -> None:
        """The `span("compose", clock=LAUNCH, phase="compose")` sink."""
        if self._open:
            self._compose += seconds

    def take(self, entered: float) -> Optional[Dict[str, float]]:
        """The phases before a `run_experiment` entered at `entered`, the
        first time it is asked; None ever after."""
        if not self._open:
            return None
        self._open = False
        phases = {
            "imports": self._imports,
            "compose": self._compose,
            "launch": max(0.0, entered - stoix_tpu.IMPORTED_AT - self._imports - self._compose),
        }
        started = process_started_at()
        if started is not None:
            phases["process_boot"] = max(0.0, stoix_tpu.IMPORTED_AT - started)
        return phases


LAUNCH = _Launch()


def _backend_is_up() -> bool:
    """Whether this process has started a jax backend yet (a module-level
    array at import starts one long before any `run_experiment`)."""
    from jax._src import xla_bridge

    return bool(xla_bridge.backends_are_initialized())


class SetupClock:
    """`span(..., clock=SetupClock(ledger), phase=...)` sink for the once-a-run
    set-up phases: seconds per phase, published as the gauge
    `stoix_tpu_setup_phase_seconds{phase=...}` as each phase closes. Built as
    the first statement of a `run_experiment`, it is open from there to the
    close of `first_tick` (the first completed window or update), and its
    phases partition that wall: what no span covered is `{phase="unspanned"}`.

    The first clock of a process also publishes what came before it
    (`LAUNCH_PHASES`): `process_boot` (the OS's start of the process to the
    package's import), `imports` (the runner modules' import blocks),
    `compose`, and `launch`, the rest up to `run_experiment`. With those the
    gauge adds up from the process's start to the first tick. A later clock
    takes those four series away again. `stoix_tpu_setup_backend_up_at_entry`
    says whether a jax backend was already up when the clock opened.

    The run's `GoodputLedger` books the clock's whole wall as `setup`, less
    what it was told of it under another name (`compile`: the warm-up;
    `recovery`: a restore; `stall`)."""

    LAUNCH_PHASES = ("process_boot", "imports", "compose", "launch")
    PHASES = (
        "preflight", "mesh_build", "env_build", "rng_key", "network_init", "learner_setup",
        "state_warmup", "restore", "evaluator_setup", "logger_build", "aot_warmup",
        "first_tick", "unspanned",
    )

    def __init__(self, ledger: Any = None) -> None:
        self._opened = time.perf_counter()
        from stoix_tpu.observability.registry import get_registry

        self._ledger = ledger
        if ledger is not None:
            ledger.begin_setup()
        self._gauge = get_registry().gauge(
            "stoix_tpu_setup_phase_seconds",
            "Wall seconds of each set-up phase of the most recent run",
        )
        self._seconds: Dict[str, float] = {}
        for phase in self.PHASES:  # a fresh run does not show the last one's
            self._gauge.set(0.0, {"phase": phase})
        self.launch = LAUNCH.take(self._opened)
        for phase in self.LAUNCH_PHASES:
            if self.launch is not None and phase in self.launch:
                self._gauge.set(self.launch[phase], {"phase": phase})
            else:
                self._gauge.remove({"phase": phase})
        get_registry().gauge(
            "stoix_tpu_setup_backend_up_at_entry",
            "1 if a jax backend was already started when the most recent run_experiment was entered",
        ).set(float(_backend_is_up()))

    def record(self, phase: str, seconds: float) -> None:
        self._seconds[phase] = self._seconds.get(phase, 0.0) + seconds
        self._gauge.set(self._seconds[phase], {"phase": phase})

    def open_first_tick(self) -> contextlib.ExitStack:
        """Set-up's last phase, `first_tick`, as a stack its owner closes where
        the first window or update completes (and once more, to no effect,
        where a run that never got there ends). Its close is the clock's."""
        stack = contextlib.ExitStack()
        stack.callback(self._close)
        stack.enter_context(span("first_tick", clock=self, phase="first_tick"))
        return stack

    def _close(self) -> None:
        wall = time.perf_counter() - self._opened
        self.record("unspanned", wall - sum(self._seconds.values()))
        if self._ledger is not None:
            self._ledger.end_setup(wall)

    def seconds(self) -> Dict[str, float]:
        """This run's own phases (those of `PHASES` that a span closed)."""
        return dict(self._seconds)
