"""Compile economy: persistent XLA compilation cache + `jax.export` AOT store.

1. **Persistent compilation cache — always on, placed from outside.**
   `configure()` runs before the first compile of every entry point that
   compiles (Anakin runner, Sebulba systems, population, serve, loop,
   bench.py, chip_smoke.py). Where it lives is decided in exactly one place,
   `cache_dir()`:

     * `JAX_COMPILATION_CACHE_DIR` set  → that directory. jax reads the
       variable into `jax_compilation_cache_dir` itself; this module then
       never writes that option.
     * unset → `<checkout>/xla_cache`, one fixed absolute path derived from
       this file's location (git-ignored). Never the working directory, a
       temp name, a pid or the time: the path is part of what a second
       process must agree on to hit.

   The `arch.compile_cache` block only carries the two admission knobs
   (min entry size / min compile time) and the export directory. Cache
   hits/misses are observable: jax's `/jax/compilation_cache/*` monitoring
   events are folded into the metrics registry as
   `stoix_tpu_compile_persistent_cache_events_total{event=hit|miss}` and
   surfaced by `cache_stats()`. What every program costs to bring to an
   executable is observable by stage: one listener of jax's duration events
   (`_CompileStages`) keeps `stoix_tpu_compile_seconds_total{program,
   stage=trace|lower|backend}`, `stoix_tpu_compiles_total{program}` and
   `stoix_tpu_compile_cache_retrieval_seconds_total` (the part of `backend`
   that read an executable back from the cache). It runs at compile events
   only: in steady state never, and an increment there names the program
   that recompiled. A corrupted cache entry degrades to a
   recompile, never a crash (`jax_raise_persistent_cache_errors` stays
   False; tests/test_compilecache.py pins it).

2. **AOT export of the top-level learn function.** `warmup_with_export`
   extends `utils/jax_utils.aot_warmup`: when `arch.compile_cache.export_dir`
   is set, the serialized `jax.export` artifact (StableHLO + shardings) of
   the jitted+shard_mapped learner is loaded when one exists for the same
   input avals / topology / jax version, else compiled once and serialized
   for peers. The deserialized path trades buffer donation for tracing
   economy (an `Exported.call` cannot donate its operands — documented in
   §2.7), so it is opt-in.

Everything here is host-side setup code: nothing in this module is
jit-reachable. A missing or unloadable EXPORT artifact downgrades to a compile
from source with a logged warning; a failed COMPILE is never swallowed
(`aot_warmup` raises).
"""

from __future__ import annotations

import hashlib
import os
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.export as jax_export

from stoix_tpu.observability import get_logger, get_registry

# jax's monitoring event names for the persistent compilation cache.
_EVENT_HITS = "/jax/compilation_cache/cache_hits"
_EVENT_MISSES = "/jax/compilation_cache/cache_misses"

_CACHE_EVENTS_METRIC = "stoix_tpu_compile_persistent_cache_events_total"

# jax's duration events of the three stages from a Python function to an
# executable (each carries `fun_name`), and of a persistent-cache read (none).
_STAGE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_EVENT_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"

COMPILE_SECONDS_METRIC = "stoix_tpu_compile_seconds_total"
COMPILES_METRIC = "stoix_tpu_compiles_total"
RETRIEVAL_SECONDS_METRIC = "stoix_tpu_compile_cache_retrieval_seconds_total"
# Set-up compiles dozens of one-op programs for eager `jnp` calls (41 names
# before `learner_fn` in a tiny MLP PPO run), and a long-lived process may see
# any number of names: the first this many keep their own, and so do as many
# again whose stage took `NAMED_FROM_SECONDS` or more (jax's own measure of a
# program worth caching), so that a learner is not lost behind the one-op
# programs. The rest share `OTHER_PROGRAM`.
MAX_PROGRAM_LABELS = 64
NAMED_FROM_SECONDS = 1.0
OTHER_PROGRAM = "other"

_listener_lock = threading.Lock()
_listener_installed = False

EXPORT_SUFFIX = ".jaxexport"


def _cache_counter():
    return get_registry().counter(
        _CACHE_EVENTS_METRIC,
        "Persistent XLA compilation cache events, labelled event=hit|miss",
    )


def _retrieval_counter():
    return get_registry().counter(
        RETRIEVAL_SECONDS_METRIC,
        "Seconds of the backend stage spent reading executables from the persistent cache",
    )


def _compiles_counter():
    return get_registry().counter(
        COMPILES_METRIC, "Backend compilations (or cache reads) of each program"
    )


def compile_counts() -> Tuple[int, int]:
    """(programs, backend compilations or cache reads) of this process so far,
    as `stoix_tpu_compiles_total{program}` has them: what a set-up log line
    prints, so that an eager op creeping back into set-up shows in a run's log."""
    counts = [count for _, count in _compiles_counter().labels_and_values()]
    return len(counts), int(sum(counts))


class _CompileStages:
    """The `jax.monitoring` duration listener behind
    `stoix_tpu_compile_seconds_total{program, stage}`: every second jax spends
    tracing, lowering or compiling (a cache read included) goes to ONE
    program and ONE stage.

    jax times each stage from outside, so its events nest: a function traced
    inside another's trace (every `jnp` call is a `jit`) reports seconds the
    outer trace reports too, and an eager op met while tracing is lowered and
    compiled inside that trace. A nested trace is the outer program's tracing
    and is dropped; anything else that ended inside a stage (it began after
    the stage did, on the same thread) is taken off that stage's seconds and
    stays under its own name. `program` is jax's `fun_name` without its
    `jit(...)` wrapper, so the three stages of one program share a label."""

    _KEPT_ENDED = 1024  # stages a thread remembers for an enclosing one to claim

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._programs: set = set()
        self._threads = threading.local()

    def _label(self, fun_name: Any, duration: float) -> str:
        name = str(fun_name)
        if name.startswith("jit(") and name.endswith(")"):
            name = name[4:-1]
        with self._lock:
            if name not in self._programs:
                room = MAX_PROGRAM_LABELS * (2 if duration >= NAMED_FROM_SECONDS else 1)
                if len(self._programs) >= room:
                    return OTHER_PROGRAM
                self._programs.add(name)
        return name

    def __call__(self, event: str, duration: float, **kwargs: Any) -> None:
        stage = _STAGE_EVENTS.get(event)
        if stage is None:
            if event == _EVENT_RETRIEVAL:
                _retrieval_counter().inc(duration)
            return
        if stage == "trace" and not jax.core.trace_ctx.is_top_level():
            return
        ended = time.perf_counter()
        began = ended - duration
        done = getattr(self._threads, "done", None)
        if done is None:
            done = self._threads.done = []
        inside = 0.0
        while done and done[-1][0] >= began:
            inside += done.pop()[1]
        done.append((began, duration))
        if len(done) > self._KEPT_ENDED:
            del done[: -self._KEPT_ENDED // 2]
        program = self._label(kwargs.get("fun_name", "unnamed"), duration)
        # Looked up an event, as `_cache_counter` is: a cleared registry (tests)
        # must not leave the listener feeding instruments nobody can read.
        get_registry().counter(
            COMPILE_SECONDS_METRIC,
            "Seconds jax spent bringing each program to an executable, by stage (trace, "
            "lower, backend: a compile or a cache read); a stage inside another counted once",
        ).inc(max(0.0, duration - inside), {"program": program, "stage": stage})
        if stage == "backend":
            _compiles_counter().inc(1.0, {"program": program})


def install_cache_metrics_listener() -> None:
    """Idempotently fold jax's compilation-cache monitoring events, and the
    durations of the compile stages, into the metrics registry. Installed by
    `configure()`; safe to call repeatedly (and from tests) — only the first
    call registers."""
    global _listener_installed
    with _listener_lock:
        if _listener_installed:
            return

        def _on_event(event: str, **_kwargs: Any) -> None:
            if event == _EVENT_HITS:
                _cache_counter().inc(1.0, {"event": "hit"})
            elif event == _EVENT_MISSES:
                _cache_counter().inc(1.0, {"event": "miss"})

        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_CompileStages())
        # Present from the first run on: a run that read nothing back reads 0.
        _retrieval_counter().inc(0.0)
        _listener_installed = True


def cache_stats() -> Dict[str, int]:
    """Persistent-cache hit/miss totals for this process (registry-backed)."""
    counter = _cache_counter()
    return {
        "hits": int(counter.value({"event": "hit"})),
        "misses": int(counter.value({"event": "miss"})),
    }


CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "xla_cache",
)


# (directory, min entry bytes, min compile secs) last applied by configure().
_applied: Optional[Tuple[str, int, float]] = None


def cache_dir() -> str:
    """Where the persistent cache lives: the variable when set, else the one
    fixed path inside the checkout."""
    return os.environ.get(CACHE_DIR_ENV) or _CHECKOUT_CACHE_DIR


def settings_from_config(config: Any) -> Dict[str, Any]:
    """The `arch.compile_cache` block as a plain dict with defaults applied
    (the dict-style read keeps STX009 happy on configs that omit the block;
    `config=None` — entry points without an arch config — is all defaults)."""
    block = (config.arch.get("compile_cache") or {}) if config is not None else {}
    min_compile = block.get("min_compile_time_secs")
    return {
        "min_entry_size_bytes": int(block.get("min_entry_size_bytes", 0) or 0),
        # jax's own default admission: programs that took >= 1 s to compile.
        "min_compile_time_secs": 1.0 if min_compile is None else float(min_compile),
        "export_dir": block.get("export_dir"),
    }


def configure(config: Any = None) -> str:
    """Turn jax's persistent compilation cache on at `cache_dir()` with the
    config's admission knobs, start recording hit/miss metrics, and return the
    directory. Must run before the first compile of interest; later compiles
    in this process all flow through the cache."""
    global _applied
    settings = settings_from_config(config)
    directory = cache_dir()
    applied = (
        directory, settings["min_entry_size_bytes"], settings["min_compile_time_secs"]
    )
    if applied == _applied:
        # A process that runs several experiments (chip_smoke.py, bench.py
        # --all, the tests) re-enters here with nothing to change.
        return directory
    os.makedirs(directory, exist_ok=True)
    if not os.environ.get(CACHE_DIR_ENV):
        jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update(
        "jax_persistent_cache_min_entry_size_bytes", settings["min_entry_size_bytes"]
    )
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", settings["min_compile_time_secs"]
    )
    # jax latches is-the-cache-used ONCE per process, at its first compile: a
    # single jit executed before this point (an import-time helper, an env
    # probe) would silently disable the cache for the whole run. Reset the
    # latch so it re-evaluates under the directory configured above.
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()
    install_cache_metrics_listener()
    _applied = applied
    get_logger("stoix_tpu.compilecache").info(
        "[compilecache] persistent XLA cache at %s (min entry %d B, min "
        "compile %.1f s)",
        directory, settings["min_entry_size_bytes"],
        settings["min_compile_time_secs"],
    )
    return directory


# ---------------------------------------------------------------------------
# jax.export AOT serialize/load of the top-level learn function
# ---------------------------------------------------------------------------


def _aval_digest(example_args: Tuple[Any, ...]) -> str:
    """Stable digest of the call signature the export is valid for: input
    avals + jax version + backend + device count. Anything that changes the
    compiled program's meaning changes the file name, so a stale artifact is
    simply never loaded (invalidation by construction, docs/DESIGN.md §2.7)."""
    avals = jax.tree.map(
        lambda leaf: str(jax.api_util.shaped_abstractify(leaf)), example_args
    )
    payload = "|".join(
        [
            str(avals),
            jax.__version__,
            jax.default_backend(),
            str(jax.device_count()),
        ]
    )
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


def export_artifact_path(export_dir: str, name: str, example_args: Tuple[Any, ...]) -> str:
    digest = _aval_digest(example_args)
    safe_name = "".join(c if (c.isalnum() or c in "-_") else "_" for c in name)
    return os.path.join(export_dir, f"{safe_name}-{digest}{EXPORT_SUFFIX}")


_registered_serializations: set = set()


def register_tree_serialization(tree: Any) -> None:
    """Make every NamedTuple node in `tree` serializable by jax.export.

    Learner states are NamedTuples of NamedTuples (PPOLearnerState,
    ActorCriticParams, optax's ScaleByAdamState, ...) and jax.export refuses
    to serialize unregistered custom pytree types. Registration needs a
    STABLE name — module.qualname is stable across processes of the same
    codebase, which is exactly the export store's compatibility domain (the
    aval digest already pins jax version/backend/topology). Idempotent;
    symmetric for serialize and deserialize, so both paths call it. Custom
    non-NamedTuple pytree nodes (if a system ever carries one) still fail
    registration-free and degrade to compile-from-source with the logged
    warning."""

    def _walk(node: Any) -> None:
        if isinstance(node, tuple) and hasattr(node, "_fields"):
            cls = type(node)
            if cls not in _registered_serializations:
                _registered_serializations.add(cls)
                try:
                    jax_export.register_namedtuple_serialization(
                        cls,
                        serialized_name=f"{cls.__module__}.{cls.__qualname__}",
                    )
                except ValueError:
                    pass  # already registered by an earlier caller/test
            for field in node:
                _walk(field)
        elif isinstance(node, (tuple, list)):
            for item in node:
                _walk(item)
        elif isinstance(node, dict):
            for item in node.values():
                _walk(item)

    _walk(tree)


def _register_signature(jit_fn: Callable, example_args: Tuple[Any, ...]) -> None:
    """Register NamedTuple serialization for the call's INPUT and OUTPUT
    trees (the output — e.g. ExperimentOutput — only exists abstractly, so
    it comes from eval_shape: a trace without the lowering the export store
    exists to skip). Needed symmetrically: serialize records the names,
    deserialize resolves them back to classes."""
    register_tree_serialization(example_args)
    try:
        register_tree_serialization(jax.eval_shape(jit_fn, *example_args))
    except Exception as exc:  # noqa: BLE001 — registration is best-effort; export will report
        get_logger("stoix_tpu.compilecache").warning(
            "[compilecache] could not abstract-trace outputs for serialization "
            "registration (%s: %s)", type(exc).__name__, exc,
        )


def save_exported(jit_fn: Callable, example_args: Tuple[Any, ...], path: str) -> bool:
    """Serialize the jitted callable for `example_args` to `path`; False (with
    a logged warning) when the function or backend is not exportable."""
    log = get_logger("stoix_tpu.compilecache")
    try:
        _register_signature(jit_fn, example_args)
        exported = jax_export.export(jit_fn)(*example_args)
        blob = exported.serialize()
    except Exception as exc:  # noqa: BLE001 — export is an optimization, not a dependency
        log.warning(
            "[compilecache] jax.export serialize failed (%s: %s) — peers will "
            "compile from source", type(exc).__name__, exc,
        )
        return False
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(blob)
    os.replace(tmp, path)  # atomic: a concurrent peer never reads a torn file
    log.info("[compilecache] exported learn function -> %s (%d bytes)", path, len(blob))
    return True


def load_exported(path: str) -> Optional[Callable]:
    """Deserialize an exported learn function; None (with a logged warning)
    when missing or unloadable — the caller then compiles from source."""
    log = get_logger("stoix_tpu.compilecache")
    if not os.path.exists(path):
        return None
    try:
        with open(path, "rb") as f:
            blob = f.read()
        exported = jax_export.deserialize(blob)
        return exported.call
    except Exception as exc:  # noqa: BLE001 — a stale/corrupt artifact degrades to recompile
        log.warning(
            "[compilecache] could not load AOT export %s (%s: %s) — compiling "
            "from source", path, type(exc).__name__, exc,
        )
        return None


def warmup_with_export(
    jit_fn: Callable,
    example_args: Tuple[Any, ...],
    export_dir: Optional[str],
    name: str,
) -> Tuple[Callable, Dict[str, Any]]:
    """AOT-warm the jitted callable, optionally through the `jax.export`
    store: with `export_dir` set, a matching serialized artifact is loaded
    (skipping trace+lower; the StableHLO→executable compile that remains can
    additionally hit the persistent cache), else the function is compiled and
    serialized for peers. Returns `(callable, info)` with info carrying
    `source` (export|compile), `export_path`, and `compile_s`.

    The exported path does NOT preserve donation (an Exported.call cannot
    donate operands), so it changes memory behavior, never values.
    """
    from stoix_tpu.utils.jax_utils import aot_warmup

    info: Dict[str, Any] = {"source": "compile", "export_path": None}
    start = time.perf_counter()
    if export_dir:
        path = export_artifact_path(export_dir, name, example_args)
        info["export_path"] = path
        if os.path.exists(path):
            # Deserialization resolves the serialized NamedTuple names back
            # to classes, so this process must register them first too.
            _register_signature(jit_fn, example_args)
        loaded = load_exported(path)
        if loaded is not None:
            compiled = aot_warmup(jax.jit(loaded), *example_args)
            info["source"] = "export"
            info["compile_s"] = time.perf_counter() - start
            get_logger("stoix_tpu.compilecache").info(
                "[compilecache] learn function restored from AOT export %s "
                "(%.2fs to executable)", path, info["compile_s"],
            )
            return compiled, info
    compiled = aot_warmup(jit_fn, *example_args)
    info["compile_s"] = time.perf_counter() - start
    if export_dir:
        save_exported(jit_fn, example_args, info["export_path"])
    return compiled, info
