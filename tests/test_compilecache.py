"""Compile-economy gate (utils/compilecache.py, docs/DESIGN.md §2.7).

The core acceptance is cross-PROCESS: two cold subprocesses run the same tiny
jitted program against one cache dir named by JAX_COMPILATION_CACHE_DIR on
CPU — the second must record persistent-cache hits and no miss, and a
corrupted cache entry must degrade to a recompile, never a crash. Where the cache lives is decided in one place: the variable when set
(and then the program never writes `jax_compilation_cache_dir` itself), else
one fixed path inside the checkout.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT_CACHE = os.path.join(REPO, "xla_cache")

# The child turns the cache on through the REAL surface (composed config ->
# compilecache.configure) with the directory coming from the variable, and
# reports the recorded metrics: registry-backed hit/miss counts + its compile
# wall time. It also proves the "never writes the option" half of the
# contract by recording every jax.config.update key.
_CHILD_SCRIPT = """
import json, os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, jax.numpy as jnp
from stoix_tpu.utils import compilecache
from stoix_tpu.utils import config as config_lib

config = config_lib.compose(
    config_lib.default_config_dir(),
    "default/anakin/default_ff_ppo.yaml",
    [
        "arch.compile_cache.min_entry_size_bytes=-1",
        "arch.compile_cache.min_compile_time_secs=0",  # admit the tiny program
    ],
)
written = []
_update = jax.config.update
jax.config.update = lambda key, value: (written.append(key), _update(key, value))[1]
directory = compilecache.configure(config)
jax.config.update = _update
assert directory == os.environ["JAX_COMPILATION_CACHE_DIR"], directory
assert jax.config.jax_compilation_cache_dir == directory
assert "jax_compilation_cache_dir" not in written, written

@jax.jit
def program(x):
    return jnp.tanh(x) @ jnp.sin(x).T + jnp.cos(x).sum()

start = time.perf_counter()
program(jnp.ones((64, 64))).block_until_ready()
compile_s = time.perf_counter() - start
print(json.dumps({**compilecache.cache_stats(), "compile_s": compile_s}))
"""


def _run_child(cache_dir):
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD_SCRIPT],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=240,
        env={
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "JAX_COMPILATION_CACHE_DIR": str(cache_dir),
        },
    )
    assert proc.returncode == 0, f"cache child failed:\n{proc.stdout}\n{proc.stderr}"
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _checkout_cache_listing():
    return sorted(os.listdir(CHECKOUT_CACHE)) if os.path.isdir(CHECKOUT_CACHE) else []


def test_persistent_cache_roundtrip_across_cold_processes(tmp_path):
    cache_dir = tmp_path / "xla_cache"
    checkout_before = _checkout_cache_listing()
    first = _run_child(cache_dir)
    assert first["hits"] == 0 and first["misses"] >= 1, first
    entries = [p for p in os.listdir(cache_dir) if p.endswith("-cache")]
    assert entries, "first run wrote no cache entries"
    # With the variable set the entries land there and nowhere else: none of
    # them is new in the checkout's own cache (which the suite's other
    # workers may be writing to meanwhile).
    assert not set(entries) & (set(_checkout_cache_listing()) - set(checkout_before))

    # Every entry the first process wrote is found again: hits, and nothing
    # compiled anew. (Not a comparison of the two processes' compile seconds:
    # under the suite's workers that is a comparison of their neighbours.)
    second = _run_child(cache_dir)
    assert second["hits"] >= 1 and second["misses"] == 0, second

    # Corruption degrades to a recompile (jax_raise_persistent_cache_errors
    # stays False), not a crash: garbage every entry and run again.
    for entry in entries:
        with open(cache_dir / entry, "wb") as f:
            f.write(b"not a compiled executable")
    third = _run_child(cache_dir)
    assert third["compile_s"] > 0.0, third


def test_cache_dir_without_the_variable_is_one_fixed_checkout_path(
    tmp_path, monkeypatch
):
    from stoix_tpu.utils import compilecache

    monkeypatch.delenv(compilecache.CACHE_DIR_ENV, raising=False)
    seen = []
    for name in ("a", "b"):
        workdir = tmp_path / name
        workdir.mkdir()
        monkeypatch.chdir(workdir)
        seen.append(compilecache.cache_dir())
    # Identical from two working directories, absolute, inside the checkout.
    assert seen[0] == seen[1] == CHECKOUT_CACHE
    assert os.path.isabs(seen[0])

    monkeypatch.setenv(compilecache.CACHE_DIR_ENV, str(tmp_path / "elsewhere"))
    assert compilecache.cache_dir() == str(tmp_path / "elsewhere")


def test_settings_from_composed_config():
    from stoix_tpu.utils import compilecache
    from stoix_tpu.utils import config as config_lib

    config = config_lib.compose(
        config_lib.default_config_dir(),
        "default/anakin/default_ff_ppo.yaml",
        ["arch.compile_cache.min_compile_time_secs=2.5"],
    )
    settings = compilecache.settings_from_config(config)
    # Where the cache lives is not a config key; whether it is on is not a
    # question (always).
    assert set(settings) == {
        "min_entry_size_bytes", "min_compile_time_secs", "export_dir"
    }
    assert settings["min_compile_time_secs"] == 2.5
    assert settings["export_dir"] is None
    # Entry points without an arch config get the defaults.
    assert compilecache.settings_from_config(None) == {
        "min_entry_size_bytes": 0, "min_compile_time_secs": 1.0, "export_dir": None,
    }


def test_aot_export_roundtrip_plain_and_shard_map(tmp_path, devices):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from stoix_tpu.parallel import create_mesh
    from stoix_tpu.utils import compilecache

    mesh = create_mesh({"data": -1})
    fn = jax.jit(
        jax.shard_map(
            lambda x: jax.lax.pmean(x * 3.0, axis_name="data"),
            mesh=mesh,
            in_specs=(P("data"),),
            out_specs=P(),
        )
    )
    x = jax.device_put(
        jnp.arange(16, dtype=jnp.float32), NamedSharding(mesh, P("data"))
    )

    compiled, info = compilecache.warmup_with_export(fn, (x,), str(tmp_path), "learn")
    assert info["source"] == "compile"
    assert os.path.exists(info["export_path"]), info
    want = np.asarray(compiled(x))

    # Second launch (same avals/topology): served from the export store, with
    # identical values — including the shard_map collective.
    restored, info2 = compilecache.warmup_with_export(fn, (x,), str(tmp_path), "learn")
    assert info2["source"] == "export"
    np.testing.assert_allclose(np.asarray(restored(x)), want)

    # Different avals: a DIFFERENT artifact name — stale exports are never
    # loaded (invalidation by construction).
    y = jax.device_put(
        jnp.arange(32, dtype=jnp.float32), NamedSharding(mesh, P("data"))
    )
    _, info3 = compilecache.warmup_with_export(fn, (y,), str(tmp_path), "learn")
    assert info3["source"] == "compile"
    assert info3["export_path"] != info2["export_path"]

    # A corrupt artifact degrades to compile-from-source, never a crash.
    with open(info2["export_path"], "wb") as f:
        f.write(b"garbage")
    recompiled, info4 = compilecache.warmup_with_export(fn, (x,), str(tmp_path), "learn")
    assert info4["source"] == "compile"
    np.testing.assert_allclose(np.asarray(recompiled(x)), want)


def test_launcher_compile_cache_reaches_jobs_as_the_variable(tmp_path):
    from stoix_tpu import launcher

    script_dir = tmp_path / "scripts"
    launcher.main(
        [
            "--systems", "stoix_tpu.systems.ppo.anakin.ff_ppo",
            "--envs", "cartpole",
            "--compile-cache", "/shared/xla",
            "--aot-export", "/shared/aot",
            "--script-dir", str(script_dir),
            "--log-dir", str(tmp_path / "logs"),
        ]
    )
    scripts = list(script_dir.glob("*.sbatch"))
    assert len(scripts) == 1
    text = scripts[0].read_text()
    assert "export JAX_COMPILATION_CACHE_DIR=/shared/xla" in text
    assert "arch.compile_cache.export_dir=/shared/aot" in text
    # Neither `enabled` nor `dir` exists as a config key any more.
    assert "compile_cache.enabled" not in text
    assert "compile_cache.dir" not in text


def test_launcher_without_compile_cache_exports_no_variable(tmp_path):
    # The cache is always on: without the flag a job uses the variable it
    # inherits, else the fixed checkout path — the script sets nothing.
    from stoix_tpu import launcher

    script_dir = tmp_path / "scripts"
    launcher.main(
        [
            "--systems", "stoix_tpu.systems.ppo.anakin.ff_ppo",
            "--envs", "cartpole",
            "--aot-export", "/shared/aot",
            "--script-dir", str(script_dir),
            "--log-dir", str(tmp_path / "logs"),
        ]
    )
    text = next(script_dir.glob("*.sbatch")).read_text()
    assert "JAX_COMPILATION_CACHE_DIR" not in text
    assert "arch.compile_cache.export_dir=/shared/aot" in text


def test_aot_warmup_raises_a_compile_error_at_the_warmup_site():
    # A kernel the backend refuses must fail HERE, not be swallowed and
    # resurface inside the first timed window: a compiled (interpret=False)
    # Pallas TPU kernel cannot lower on the CPU backend.
    import jax
    import jax.numpy as jnp

    from stoix_tpu.ops.scan_kernels import pallas_linear_recurrence_reverse
    from stoix_tpu.utils.jax_utils import aot_warmup

    ones = jnp.ones((8, 128), jnp.float32)
    refused = jax.jit(lambda w, d, i: pallas_linear_recurrence_reverse(w, d, i))
    with pytest.raises(Exception, match="(?i)interpret|cpu"):
        aot_warmup(refused, ones, ones, ones[0])
    # A plain wrapper has nothing to compile ahead of time: returned as is.
    wrapper = lambda w, d, i: refused(w, d, i)
    assert aot_warmup(wrapper, ones, ones, ones[0]) is wrapper


# ---------------------------------------------------------------------------
# The compile stages by program (one jax.monitoring duration listener)
# ---------------------------------------------------------------------------

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"


def _stage_seconds(program):
    from stoix_tpu.observability import get_registry
    from stoix_tpu.utils import compilecache

    counter = get_registry().counter(compilecache.COMPILE_SECONDS_METRIC)
    return {
        stage: counter.value({"program": program, "stage": stage})
        for stage in ("trace", "lower", "backend")
    }


def _compiles(program):
    from stoix_tpu.observability import get_registry
    from stoix_tpu.utils import compilecache

    return get_registry().counter(compilecache.COMPILES_METRIC).value({"program": program})


@pytest.fixture
def fresh_stage_listener():
    """A listener with no names taken yet, beside the process's own: a worker
    that has run other tests has long used up its 64 names, and the probe
    programs below would be `other` to it."""
    import jax
    from jax._src import monitoring

    from stoix_tpu.utils import compilecache

    own = [
        listener for listener in monitoring.get_event_duration_listeners()
        if isinstance(listener, compilecache._CompileStages)
    ]
    for listener in own:  # or both would count every event
        monitoring.unregister_event_duration_listener(listener)
    listen = compilecache._CompileStages()
    jax.monitoring.register_event_duration_secs_listener(listen)
    yield listen
    monitoring.unregister_event_duration_listener(listen)
    for listener in own:
        jax.monitoring.register_event_duration_secs_listener(listener)


@pytest.mark.parametrize("stage", ["trace", "lower", "backend"])
def test_a_forced_recompile_is_attributed_to_its_program_and_stage(stage, fresh_stage_listener):
    """A named `jit` called with a second shape compiles again: one more
    compilation under its own `program` label, and seconds in every stage."""
    import jax
    import jax.numpy as jnp

    def recompiled_probe_program(x):
        return jnp.tanh(x) * 3.0

    probe = jax.jit(recompiled_probe_program)
    probe(jnp.ones(3)).block_until_ready()
    seconds, compiles = _stage_seconds("recompiled_probe_program"), _compiles("recompiled_probe_program")
    assert compiles >= 1.0 and seconds[stage] > 0.0
    probe(jnp.ones(3)).block_until_ready()  # same shape: nothing compiles, nothing moves
    assert _stage_seconds("recompiled_probe_program") == seconds
    probe(jnp.ones(5)).block_until_ready()  # the forced recompile
    assert _compiles("recompiled_probe_program") == compiles + 1.0
    assert _stage_seconds("recompiled_probe_program")[stage] > seconds[stage]


def test_a_function_traced_inside_another_is_the_outer_programs_tracing(fresh_stage_listener):
    """jax times a nested `jit`'s trace inside the outer trace's seconds: the
    inner name gets no series, and each second is counted once."""
    import time

    import jax
    import jax.numpy as jnp

    @jax.jit
    def nested_probe_inner(x):
        time.sleep(0.05)  # tracing time, inside the outer trace
        return jnp.cos(x)

    def nested_probe_outer(x):
        return nested_probe_inner(x) + 1.0

    started = time.perf_counter()
    jax.jit(nested_probe_outer)(jnp.ones(4)).block_until_ready()
    wall = time.perf_counter() - started
    outer, inner = _stage_seconds("nested_probe_outer"), _stage_seconds("nested_probe_inner")
    assert inner == {"trace": 0.0, "lower": 0.0, "backend": 0.0}
    assert 0.05 <= outer["trace"] and sum(outer.values()) <= wall


def test_compile_stage_listener_counts_each_second_once_and_bounds_its_labels(monkeypatch):
    """The listener on synthetic events: a stage that ended inside another
    (it began later, on the same thread) is taken off the outer's seconds; the
    first MAX_PROGRAM_LABELS names keep their own, as many again if a stage
    took a second or more, the rest are `other`; a cache read is its own
    counter."""
    from stoix_tpu.observability import get_registry
    from stoix_tpu.utils import compilecache

    import types

    monkeypatch.setattr(compilecache, "MAX_PROGRAM_LABELS", 2)
    now = [0.0]
    monkeypatch.setattr(compilecache, "time", types.SimpleNamespace(perf_counter=lambda: now[0]))
    listen = compilecache._CompileStages()

    def ended(at, event, seconds, fun_name):
        now[0] = at
        listen(event, seconds, fun_name=fun_name)

    # A trace from 100 to 110 s that met an eager op at 102 s: lowered in a
    # quarter of a second, compiled in half a second, inside the trace.
    ended(102.25, LOWER_EVENT, 0.25, "jit(labels_eager_op)")
    ended(102.75, BACKEND_EVENT, 0.5, "jit(labels_eager_op)")
    ended(110.0, TRACE_EVENT, 10.0, "labels_outer")
    assert _stage_seconds("labels_eager_op") == {"trace": 0.0, "lower": 0.25, "backend": 0.5}
    assert _stage_seconds("labels_outer")["trace"] == pytest.approx(10.0 - 0.75)
    assert _compiles("labels_eager_op") == 1.0 and _compiles("labels_outer") == 0.0
    # Its own lowering and compilation follow the trace, and are whole.
    ended(111.0, LOWER_EVENT, 1.0, "jit(labels_outer)")
    ended(115.0, BACKEND_EVENT, 4.0, "jit(labels_outer)")
    assert _stage_seconds("labels_outer") == {
        "trace": pytest.approx(9.25), "lower": 1.0, "backend": 4.0
    }
    # Two names are in: a third is `other`, unless a stage took a second.
    other = _stage_seconds(compilecache.OTHER_PROGRAM)
    ended(120.0, BACKEND_EVENT, 0.125, "jit(labels_third_small)")
    assert _stage_seconds("labels_third_small")["backend"] == 0.0
    assert _stage_seconds(compilecache.OTHER_PROGRAM)["backend"] == other["backend"] + 0.125
    ended(130.0, BACKEND_EVENT, 2.0, "jit(labels_third_large)")
    ended(140.0, BACKEND_EVENT, 2.0, "jit(labels_fourth_large)")
    ended(150.0, BACKEND_EVENT, 2.0, "jit(labels_fifth_large)")  # beyond twice the bound
    assert _stage_seconds("labels_third_large")["backend"] == 2.0
    assert _stage_seconds("labels_fourth_large")["backend"] == 2.0
    assert _stage_seconds("labels_fifth_large")["backend"] == 0.0
    assert _compiles(compilecache.OTHER_PROGRAM) >= 2.0
    retrieval = get_registry().counter(compilecache.RETRIEVAL_SECONDS_METRIC)
    before = retrieval.value()
    listen("/jax/compilation_cache/cache_retrieval_time_sec", 1.5)
    listen("/jax/some/other/duration", 99.0, fun_name="ignored")
    assert retrieval.value() == before + 1.5


def test_the_two_series_the_stage_counters_replaced_are_gone():
    """`stoix_tpu_compile_entry_seconds` and
    `stoix_tpu_runner_compile_seconds_total` recorded one span's seconds and
    had no reader: no source file of the package names them any more."""
    root = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "stoix_tpu")
    gone = ("stoix_tpu_compile_entry_seconds", "stoix_tpu_runner_compile_seconds_total")
    for folder, _, files in os.walk(root):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(folder, name), encoding="utf-8") as handle:
                    text = handle.read()
                assert not any(series in text for series in gone), os.path.join(folder, name)
