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
