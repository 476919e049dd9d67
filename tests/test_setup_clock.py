"""The set-up clock adds up (docs/DESIGN.md §2.2, ISSUE 36): in a process
launched as an operator launches one, the phases of
`stoix_tpu_setup_phase_seconds` partition the wall from the OS's start of the
process to the first completed window (Anakin) or update (Sebulba),
`unspanned` is what no span covered, a second run in the same process shows
no stale launch phases, and set-up is the goodput ledger's `setup`, not its
`compute`. Each architecture is one child process (tests/setup_clock_child.py,
two runs).

The checkpoint library loads where a checkpointer is built (§2.2, ISSUE 37):
the same children say at each moment whether orbax is in `sys.modules` and
what `stoix_tpu_checkpoint_library_import_seconds` reads, and a third child,
`anakin_saving`, runs with checkpointing on: it saves, then restores."""

import json
import os
import subprocess
import sys
import time

import pytest

from stoix_tpu.observability import SetupClock, goodput

CHILD = os.path.join(os.path.dirname(os.path.abspath(__file__)), "setup_clock_child.py")
LAUNCH = set(SetupClock.LAUNCH_PHASES)
OWN = {
    "anakin": {
        "mesh_build", "env_build", "rng_key", "learner_setup", "evaluator_setup",
        "logger_build", "aot_warmup", "first_tick", "unspanned",
    },
    "sebulba": {
        "mesh_build", "env_build", "network_init", "learner_setup", "evaluator_setup",
        "logger_build", "first_tick", "unspanned",
    },
}


def _launch(architecture, cwd=None):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    spawned = time.time()
    done = subprocess.run(
        [sys.executable, CHILD, architecture], env=env, capture_output=True, text=True,
        timeout=600, cwd=cwd,
    )
    assert done.returncode == 0, done.stderr[-4000:]
    report = json.loads(done.stdout.strip().splitlines()[-1])
    report["spawned_epoch"] = spawned
    return report


@pytest.fixture(scope="module")
def anakin_child():
    return _launch("anakin")


@pytest.fixture(scope="module")
def sebulba_child():
    return _launch("sebulba")


@pytest.fixture(scope="module")
def anakin_saving_child(tmp_path_factory):
    """Saves under its working directory: a scratch one."""
    return _launch("anakin_saving", cwd=str(tmp_path_factory.mktemp("saving_child")))


@pytest.fixture(params=["anakin", "sebulba"])
def child(request):
    return request.param, request.getfixturevalue(f"{request.param}_child")


def _own_phases_partition_the_wall(run):
    """A run's own phases (the launch phases apart) cover 98% of the wall from
    `run_experiment`'s entry to the first tick; `unspanned` is the rest."""
    own = {p: s for p, s in run["phases"].items() if p not in LAUNCH}
    spanned = sum(s for p, s in own.items() if p != "unspanned")
    wall = run["entry_to_first_tick_s"]
    assert spanned >= 0.98 * wall, (spanned, wall, own)
    assert own["unspanned"] == pytest.approx(wall - spanned, abs=0.02)
    assert 0.0 < own["unspanned"] < 0.5
    return own


def test_first_run_partitions_the_wall_from_process_start_to_first_tick(child):
    """From outside: the parent's clock before the spawn to the child's clock
    at the close of `first_tick`. The gauge's phases may not exceed it, and
    cover 98% of it."""
    _, report = child
    first = report["runs"][0]
    wall = first["first_tick_epoch"] - report["spawned_epoch"]
    covered = sum(first["phases"].values())
    assert LAUNCH <= set(first["phases"]), first["phases"]
    assert 0.98 * wall <= covered <= wall + 0.05, (covered, wall, first["phases"])


def test_first_run_launch_phases_are_what_passed_before_run_experiment(child):
    architecture, report = child
    first = report["runs"][0]
    phases = first["phases"]
    assert first["stats_launch_phases"] == {p: phases[p] for p in LAUNCH}
    # The Anakin runner's import block pulls in jax, flax, the envs (not orbax,
    # since PR 37): seconds. A Sebulba system module has imported most of that
    # itself before it reaches the runner's block, and those seconds are
    # `launch`'s.
    assert phases["imports"] > (0.5 if architecture == "anakin" else 0.0)
    assert phases["process_boot"] > 0.0
    assert 0.0 < phases["compose"] < 1.0
    assert phases["launch"] >= 0.0


def test_each_run_names_its_own_phases_and_unspanned_is_what_is_left(child):
    architecture, report = child
    for run in report["runs"]:
        own = _own_phases_partition_the_wall(run)
        # Every phase of the table is a series; the ones this run closed are > 0.
        assert set(own) == set(SetupClock.PHASES)
        assert {p for p, s in own.items() if s > 0.0} == OWN[architecture], own
        assert {p: s for p, s in own.items() if s > 0.0} == pytest.approx(
            run["stats_setup_phases"], abs=1e-5
        )


def test_second_run_in_one_process_publishes_no_stale_launch_phases(child):
    _, report = child
    second = report["runs"][1]
    assert not LAUNCH & set(second["phases"]), second["phases"]
    assert second["stats_launch_phases"] is None
    assert second["backend_up_at_entry"] == 1.0  # the first run started it
    assert report["runs"][0]["backend_up_at_entry"] in (0.0, 1.0)


def test_set_up_is_the_ledgers_setup_and_not_goodput(child):
    """The clock's wall goes to `setup`, less the warm-up's `compile`; the
    fractions still sum to one over the ten phases."""
    architecture, report = child
    for run in report["runs"]:
        ledger, phases = run["goodput"], run["phases"]
        assert set(ledger["fractions"]) == set(goodput.PHASES)
        assert sum(ledger["fractions"].values()) == pytest.approx(1.0, abs=1e-6)
        own_wall = sum(s for p, s in phases.items() if p not in LAUNCH)
        booked = ledger["seconds"]["setup"] + ledger["seconds"]["compile"]
        assert booked == pytest.approx(own_wall, abs=0.02), (ledger["seconds"], phases)
        assert ledger["seconds"]["compile"] == pytest.approx(phases["aot_warmup"], abs=1e-4)
        # What is left for the other phases is steady state's, and shorter.
        assert ledger["seconds"]["compute"] < ledger["wall_s"] - own_wall + 0.02


def test_a_steady_state_recompile_is_named(child):
    """After set-up (two whole runs), a `jit` that compiles shows under its own
    name in `stoix_tpu_compiles_total`: what `correct` could not say when it
    found a compilation inside the interval."""
    _, report = child
    assert report["steady_state_recompiles"] == [0.0, 1.0]


@pytest.mark.parametrize(
    "architecture, moment, loaded",
    [
        ("anakin", "runner_imported", False),
        ("anakin", "run_0", False),
        ("anakin", "run_1", False),
        ("sebulba", "run_1", False),
        ("anakin_saving", "runner_imported", False),
        ("anakin_saving", "run_0", True),
        ("anakin_saving", "run_1", True),
    ],
)
def test_the_checkpoint_library_is_loaded_only_by_a_run_that_checkpoints(
    request, architecture, moment, loaded
):
    """After `import stoix_tpu.systems.runner` and after whole runs with
    checkpointing off, neither orbax nor a `google.cloud` module is loaded and
    the gauge is absent; a run that saves loads it, once (the restoring run
    after it reads the same seconds)."""
    report = request.getfixturevalue(f"{architecture}_child")
    at = {m["moment"]: m for m in report["moments"]}[moment]
    assert at["orbax"] is loaded, at
    assert loaded or not at["google_cloud"], at  # it comes with orbax or not at all
    assert (at["import_seconds"] is not None) is loaded, at
    if loaded:
        assert at["import_seconds"] > 0.0
        assert at["import_seconds"] == report["moments"][-1]["import_seconds"]


def test_a_saving_run_pays_the_import_in_logger_build_and_its_phases_add_up(
    anakin_saving_child,
):
    """The seconds moved from `imports` (over before orbax was loaded: the
    `runner_imported` case above) to the phase that builds the checkpointer;
    the table of the run that saves, and of the one that restores, still
    partitions its wall."""
    report = anakin_saving_child
    saving, restoring = report["runs"]
    assert report["saved_steps"], report["saved_steps"]
    imported = report["moments"][-1]["import_seconds"]
    assert saving["phases"]["logger_build"] >= imported > 0.0
    assert restoring["phases"]["restore"] > 0.0
    assert restoring["phases"]["logger_build"] < imported
    for run in report["runs"]:
        _own_phases_partition_the_wall(run)
