"""The seven per-layer readers of set-up added with the set-up clock (PR 36):
each over a synthetic registry mark and a synthetic clock, and each returning
nothing on a program that publishes none of its series (the tree before
PR 36, whose gauge stops at `first_tick` and whose compile seconds nobody
counts by stage)."""

import types

import pytest

import _paths  # noqa: F401 — puts the checkout's root on sys.path
from benchmarks.harness import clock as clock_lib
from benchmarks.harness import loader

GAUGE = "stoix_tpu_setup_phase_seconds"
SECONDS = "stoix_tpu_compile_seconds_total"
RETRIEVAL = "stoix_tpu_compile_cache_retrieval_seconds_total"
CELL = "sebulba_ppo_cartpole_1chip"
NEW = (
    "setup_boot_s", "setup_import_s", "setup_launch_s", "setup_warmup_s",
    "setup_trace_lower_s", "setup_cache_load_s", "setup_unspanned_s",
)

phase = lambda name: (GAUGE, (("phase", name),), "value")
stage = lambda program, name: (SECONDS, (("program", program), ("stage", name)), "value")

PHASES = {
    "process_boot": 9.0, "imports": 12.0, "compose": 0.25, "launch": 5.0,
    "preflight": 0.0, "mesh_build": 0.5, "env_build": 0.125, "rng_key": 0.25,
    "network_init": 0.0, "learner_setup": 2.5, "state_warmup": 0.0, "restore": 0.0,
    "evaluator_setup": 0.5, "logger_build": 0.125, "aot_warmup": 5.5, "first_tick": 7.0,
    "unspanned": 0.25,
}
REGISTRY = {
    **{phase(name): seconds for name, seconds in PHASES.items()},
    stage("learner_fn", "trace"): 1.5, stage("learner_fn", "lower"): 0.75,
    stage("learner_fn", "backend"): 4.0, stage("add", "trace"): 0.125,
    stage("add", "lower"): 0.0625, stage("add", "backend"): 0.5,
    ("stoix_tpu_compiles_total", (("program", "add"),), "value"): 7.0,
    (RETRIEVAL, (), "value"): 3.25,
    ("stoix_tpu_other_seconds", (("phase", "imports"),), "value"): 99.0,
}
# Before PR 36: the gauge's five phases, the cache's events, and no more.
PARENT_REGISTRY = {
    phase("env_build"): 0.125, phase("learner_setup"): 2.5, phase("network_init"): 0.0,
    phase("evaluator_setup"): 0.0, phase("aot_warmup"): 0.0, phase("first_tick"): 7.0,
    ("stoix_tpu_compile_persistent_cache_events_total", (("event", "hit"),), "value"): 4.0,
}
# The process began at 100 s; the first tick at 143 s; set-up ended four
# warm-up ticks later, at 152.5 s: `setup_s` 52.5, of which 9.5 are ticks.
PROCESS_START, FIRST_TICK, SETUP_END = 100.0, 143.0, 152.5


def synthetic_ctx(registry, marks=2):
    clock = clock_lib.IntervalClock(30.0, 5, lambda: None, process_start=PROCESS_START)
    clock.ticks = [clock_lib.Tick(FIRST_TICK + 2.375 * i, 1000 * (i + 1)) for i in range(8)]
    clock.start, clock.start_index = SETUP_END, 4
    assert clock.ticks[4].time == SETUP_END
    ctx = types.SimpleNamespace(clock=clock)
    ctx.registry_marks = [(4 + i, SETUP_END + 2.375 * i, dict(registry)) for i in range(marks)]
    return ctx


def reader(name):
    return dict((entry["name"], read) for entry, read in loader.load_readers("per_layer", CELL))[name]


EXPECTED = {
    "setup_boot_s": 9.0,
    "setup_import_s": 12.0,
    "setup_launch_s": 5.0 + 0.25,
    "setup_warmup_s": 5.5 + 0.5,
    "setup_trace_lower_s": 1.5 + 0.75 + 0.125 + 0.0625,
    "setup_cache_load_s": 3.25,
    # 52.5 of set-up, less 43.0 of phases, less 9.5 of warm-up ticks.
    "setup_unspanned_s": (SETUP_END - PROCESS_START) - sum(PHASES.values()) - (SETUP_END - FIRST_TICK),
}


@pytest.mark.parametrize("name", NEW)
def test_setup_reader_reads_the_newest_mark(name):
    older = {key: 0.0 for key in REGISTRY}
    ctx = synthetic_ctx(REGISTRY)
    ctx.registry_marks[0] = (4, SETUP_END, older)
    assert reader(name)(ctx) == pytest.approx(EXPECTED[name], abs=1e-9)


@pytest.mark.parametrize("name", NEW)
def test_setup_reader_returns_nothing_where_the_program_publishes_nothing(name):
    read = reader(name)
    assert read(synthetic_ctx(REGISTRY, marks=0)) is None  # no mark was taken
    assert read(synthetic_ctx({})) is None  # a program without the series
    if name != "setup_warmup_s":  # its two phases are the parent's too (PR 23)
        assert read(synthetic_ctx(PARENT_REGISTRY)) is None


def test_the_phases_and_the_remainder_add_up_to_setup_s():
    """What the table is for: the seven numbers and the two old ones of the
    gauge, with the warm-up ticks, are `setup_s`."""
    ctx = synthetic_ctx(REGISTRY)
    parts = [reader(name)(ctx) for name in (
        "setup_boot_s", "setup_import_s", "setup_launch_s", "setup_warmup_s", "setup_build_s",
        "setup_first_tick_s", "setup_unspanned_s",
    )]
    rest = sum(PHASES[name] for name in ("mesh_build", "rng_key", "logger_build", "unspanned"))
    assert sum(parts) + rest + (SETUP_END - FIRST_TICK) == pytest.approx(ctx.clock.setup_s)


def test_unspanned_reader_needs_a_first_tick_and_an_end_of_setup():
    ctx = synthetic_ctx(REGISTRY)
    ctx.clock.start = None
    assert reader("setup_unspanned_s")(ctx) is None
    ctx = synthetic_ctx(REGISTRY)
    ctx.clock.ticks = []
    assert reader("setup_unspanned_s")(ctx) is None


def test_every_new_reader_is_an_entry_that_moves_setup_s_in_all_cells():
    bench = loader.load_benchmark()
    cells = [w["name"] for w in bench["workloads"]]
    entries = {e["name"]: e for e in bench["per_layer"]}
    for name in NEW:
        entry = entries[name]
        assert entry["moves"] == "setup_s" and entry["unit"] == "s" and entry["better"] == "lower"
        assert entry["workloads"] == cells
        assert entry["layer"] in ("Set-up", "Compile economy")
    assert [e["name"] for e in bench["per_layer"]][-len(NEW):] == list(NEW)  # appended, in order
