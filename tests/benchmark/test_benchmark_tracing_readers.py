"""The per-layer readers that read what the program's one span primitive and
its scope table emit (PR 23), each on synthetic events or a stub context, as
test_benchmark_trace_reduce.py does for the reductions; and the join itself,
on a two-thread trace recorded on the chip through the program's own `span`
(tests/benchmark/record_threads_fixture.py; data/fixture_threads_1chip.xplane.pb)."""

import os
import types

import pytest

import _paths
from benchmarks.harness import loader, program_reads, xplane_proto
from benchmarks.harness import trace_reduce as tr
from benchmarks.harness.trace_reduce import Event
from stoix_tpu.observability import HOST_SPANS, SCOPES

D0, D1 = "/device:TPU:0", "/device:TPU:1"
CELLS = {
    "anakin": "anakin_ppo_ant_1chip", "anakin4": "anakin_ppo_ant_4chip",
    "sebulba": "sebulba_ppo_cartpole_1chip",
}


def op(plane, name, start, dur, path):
    stats = {"tf_op": path, "program": "jit_learner_fn"}
    return Event(plane, tr.OPS_LINE, f"%{name} = f32[8]{{0}} thing()", start, dur, stats)


def module(plane, start, dur):
    return Event(plane, tr.MODULES_LINE, "jit_learner_fn(7)", start, dur, {})


def reader(name, cell=CELLS["anakin"]):
    return dict(
        (entry["name"], read) for entry, read in loader.load_readers("per_layer", cell)
    )[name]


def learner_trace():
    """Two chips, on each three executions of a 1000 ps learner; only the
    middle one lies whole inside the traced window. In it: rollout 400 ps
    (policy 100, env 300), gae 100 (written `vmap(gae)`, as JAX writes a
    scope entered directly under vmap), two epochs of 250 with a shuffle of
    150 each. Chip 1's middle execution is all rollout_env."""
    base = "jit(learner_fn)/while/body/closed_call"
    sgd = f"{base}/ppo_epoch/ppo_minibatch/transpose(jvp(torso))"
    events = []
    for start in (0, 2000, 4000):
        events += [module(D0, start, 1000), module(D1, start, 1000)]
        events += [op(D1, "fusion.9", start, 1000, f"{base}/rollout/rollout_env/mul")]
        events += [
            op(D0, "fusion.1", start, 100, f"{base}/rollout/rollout_policy/dot_general"),
            op(D0, "fusion.2", start + 100, 300, f"{base}/rollout/rollout_env/mul"),
            op(D0, "fusion.3", start + 400, 100, f"{base}/vmap(gae)/while/body/add"),
            op(D0, "sort.4", start + 500, 50, f"{base}/ppo_epoch/minibatch_shuffle/sort"),
            op(D0, "gather.5", start + 550, 100, f"{base}/ppo_epoch/minibatch_shuffle/gather"),
            op(D0, "fusion.6", start + 650, 100, f"{sgd}/dot_general"),
            op(D0, "sort.4", start + 750, 50, f"{base}/ppo_epoch/minibatch_shuffle/sort"),
            op(D0, "gather.5", start + 800, 100, f"{base}/ppo_epoch/minibatch_shuffle/gather"),
            op(D0, "fusion.6", start + 900, 100, f"{sgd}/dot_general"),
        ]
    return tr.Trace.from_events(events)


def trace_ctx(trace=None, cell=CELLS["anakin"]):
    return types.SimpleNamespace(
        cell=loader.load_cell(cell), trace_data=learner_trace() if trace is None else trace
    )


# Mean over the two chips of the middle execution: chip 0 as described, chip
# 1 all rollout_env.
@pytest.mark.parametrize("name,expected", [
    ("rollout_share", (40.0 + 100.0) / 2),
    ("rollout_env_share", (30.0 + 100.0) / 2),
    ("gae_share", (10.0 + 0.0) / 2),
    ("shuffle_share", (30.0 + 0.0) / 2),
])
def test_scope_share_readers_read_the_programs_name_table(name, expected):
    assert reader(name)(trace_ctx()) == pytest.approx(expected)
    # The old reader beside them is the same arithmetic for the config's scope.
    assert reader("update_share")(trace_ctx()) == pytest.approx((50.0 + 0.0) / 2)
    # Nothing to read: an untraced run, or a trace that names no path at all.
    untraced = types.SimpleNamespace(cell=loader.load_cell(CELLS["anakin"]), trace_data=None)
    assert reader(name)(untraced) is None
    bare = tr.Trace.from_events([module(D0, 0, 10), op(D0, "fusion.1", 0, 10, "")])
    assert reader(name)(trace_ctx(bare)) is None


def test_scope_names_come_from_the_program_not_from_the_config():
    assert program_reads.program_scope("minibatch_shuffle") == SCOPES["minibatch_shuffle"]
    assert program_reads.program_scope("no_such_scope") is None
    assert SCOPES["update_epoch"] == loader.load_cell(CELLS["anakin"]).config["scopes"]["update"]
    # A scope the trace lacks reads 0.0, not nothing: hence the `workloads`
    # lists that keep rollout, rollout_env and gae out of the Sebulba cell.
    sebulba = tr.Trace.from_events([
        module(D0, 0, 100), module(D0, 200, 100), module(D0, 400, 100),
        op(D0, "gather.5", 200, 60, "jit(per_shard)/while/body/ppo_epoch/minibatch_shuffle/gather"),
        op(D0, "fusion.6", 260, 40, "jit(per_shard)/while/body/ppo_epoch/ppo_minibatch/dot"),
        op(D0, "fusion.6", 0, 100, ""), op(D0, "fusion.6", 400, 100, ""),
    ])
    ctx = trace_ctx(sebulba, CELLS["sebulba"])
    ctx.cell.config["programs"]["learn"] = ["learner_fn"]  # the synthetic module's name
    assert reader("shuffle_share", CELLS["sebulba"])(ctx) == pytest.approx(60.0)
    assert program_reads.learner_scope_share(ctx, "rollout") == 0.0
    per_layer = {m["name"]: m for m in loader.load_benchmark()["per_layer"]}
    for name in ("rollout_share", "rollout_env_share", "gae_share"):
        assert per_layer[name]["workloads"] == [CELLS["anakin"], CELLS["anakin4"]]
    assert CELLS["sebulba"] in per_layer["shuffle_share"]["workloads"]


def misc_ctx():
    clock = types.SimpleNamespace(start=100.0, seconds=30.0)
    misc = [
        (90.0, {"actor0_inference_time": 9.0, "actor0_env_step_time": 9.0}),  # set-up
        (110.0, {"actor0_inference_time": 0.040, "actor1_inference_time": 0.044,
                 "actor0_env_step_time": 0.010, "actor1_env_step_time": 0.012,
                 "actor0_rollout_time": 3.2, "learner_learn_time": 3.5}),
        (120.0, {"actor0_inference_time": 0.042, "actor1_inference_time": 0.042,
                 "actor0_env_step_time": 0.011, "actor1_env_step_time": 0.011}),
        (140.0, {"actor0_inference_time": 7.0}),  # after the interval
    ]
    return types.SimpleNamespace(clock=clock, misc=misc, shapes={"rollout_length": 64})


@pytest.mark.parametrize("name,expected_ms", [
    ("sebulba_actor_inference_ms", 42.0), ("sebulba_actor_env_step_ms", 11.0),
])
def test_actor_step_split_readers_average_the_misc_means_inside_the_interval(name, expected_ms):
    read = reader(name, CELLS["sebulba"])
    assert read(misc_ctx()) == pytest.approx(expected_ms)
    empty = misc_ctx()
    empty.misc = []
    assert read(empty) is None
    # The outside timer they split reads the same events.
    assert reader("sebulba_actor_step_ms", CELLS["sebulba"])(misc_ctx()) == pytest.approx(50.0)


def registry_ctx(marks):
    ctx = types.SimpleNamespace(registry_marks=marks)
    ctx.registry_span = lambda: (
        None if len(marks) < 2 else (marks[0][2], marks[-1][2], marks[-1][1] - marks[0][1])
    )
    return ctx


def test_policy_lag_reader_is_the_mean_over_the_rollouts_consumed_in_the_interval():
    lag = "stoix_tpu_sebulba_policy_lag_updates"
    before = {(lag, (), "sum"): 9.0, (lag, (), "count"): 10.0}
    after = {(lag, (), "sum"): 9.0 + 11.0, (lag, (), "count"): 10.0 + 12.0}
    read = reader("sebulba_policy_lag_updates", CELLS["sebulba"])
    assert read(registry_ctx([(5, 100.0, before), (11, 130.0, after)])) == pytest.approx(11 / 12)
    assert read(registry_ctx([(5, 100.0, before)])) is None  # no whole update
    assert read(registry_ctx([(5, 100.0, before), (6, 105.0, before)])) is None  # none consumed
    assert read(registry_ctx([(5, 100.0, {}), (6, 105.0, {})])) is None  # no such histogram


@pytest.mark.parametrize(
    "name,expected", [("setup_build_s", 1.5 + 6.0), ("setup_first_tick_s", 9.0)]
)
def test_setup_readers_read_the_setup_gauge_of_the_newest_mark(name, expected):
    gauge = "stoix_tpu_setup_phase_seconds"
    phase = lambda p: (gauge, (("phase", p),), "value")
    registry = {
        phase("env_build"): 1.5, phase("learner_setup"): 6.0, phase("network_init"): 0.0,
        phase("evaluator_setup"): 0.25, phase("aot_warmup"): 20.0, phase("first_tick"): 9.0,
        ("stoix_tpu_other_seconds", (("phase", "env_build"),), "value"): 99.0,
    }
    read = reader(name)
    assert read(registry_ctx([(1, 50.0, {}), (2, 55.0, registry)])) == pytest.approx(expected)
    assert read(registry_ctx([])) is None
    assert read(registry_ctx([(1, 50.0, {})])) is None  # a program without the gauge


# --------------------------------------------------------------------------
# The join, on the trace recorded on the chip
# --------------------------------------------------------------------------

THREADS_FIXTURE = os.path.join(_paths.DATA, "fixture_threads_1chip.xplane.pb")
ALL_SPANS = sorted({name for names in HOST_SPANS.values() for name in names})


@pytest.fixture(scope="module")
def threads_trace():
    return tr.read_xplane(THREADS_FIXTURE, host_names=ALL_SPANS)


def test_idle_gaps_of_the_two_thread_trace_are_attributed_to_span_names(threads_trace):
    """Host spans of both threads are on the device ops' clock: the device
    goes idle inside each of the actor's 4 ms sleeps, and the gap is named
    by the span the sleep ran under, not `unattributed`."""
    from record_threads_fixture import ROLLOUTS, SLEEP_S, STEPS

    gaps = tr.longest_idle_gaps(threads_trace, ALL_SPANS, ROLLOUTS * STEPS)
    sleeps = [(label, seconds) for label, seconds in gaps if seconds > SLEEP_S / 2]
    assert len(sleeps) == ROLLOUTS * STEPS and all(s < 3 * SLEEP_S for _, s in sleeps), gaps
    labels = [label for label, _ in sleeps]
    # All but the very first: that gap begins 0.19 ms into the profile, before
    # the first span reads as open (the two clocks agree to a millisecond or
    # two, below).
    assert labels.count("actor_env_step") == ROLLOUTS * STEPS - 1, gaps
    assert set(labels) <= {"actor_env_step", "unattributed"}, gaps
    # With no names to look for, the same gaps read as they do in the ledger.
    assert {label for label, _ in tr.longest_idle_gaps(threads_trace, [], 4)} == {"unattributed"}


def test_host_and_device_lines_agree_to_a_couple_of_milliseconds(threads_trace):
    """How well the one clock holds: each `learner_update` span dispatched
    one `per_shard` execution and returned after it ended, so the execution
    should lie inside its span. On this recording the device ops read 1.0 to
    1.7 ms EARLIER than that: attribution of gaps shorter than a couple of
    milliseconds is not to be trusted (PERF.md §7)."""
    updates = sorted(
        (e.start_ps, e.start_ps + e.dur_ps) for e in threads_trace.host
        if e.name == "learner_update"
    )
    plane = threads_trace.planes[0]
    executions = sorted(
        (start, end) for name, start, end in threads_trace.modules[plane]
        if "per_shard" in name
    )
    assert len(updates) == len(executions) == 3
    for (span_start, span_end), (start, end) in zip(updates, executions):
        early_ms = (span_start - start) / 1e9
        assert end <= span_end and 0.0 < early_ms < 3.0, early_ms


def test_each_threads_spans_lie_on_its_own_host_line():
    with open(THREADS_FIXTURE, "rb") as handle:
        space = xplane_proto.parse("XSpace", handle.read())
    lines = {}
    for plane in space.planes:
        if not plane.name.startswith(tr.HOST_PLANE_PREFIX):
            continue
        names = {entry.key: entry.value.name for entry in plane.event_metadata}
        for line in plane.lines:
            seen = {names[ev.metadata_id] for ev in line.events} & set(ALL_SPANS)
            if seen:
                lines[(plane.name, line.id)] = seen
    actor = {"actor_rollout", "actor_inference", "actor_env_step", "pipeline_put"}
    learner = {"learner_rollout_wait", "learner_update"}
    assert sorted(lines.values(), key=sorted) == sorted([actor, learner], key=sorted), lines


# --------------------------------------------------------------------------
# The readers on a trace the profiler damaged (tests/benchmark/_loop_trace.py)
# --------------------------------------------------------------------------

from _loop_trace import D0 as L0, D1 as L1, loop_trace  # noqa: E402

WINDOW_SHARES = ["eval_device_share", "learn_device_share", "device_idle_share"]
SCOPE_SHARES = ["update_share", "rollout_share", "rollout_env_share", "gae_share", "shuffle_share"]


def loop_ctx(*args, cell=CELLS["anakin"], **kwargs):
    ctx = trace_ctx(loop_trace(*args, **kwargs), cell)
    ctx.shapes = {"update_cost": {"flops": 1.0e3, "bytes": 1.0}, "updates_per_tick": 1}
    ctx.device = {"kind": "TPU v5 lite"}
    return ctx


@pytest.mark.parametrize("name", WINDOW_SHARES + SCOPE_SHARES + ["update_roofline_share"])
@pytest.mark.parametrize("windows,lost,planes", [
    (3, "first", (L0,)), (3, "first", (L0, L1)), (3, "last", (L0,)), (2, "first", (L0,)), (2, "last", (L0,)),
])
def test_readers_read_a_damaged_twin_as_they_read_the_whole_trace(name, windows, lost, planes):
    """What the driver reads of a run whose profiler lost a boundary. The
    learner's shares are the undamaged trace's wherever a whole execution is
    left. The window's shares: with the last boundary lost, those of a
    session that ended with the evaluator (310 ps earlier); with a hole
    INSIDE the window they are left out, never printed as if right (the
    arithmetic under them still gives the whole trace's shares on this
    synthetic loop, test_benchmark_trace_reduce.py; on the chip a hole took
    one evaluation of two and halved `eval_device_share`, PR 27)."""
    read = reader(name)
    whole, twin = read(loop_ctx(windows, None, planes)), read(loop_ctx(windows, lost, planes))
    assert whole is not None and 0.0 < whole <= 100.0
    if name in WINDOW_SHARES:
        if lost == "first":
            assert twin is None
        else:
            window = 700 + windows * 1100 - 1000 - 10
            expected = {
                "eval_device_share": (100 * windows - 10) / window,
                "learn_device_share": (1000 * windows - 300) / window,
                "device_idle_share": (60 * windows - 10) / window,
            }[name]
            assert twin == pytest.approx(100.0 * expected, rel=1e-12)
    elif windows == 2 and lost == "first":
        assert twin is None  # the one whole execution is the one that was lost
    else:
        assert twin == pytest.approx(whole, rel=1e-12)


@pytest.mark.parametrize("name", WINDOW_SHARES + SCOPE_SHARES + ["update_roofline_share"])
def test_readers_on_four_chips_of_which_one_lost_its_first_boundary(name):
    """One chip of four damaged (the fast program's usual four-chip session,
    my chip runs, PR 27): every chip stays in the trace; the learner's shares
    are read on the three chips that hold a whole execution and are the whole
    trace's; the window's shares are left out."""
    planes = tuple(f"/device:TPU:{i}" for i in range(4))
    read = reader(name)
    whole = read(loop_ctx(2, None, planes))
    twin = read(loop_ctx(2, "first", planes, damaged_planes=planes[2:3]))
    assert whole is not None
    if name in WINDOW_SHARES:
        assert twin is None
    else:
        assert twin == pytest.approx(whole, rel=1e-12)


def test_the_four_chip_readers_count_collectives_over_readable_time():
    """`collective_calls_per_update` on a twin whose every chip lost the first
    boundary: the all-reduce of the lost execution is unnamed and is not
    counted, and neither is its time. `collective_exposed_share` is a share
    of the window: left out with a hole inside it, and with the last boundary
    lost that of the shorter session."""
    from _loop_trace import loop_events

    def with_all_reduce(lost):
        events = []
        for e in loop_events(3, lost, (L0, L1)):
            # The SGD fusion of every named learner execution becomes an all-reduce.
            if e.line == tr.OPS_LINE and "ppo_minibatch" in e.stats.get("tf_op", ""):
                e = e._replace(name="%all-reduce.6 = f32[8]{0} thing()")
            events.append(e)
        ctx = trace_ctx(tr.Trace.from_events(events), CELLS["anakin4"])
        ctx.shapes = {"updates_per_tick": 1}
        return ctx

    calls = reader("collective_calls_per_update", CELLS["anakin4"])
    whole = calls(with_all_reduce(None))
    assert whole is not None and calls(with_all_reduce("first")) == pytest.approx(whole, rel=1e-12)
    exposed = reader("collective_exposed_share", CELLS["anakin4"])
    assert exposed(with_all_reduce(None)) == pytest.approx(100.0 * 600 / 3300, rel=1e-12)
    assert exposed(with_all_reduce("first")) is None
    # Last boundary lost: the learner's head held no all-reduce, the window is 310 ps shorter.
    assert exposed(with_all_reduce("last")) == pytest.approx(100.0 * 600 / (3300 - 310), rel=1e-12)
