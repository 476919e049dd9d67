"""The trace reduction: interval arithmetic and every reduction on synthetic
(plane, line, event) records, then the reading of a `.xplane.pb` recorded on
the chip (tests/benchmark/record_fixture.py; data/*.xplane.pb)."""

import os

import pytest

import _paths
from benchmarks.harness import trace_reduce as tr
from benchmarks.harness.trace_reduce import Event

D0, D1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"


def op(plane, name, start, dur, path="", category=""):
    stats = {"tf_op": path, "hlo_category": category, "program": "jit_learner_fn"}
    return Event(plane, tr.OPS_LINE, f"%{name} = f32[8]{{0}} thing()", start, dur, stats)


def module(plane, name, start, dur):
    return Event(plane, tr.MODULES_LINE, name, start, dur, {})


def host(name, start, dur):
    return Event(HOST, "python3", name, start, dur, {})


def _total(intervals):
    return sum(end - start for start, end in tr.merge(intervals))


def _subtract(a, b):
    """The parts of union(a) not covered by union(b): the plain list
    arithmetic the array functions are held to."""
    out, cover = [], tr.merge(b)
    for start, end in tr.merge(a):
        cursor = start
        for c_start, c_end in cover:
            if c_end <= cursor or c_start >= end:
                continue
            if c_start > cursor:
                out.append((cursor, c_start))
            cursor = max(cursor, c_end)
        if cursor < end:
            out.append((cursor, end))
    return out


def test_array_union_and_overlap_agree_with_the_list_arithmetic():
    import numpy as np

    rng = np.random.default_rng(0)
    for _ in range(20):
        a = [(int(s), int(s + d)) for s, d in zip(rng.integers(0, 1000, 30), rng.integers(0, 40, 30))]
        b = [(int(s), int(s + d)) for s, d in zip(rng.integers(0, 1000, 30), rng.integers(0, 40, 30))]
        arr = lambda xs: (np.asarray([x[0] for x in xs], np.int64), np.asarray([x[1] for x in xs], np.int64))
        ma, mb = tr.merged_arrays(*arr(a)), tr.merged_arrays(*arr(b))
        assert list(zip(ma[0].tolist(), ma[1].tolist())) == tr.merge(a)
        assert tr.overlap(ma, mb) == _total(a) - sum(e - s for s, e in _subtract(a, b))


def test_merge_and_the_list_reference():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [(0, 3), (5, 8)]
    assert _total([(0, 10), (5, 15), (20, 21)]) == 16
    assert _subtract([(0, 10)], [(2, 3), (5, 7), (9, 12)]) == [(0, 2), (3, 5), (7, 9)]
    assert _subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert _subtract([(0, 4)], []) == [(0, 4)]


def _two_chip_trace(extra=()):
    """Chip 0: ops cover [0,400)+[600,1000) ps; chip 1: [0,1000) whole. A
    `while` op contains the learner's ops on each chip, as in a real trace."""
    return tr.Trace.from_events(list(extra) + [
        op(D0, "while.1", 0, 400, "jit(learner_fn)/while"), op(D1, "while.1", 0, 700, "jit(learner_fn)/while"),
        module(D0, "jit_learner_fn(11)", 0, 400), module(D0, "jit__shard_eval(12)", 600, 400),
        module(D1, "jit_learner_fn(11)", 0, 700), module(D1, "jit__shard_eval(12)", 700, 300),
        op(D0, "fusion.1", 0, 100, "jit(learner_fn)/rollout/mul"),
        op(D0, "fusion.2", 100, 200, "jit(learner_fn)/ppo_epoch/ppo_minibatch/dot_general"),
        op(D0, "all-reduce.3", 300, 100, "jit(learner_fn)/ppo_epoch/ppo_minibatch/pmean"),
        op(D0, "fusion.9", 600, 400, "jit(_shard_eval)/while/body/tanh"),
        op(D1, "fusion.1", 0, 300, "jit(learner_fn)/rollout/mul"),
        op(D1, "fusion.2", 300, 300, "jit(learner_fn)/ppo_epoch/ppo_minibatch/dot_general"),
        op(D1, "all-reduce.3", 600, 100, "jit(learner_fn)/ppo_epoch/ppo_minibatch/pmean"),
        op(D1, "fusion.9", 700, 300, "jit(_shard_eval)/while/body/tanh"),
        host("learn_dispatch", 350, 100), host("some_other_span", 0, 1000),
    ])


def test_busy_union_and_window_are_means_over_chips():
    busy = tr.busy_and_window(_two_chip_trace())
    assert busy["chips"] == 2 and busy["window_s"] == pytest.approx(1000e-12)
    assert busy["busy_s"] == pytest.approx((800 + 1000) / 2 * 1e-12)


def test_overlapping_ops_are_counted_once():
    events = [op(D0, "a", 0, 100), op(D0, "b", 50, 100), op(D0, "c", 300, 10)]
    busy = tr.busy_and_window(tr.Trace.from_events(events))
    assert busy["busy_s"] == pytest.approx(160e-12) and busy["window_s"] == pytest.approx(310e-12)


def test_no_device_ops_is_nothing_to_read():
    nothing = tr.Trace.from_events([host("x", 0, 10)])
    assert tr.busy_and_window(nothing) is None
    assert tr.collective_stats(nothing) is None
    assert tr.longest_idle_gaps(nothing, ["x"]) == []


def test_seconds_by_program_strips_the_run_id_and_averages():
    sums = tr.seconds_by_program(_two_chip_trace())
    assert sums == {
        "jit_learner_fn": pytest.approx(550e-12), "jit__shard_eval": pytest.approx(350e-12)
    }
    assert tr.program_seconds(_two_chip_trace(), ["learner_fn"]) == pytest.approx(550e-12)
    assert tr.program_seconds(_two_chip_trace(), ["nothing"]) is None


def test_scope_seconds_by_named_scope_and_inside_a_program():
    events = _two_chip_trace()
    # ppo_epoch: chip 0 200+100, chip 1 300+100.
    assert tr.scope_seconds(events, "ppo_epoch") == pytest.approx(350e-12)
    assert tr.scope_seconds(events, "rollout") == pytest.approx(200e-12)
    windows = tr.program_windows(events, ["_shard_eval"])
    assert tr.scope_seconds(events, "ppo_epoch", within=windows) == 0.0
    assert tr.scope_seconds(events, "while", within=windows) == pytest.approx(350e-12)
    # A trace with no framework paths at all: nothing to read.
    assert tr.scope_seconds(tr.Trace.from_events([op(D0, "fusion.1", 0, 10)]), "ppo_epoch") is None
    # "epoch" is not "ppo_epoch": whole path components only.
    assert tr.scope_seconds(events, "epoch") == 0.0


def test_whole_only_drops_executions_that_touch_the_windows_edges():
    trace = tr.Trace.from_events([
        module(D0, "jit_learner_fn(1)", 0, 100), module(D0, "jit_learner_fn(1)", 200, 100),
        module(D0, "jit_learner_fn(1)", 400, 100),
        op(D0, "fusion.1", 0, 100), op(D0, "fusion.1", 200, 100), op(D0, "fusion.1", 400, 100),
    ])
    assert tr.program_windows(trace, ["learner_fn"])[D0] == [(0, 100), (200, 300), (400, 500)]
    assert tr.program_windows(trace, ["learner_fn"], whole_only=True)[D0] == [(200, 300)]


def test_exposed_collective_is_collective_minus_everything_else():
    stats = tr.collective_stats(_two_chip_trace())
    assert stats["calls"] == 1 and stats["exposed_s"] == pytest.approx(100e-12)
    # An async pair overlapped by compute on another line of the same chip:
    events = [
        op(D0, "while.9", 0, 120),  # the container does not hide the collective
        op(D0, "all-reduce-start.1", 0, 10), op(D0, "fusion.5", 10, 80),
        op(D0, "all-reduce-done.1", 90, 30), op(D0, "fusion.6", 100, 10),
    ]
    stats = tr.collective_stats(tr.Trace.from_events(events))
    assert stats["calls"] == 1  # start/done is one call
    assert stats["collective_s"] == pytest.approx(40e-12)
    assert stats["exposed_s"] == pytest.approx(30e-12)  # [0,10) + [90,100)


def test_top_device_ops_groups_by_scope_label():
    top = tr.top_device_ops(_two_chip_trace(), n=2)
    assert top[0] == ["jit_learner_fn: while/body/tanh [fusion]", pytest.approx(350e-12)]
    assert top[1][0] == "jit_learner_fn: ppo_epoch/ppo_minibatch/dot_general [fusion]"
    # Leaf ops only: the while that contains them is not a group.
    assert len(tr.top_device_ops(_two_chip_trace(), n=10)) == 4


def test_idle_gaps_are_named_by_the_innermost_open_annotation():
    gaps = tr.longest_idle_gaps(_two_chip_trace(), ["learn_dispatch"], n=5)
    assert gaps == [["learn_dispatch", pytest.approx(200e-12)]]
    assert tr.longest_idle_gaps(_two_chip_trace(), [], n=5) == [["unattributed", pytest.approx(200e-12)]]


def test_program_name_and_collective_match():
    assert tr.program_name("jit_learner_fn(1234567)") == "jit_learner_fn"
    assert tr.program_name("jit_f") == "jit_f"
    assert tr.instruction_name("%fusion.5 = (bf16[2]{0}, f32[2]{0}) fusion(%p.1), kind=kLoop") == "fusion.5"
    kind = lambda name, category="": tr.OpKind(name, "p", "", category)
    assert kind("all-reduce.7").collective and kind("collective-permute-start.2").collective
    assert kind("fusion.7", "all-reduce fusion").collective and not kind("fusion.7").collective
    assert kind("while.3").container and kind("x.1", "while").container and not kind("fusion.1").container


# --------------------------------------------------------------------------
# The trace recorded on the chip
# --------------------------------------------------------------------------

FIXTURES = [
    name for name in ("fixture_1chip.xplane.pb", "fixture_4chip.xplane.pb")
    if os.path.exists(os.path.join(_paths.DATA, name))
]


@pytest.fixture(scope="module", params=FIXTURES)
def recorded(request):
    path = os.path.join(_paths.DATA, request.param)
    chips = 4 if "4chip" in request.param else 1
    return chips, tr.read_xplane(path, host_names=["learn_dispatch"])


def test_recorded_trace_has_one_plane_a_chip_with_ops_and_modules(recorded):
    chips, events = recorded
    planes = events.planes
    assert len(planes) == chips
    for plane in planes:
        assert events.ops[plane].start.size and events.modules[plane], plane
    assert tr.has_paths(events)


def test_recorded_trace_busy_is_positive_and_under_the_window(recorded):
    chips, events = recorded
    busy = tr.busy_and_window(events)
    assert busy["chips"] == chips
    assert 0.0 < busy["busy_s"] < busy["window_s"]
    # The recorder sleeps 2 ms after each of 3 steps: the device idles.
    assert busy["window_s"] - busy["busy_s"] > 0.002


def test_recorded_trace_programs_and_scope(recorded):
    chips, events = recorded
    by_program = tr.seconds_by_program(events)
    learn = tr.program_seconds(events, ["learner_fn"])
    evaluate = tr.program_seconds(events, ["_shard_eval"])
    assert learn and evaluate and learn > evaluate, by_program
    windows = tr.program_windows(events, ["learner_fn"])
    assert all(len(v) == 3 for v in windows.values())  # three steps were traced
    assert all(len(v) == 2 for v in tr.program_windows(events, ["learner_fn"], whole_only=True).values())
    scoped = tr.scope_seconds(events, "ppo_epoch", within=windows)
    assert scoped is not None and 0.0 < scoped < learn
    assert tr.scope_seconds(events, "ppo_epoch", tr.program_windows(events, ["_shard_eval"])) == 0.0


def test_recorded_trace_collectives(recorded):
    chips, events = recorded
    stats = tr.collective_stats(events)
    if chips == 1:
        assert stats["calls"] == 0 and stats["exposed_s"] == 0.0
    else:
        # Two pmeans a step, three steps.
        assert stats["calls"] == pytest.approx(6.0)
        assert 0.0 < stats["exposed_s"] <= stats["collective_s"]


def test_recorded_trace_breakdown(recorded):
    chips, events = recorded
    top = tr.top_device_ops(events, 10)
    assert 1 <= len(top) <= 10 and top == sorted(top, key=lambda kv: -kv[1])
    gaps = tr.longest_idle_gaps(events, ["learn_dispatch"], 10)
    assert gaps and gaps[0][1] > 0.001 and all(g[0] in ("learn_dispatch", "unattributed") for g in gaps)
