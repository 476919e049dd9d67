"""The capture path and the hand-declared trace schema against the installed
jax. `benchmarks/harness/trace_capture.py` drives a session class under
`jax._src` and `xplane_proto.py` declares the trace's protobuf fields by
number; both were written against one jax. These tests capture a trace
through that very path, read it back, and hold the reader to
`jax.profiler.ProfileData` on the trace recorded on the chip, so an upgrade
that moves either fails here and not in a chip run."""

import os

import pytest

import _paths
from benchmarks.harness import observe, trace_capture
from benchmarks.harness import trace_reduce as tr

WRITTEN_AGAINST = "0.9."


def test_the_private_profiler_session_is_where_the_harness_expects_it():
    import jax
    from jax._src.lib import _profiler

    assert jax.__version__.startswith(WRITTEN_AGAINST), (
        f"jax {jax.__version__}: re-check benchmarks/harness/trace_capture.py (ProfilerSession, "
        "its options and stop()) and xplane_proto.py's field numbers, then move this pin"
    )
    assert callable(_profiler.ProfilerSession) and hasattr(_profiler.ProfilerSession, "stop")
    assert hasattr(jax.profiler.ProfileOptions(), "host_tracer_level")


def test_a_trace_captured_through_the_harness_reads_back(tmp_path):
    """TraceWindow over two ticks on the CPU backend: the file is an XSpace
    the reduction reads, with the TraceAnnotation on a host line."""
    import jax
    import jax.numpy as jnp

    step = jax.jit(lambda x: jnp.tanh(x) @ x)
    x = step(jnp.ones((64, 64)))
    judge = lambda path: (tr.read_xplane(path, host_names=["learn_dispatch"]), {"window_sound": True})
    window = trace_capture.TraceWindow(str(tmp_path), start_tick=1, ticks=2, judge=judge, sessions=1)
    for tick in range(5):
        window.on_tick(tick)
        assert window.running == (1 <= tick < 3)
        with jax.profiler.TraceAnnotation("learn_dispatch"):
            x = step(x)
        jax.block_until_ready(x)
    window.close()  # closing twice is harmless
    assert window.done and not os.listdir(tmp_path)  # read once closed, and removed
    assert [(r["opened_at_tick"], r["complete"], r["sound"]) for r in window.records] == [(1, True, True)]
    trace = window.chosen[0]
    assert [h.name for h in trace.host] == ["learn_dispatch"] * 2  # ticks 1 and 2 only
    assert all(h.dur_ps > 0 for h in trace.host)
    assert tr.busy_and_window(trace) is None  # the CPU backend has no device plane


# --------------------------------------------------------------------------
# Several sessions, judged as they close (no profiler: `start` and `stop` of
# trace_capture, the one place that names jax's session class, are faked)
# --------------------------------------------------------------------------


@pytest.fixture()
def fake_sessions(monkeypatch):
    """Sessions are numbers; `stop` writes the number to the file."""
    made = []

    def start():
        made.append(len(made))
        return f"session-{made[-1]}"

    def stop(session, path):
        with open(path, "w") as handle:
            handle.write(session)

    monkeypatch.setattr(trace_capture, "start", start)
    monkeypatch.setattr(trace_capture, "stop", stop)
    return made


def _judge(verdicts, seen):
    """judge(path) -> (what the file held, the next of `verdicts`): whole
    learner executions, and unreadable seconds that readable time follows.
    Every trace also lost 2 ms at its very end, which is no fault."""
    def judge(path):
        with open(path) as handle:
            seen.append((os.path.basename(path), handle.read()))
        whole, inside = verdicts[len(seen) - 1]
        facts = {
            "whole_learner_executions": whole, "chips_with_whole_execution": min(whole, 1),
            "unreadable_s": inside + 0.002, "lost_inside_s": inside, "window_sound": whole > 0 and not inside,
        }
        return seen[-1][1], facts
    return judge


def _drive(window, ticks):
    states = []
    for tick in range(ticks):
        window.on_tick(tick)
        states.append((window.running, window.busy))
    return states


@pytest.mark.parametrize("verdicts,cap,opened,used", [
    ([(1, 0.0)], 3, [2], 0),  # sound at once: one session, as before PR 27
    ([(0, 1.4), (1, 0.0)], 3, [2, 5], 1),  # the lost execution costs one more session
    ([(1, 0.3), (1, 0.0)], 3, [2, 5], 1),  # readable but damaged: a clean one is looked for
    ([(0, 1.4), (0, 1.4), (1, 0.2)], 3, [2, 5, 8], 2),  # none clean: the one with a whole execution
    ([(0, 1.4), (0, 1.3), (0, 1.2)], 3, [2, 5, 8], 0),  # none readable: the first, and the run says so
    ([(0, 1.4), (1, 0.0)], 1, [2], 0),  # a cap of one: one session whatever it holds
    ([(0, 1.4), (0, 1.4), (0, 1.4), (1, 0.0)], None, [2, 5, 8], 0),  # no cap given: trace_capture's own, three
])
def test_sessions_open_and_close_on_the_ticks_stated(tmp_path, fake_sessions, verdicts, cap, opened, used):
    seen = []
    given = {} if cap is None else {"sessions": cap}
    window = trace_capture.TraceWindow(str(tmp_path), 2, 2, _judge(verdicts, seen), **given)
    assert window.sessions == (cap or trace_capture.MAX_SESSIONS == 3 and 3)
    states = _drive(window, 14)
    # A session runs for two ticks; the next opens one tick after the close.
    running = [tick for tick, (is_running, _) in enumerate(states) if is_running]
    assert running == [tick for start in opened for tick in (start, start + 1)]
    assert [r["opened_at_tick"] for r in window.records] == opened == [2 + 3 * i for i in range(len(opened))]
    # Busy from the first opening until no further session will open: the
    # run is held for exactly that long.
    assert [busy for _, busy in states] == [2 <= tick < opened[-1] + 2 for tick in range(14)]
    assert window.done and len(fake_sessions) == len(opened)
    # Files are read in order, each once, and removed.
    assert seen == [(f"trace-{i}.xplane.pb", f"session-{i}") for i in range(len(opened))]
    assert os.listdir(tmp_path) == []
    trace, record = window.chosen
    assert (trace, record["session"]) == (f"session-{used}", used)
    report = window.report()
    assert (report["sessions"], report["used"]) == (len(opened), used)
    assert report["whole_learner_executions"] == verdicts[used][0]
    assert [c["sound"] for c in report["candidates"]] == [w > 0 and not u for w, u in verdicts[:len(opened)]]
    assert all(c["stop_s"] >= 0.0 and c["read_s"] >= 0.0 and c["bytes"] > 0 for c in report["candidates"])


def test_the_runs_end_closes_an_open_session_which_is_then_not_complete(tmp_path, fake_sessions):
    seen = []
    window = trace_capture.TraceWindow(str(tmp_path), 2, 2, _judge([(0, 1.4), (1, 0.0)], seen), sessions=3)
    _drive(window, 6)  # the second session opened at tick 5
    assert window.running and window.busy and len(window.records) == 1
    window.close()
    window.close()  # closing twice is harmless
    assert not window.running and not window.busy and window.done
    assert [(r["opened_at_tick"], r["complete"], r["sound"]) for r in window.records] == [(2, True, False), (5, False, False)]
    # Cut short, it is no better than the first: the earlier of two alike stays.
    assert window.chosen[1]["session"] == 1 and window.chosen[0] == "session-1"
    window.on_tick(9)
    assert not window.running and len(fake_sessions) == 2  # nothing opens after the end


def test_a_session_being_ended_still_holds_the_stop_back(tmp_path, fake_sessions):
    """The deadline's thread reads `busy` while the main thread is inside
    stop() and the judge, which take a minute on the chip."""
    held = []

    def judge(path):
        held.append(window.busy)
        return "trace", {"chips_with_whole_execution": 0, "unreadable_s": 1.0, "window_sound": False}

    window = trace_capture.TraceWindow(str(tmp_path), 2, 2, judge, sessions=2)
    _drive(window, 5)
    assert held == [True] and window.busy and not window.running  # the second opens at tick 5


@pytest.mark.parametrize("left,opened", [
    (float("inf"), [2, 5, 8]),  # time enough: up to the cap
    (75.0, [2, 5, 8]),  # what a session cost (10 s traced, 60 to end, 5 to read) just fits
    (74.9, [2]),  # it does not: a session that could not be over in time is not opened
])
def test_a_session_opens_only_if_what_the_last_one_cost_still_fits(tmp_path, fake_sessions, monkeypatch, left, opened):
    """The cost is the last session's own, measured: on four chips ending
    and reading take 108 s, in the token cell 4 (my chip runs, PR 27), and no
    constant fits both."""
    now = [0.0]
    monkeypatch.setattr(trace_capture.time, "perf_counter", lambda: now[0])

    def stop(session, path):  # ending a session takes 60 s of the clock
        now[0] += 60.0
        with open(path, "w") as handle:
            handle.write(session)

    def judge(path):  # and reading it 5
        now[0] += 5.0
        return "trace", {"chips_with_whole_execution": 0, "window_sound": False}

    monkeypatch.setattr(trace_capture, "stop", stop)
    window = trace_capture.TraceWindow(str(tmp_path), 2, 2, judge, seconds_left=lambda: left)
    for tick in range(12):
        window.on_tick(tick)
        now[0] += 5.0  # a tick every 5 s: a session of two ticks traces 10 s
    assert [r["opened_at_tick"] for r in window.records] == opened and window.done and not window.busy
    assert [(r["traced_s"], r["stop_s"], r["read_s"]) for r in window.records] == [(10.0, 60.0, 5.0)] * len(opened)


def test_the_first_session_opens_whatever_the_clock_says(tmp_path, fake_sessions):
    window = trace_capture.TraceWindow(str(tmp_path), 2, 2, _judge([(0, 1.4)], []), seconds_left=lambda: 0.0)
    _drive(window, 9)
    assert [r["opened_at_tick"] for r in window.records] == [2] and window.done


def test_a_window_that_never_opened_is_not_busy(tmp_path, fake_sessions):
    window = trace_capture.TraceWindow(str(tmp_path), 5, 2, _judge([], []), sessions=3)
    _drive(window, 3)
    assert not window.busy and not window.records  # an interval too short does not hold the stop
    window.close()
    assert window.chosen is None and window.report() == {"sessions": 0, "used": None, "candidates": []}


FIXTURES = [
    name for name in ("fixture_1chip.xplane.pb", "fixture_4chip.xplane.pb")
    if os.path.exists(os.path.join(_paths.DATA, name))
]


@pytest.mark.parametrize("name", FIXTURES)
def test_the_declared_schema_reads_what_profiledata_reads(name):
    """Plane by plane: the same op and module events, names and picoseconds,
    from the hand-declared messages and from jax's own reader."""
    from jax.profiler import ProfileData

    path = os.path.join(_paths.DATA, name)
    ours = tr.read_xplane(path, host_names=["learn_dispatch"])
    theirs = {p.name: p for p in ProfileData.from_file(path).planes if tr.DEVICE_PLANE.match(p.name)}
    assert sorted(theirs) == ours.planes
    for plane_name, plane in theirs.items():
        lines = {line.name: list(line.events) for line in plane.lines}
        ops, chip = lines[tr.OPS_LINE], ours.ops[plane_name]
        assert len(ops) == chip.start.size
        # ProfileData gives whole nanoseconds, the declared messages picoseconds.
        assert [int(e.duration_ns) for e in ops] == ((chip.end - chip.start) // 1000).tolist()
        assert [tr.instruction_name(e.name) for e in ops] == [
            ours.kinds[k].name for k in chip.kind.tolist()
        ]
        modules = [(tr.program_name(e.name), int(e.duration_ns)) for e in lines[tr.MODULES_LINE]]
        assert modules == [
            (name_, (end - start) // 1000) for name_, start, end in ours.modules[plane_name]
        ]
    host = [
        e for p in ProfileData.from_file(path).planes if p.name.startswith(tr.HOST_PLANE_PREFIX)
        for line in p.lines for e in line.events if e.name == "learn_dispatch"
    ]
    assert len(host) == len(ours.host) == 3


def test_a_stat_stored_as_a_reference_is_looked_up():
    from benchmarks.harness import xplane_proto

    stat = xplane_proto.messages()["XStat"](metadata_id=1, ref_value=7)
    assert tr._stat_value(stat, {7: "convolution fusion"}) == "convolution fusion"
    assert tr._stat_value(stat, {}) == ""
    assert tr._stat_value(xplane_proto.messages()["XStat"](str_value="loop fusion"), {}) == "loop fusion"
    assert tr._stat_value(xplane_proto.messages()["XStat"](int64_value=12), {}) == 12


class _FakeDevice:
    platform, device_kind = "tpu", "TPU v5 lite"

    def __init__(self, in_use, reserved):
        self._stats = {"peak_bytes_in_use": in_use, "peak_bytes_reserved": reserved, "bytes_limit": 16 << 30}

    def memory_stats(self):
        return self._stats


def test_memory_peak_is_the_larger_of_two_real_peaks_on_the_fullest_chip():
    facts = observe.device_facts([_FakeDevice(500, 5000), _FakeDevice(1700, 4990)])
    assert facts["memory_peak_bytes"] == 5000  # never the sum of two peaks
    assert (facts["peak_bytes_in_use"], facts["peak_bytes_reserved"]) == (500, 5000)
    assert (facts["platform"], facts["kind"], facts["count"]) == ("tpu", "TPU v5 lite", 2)
    # A cell that live arrays fill (a replay buffer) reads by its arrays.
    assert observe.device_facts([_FakeDevice(9000, 100)])["memory_peak_bytes"] == 9000
