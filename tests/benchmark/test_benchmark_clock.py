"""The rate estimator and the interval clock, on synthetic timestamps."""

import pytest

import _paths  # noqa: F401
from benchmarks.harness import clock
from benchmarks.harness.clock import Tick


def _ticks(period, steps, n, start=100.0):
    return [Tick(start + i * period, (i + 1) * steps) for i in range(n)]


@pytest.mark.parametrize("edge_shift", [0.0, 0.3, 0.9])
def test_rate_counts_whole_units_whatever_the_edges(edge_shift):
    # One unit of 1000 steps a second; the interval's far edge falls anywhere
    # between two completions and the estimate does not move.
    ticks = _ticks(1.0, 1000, 40)
    rate = clock.rate_over_interval(ticks, start=100.0, seconds=10.0 + edge_shift)
    assert rate.steps_per_s == pytest.approx(1000.0)
    assert (rate.first, rate.last) == (0, 10)
    assert rate.steps == 10_000 and rate.seconds == pytest.approx(10.0)


def test_rate_ignores_completions_outside_the_interval():
    slow_warmup = [Tick(50.0, 1000)]
    steady = [Tick(100.0 + i * 0.5, 2000 + i * 1000) for i in range(30)]
    late = [Tick(130.0, 99_000_000)]  # the drain after the deadline
    rate = clock.rate_over_interval(slow_warmup + steady + late, start=100.0, seconds=10.0)
    assert rate.steps_per_s == pytest.approx(2000.0)
    assert rate.first == 1 and rate.last == 21


def test_rate_needs_two_completions():
    with pytest.raises(ValueError, match="interval too short"):
        clock.rate_over_interval(_ticks(20.0, 1000, 3), start=100.0, seconds=10.0)


def test_rate_refuses_a_clock_that_does_not_advance():
    with pytest.raises(ValueError, match="do not advance"):
        clock.rate_between([Tick(1.0, 10), Tick(1.0, 20)], 0, 1)


def test_drift_compares_first_and_last_third():
    # 12 units: the first six take 1 s each, the last six 2 s each.
    times, t = [], 0.0
    for i in range(13):
        times.append(t)
        t += 1.0 if i < 6 else 2.0
    ticks = [Tick(x, (i + 1) * 100) for i, x in enumerate(times)]
    assert clock.drift(ticks, 0, 12) == pytest.approx(-0.5)
    assert clock.drift(ticks, 0, 4) is None


def test_interval_clock_starts_after_warmup_and_ready_and_fires_deadline():
    fired = []
    ready = {"ok": False}
    ic = clock.IntervalClock(
        seconds=0.05, warmup_ticks=2, on_deadline=lambda: fired.append(True),
        ready=lambda: ready["ok"], process_start=0.0,
    )
    seen = []
    ic.on_tick(lambda index, tick: seen.append(index))
    ic.tick(10)
    ic.tick(20)
    assert ic.start is None  # warm-up done, not ready
    ready["ok"] = True
    ic.tick(30)
    assert ic.start == ic.ticks[2].time and ic.setup_s == pytest.approx(ic.start)
    ic._timer.join(timeout=2.0)
    assert fired == [True] and seen == [0, 1, 2]
    ic.cancel()


def test_interval_clock_refuses_no_warmup():
    with pytest.raises(ValueError):
        clock.IntervalClock(1.0, warmup_ticks=0, on_deadline=lambda: None)


def test_learn_check_reads_the_first_evaluation_at_or_after_the_budget():
    from benchmarks.harness.observe import learn_check_verdict

    evals = [(100, 20.0), (200, 30.0), (300, 60.0)]
    assert learn_check_verdict(evals, None) is None
    ok = learn_check_verdict(evals, {"steps": 150, "min_return": 25.0})
    assert ok == {"at_steps": 200, "return": 30.0, "min_return": 25.0, "ok": True}
    assert not learn_check_verdict(evals, {"steps": 150, "min_return": 35.0})["ok"]
    # A run that never reaches the budget fails the check.
    assert not learn_check_verdict(evals, {"steps": 400, "min_return": 1.0})["ok"]


@pytest.mark.parametrize(
    "tick_times, first_eval_at, compile_ends, expected",
    [
        ([1.0], 0.5, [], False),  # one tick bounds no whole update
        ([1.0, 2.0], None, [], False),  # no evaluation is back yet
        ([1.0, 2.0], 1.5, [], False),  # back while the last update ran, not before it
        ([1.0, 2.0], 0.9, [1.2], False),  # back in time, but its handler compiled in the update
        ([1.0, 2.0, 3.0], 0.9, [1.2], True),  # the next update began after it and compiled nothing
        ([1.0, 2.0], 1.0, [0.99], True),  # a compilation before the update began does not count
    ],
)
def test_sebulba_setup_ends_after_a_whole_quiet_update_begun_after_the_first_evaluation(
    tick_times, first_eval_at, compile_ends, expected
):
    """The evaluator thread logs an evaluation when it is dispatched; the
    returns, and the mean its result handler compiles, come a whole learner
    update later when the learner gets the device first. Set-up may not end
    before that compilation has."""
    from benchmarks.harness import loader, observe

    counter = observe.CompileCounter()
    counter.ended_at = list(compile_ends)
    ticks = [Tick(t, (i + 1) * 100) for i, t in enumerate(tick_times)]
    settled = loader.load_driver("sebulba").settled
    assert settled(ticks, first_eval_at, counter.inside) is expected
