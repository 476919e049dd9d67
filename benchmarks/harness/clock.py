"""The harness's own clock: completions of whole units of training work
("ticks": one Anakin eval window, one Sebulba learner update), stamped on the
host when the unit's results are on the host, and the rate worked out from
them. Pure functions over (time, steps) pairs, so synthetic stamps test them.

Set-up ends at the completion of the last warm-up tick; the measured interval
is the `seconds` after it. The rate counts whole ticks only — env steps
between the first and the last completion inside the interval over the time
between those two completions — so it does not depend on where the interval's
edges fall between two completions.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, List, NamedTuple, Optional, Sequence, Tuple


class Tick(NamedTuple):
    time: float  # host perf_counter seconds at completion
    steps: int  # the program's own count of training env steps so far


class Rate(NamedTuple):
    steps_per_s: float
    first: int  # index of the first completion inside the interval
    last: int  # index of the last completion inside the interval
    steps: int
    seconds: float


def ticks_in_interval(
    ticks: Sequence[Tick], start: float, seconds: float
) -> Tuple[int, int]:
    """Indices [first, last] of the completions with start <= time <= start +
    seconds. Raises if fewer than two fall inside: one completion bounds no
    whole unit of work."""
    inside = [i for i, t in enumerate(ticks) if start <= t.time <= start + seconds]
    if len(inside) < 2:
        raise ValueError(
            f"{len(inside)} completion(s) inside the {seconds} s interval: "
            "no whole unit of work to measure (interval too short for this cell)"
        )
    return inside[0], inside[-1]


def rate_between(ticks: Sequence[Tick], first: int, last: int) -> Rate:
    a, b = ticks[first], ticks[last]
    if b.time <= a.time or b.steps <= a.steps:
        raise ValueError(f"completions do not advance: {a} -> {b}")
    return Rate(
        (b.steps - a.steps) / (b.time - a.time), first, last,
        b.steps - a.steps, b.time - a.time,
    )


def rate_over_interval(ticks: Sequence[Tick], start: float, seconds: float) -> Rate:
    first, last = ticks_in_interval(ticks, start, seconds)
    return rate_between(ticks, first, last)


def drift(ticks: Sequence[Tick], first: int, last: int) -> Optional[float]:
    """Rate over the last third of the interval's completions over the rate
    over the first third, minus 1 (None under six units of work). An
    evaluation that costs more as the policy learns shows here as < 0."""
    n = last - first
    if n < 6:
        return None
    third = n // 3
    head = rate_between(ticks, first, first + third).steps_per_s
    tail = rate_between(ticks, last - third, last).steps_per_s
    return tail / head - 1.0


class IntervalClock:
    """Collects ticks from the main thread, declares set-up over once
    `warmup_ticks` completions exist and `ready()` holds, and then arms
    `on_deadline` (the program's own graceful stop) `seconds` later."""

    def __init__(
        self,
        seconds: float,
        warmup_ticks: int,
        on_deadline: Callable[[], None],
        ready: Callable[[], bool] = lambda: True,
        process_start: Optional[float] = None,
    ) -> None:
        if warmup_ticks < 1:
            raise ValueError("warmup_ticks must be >= 1: the first unit compiles")
        self.seconds = float(seconds)
        self.warmup_ticks = int(warmup_ticks)
        self.process_start = time.perf_counter() if process_start is None else process_start
        self.ticks: List[Tick] = []
        self.start: Optional[float] = None  # perf_counter at set-up end
        self.start_index: Optional[int] = None  # index of the tick that ended set-up
        self._ready = ready
        self._on_deadline = on_deadline
        self._timer: Optional[threading.Timer] = None
        self._listeners: List[Callable[[int, Tick], None]] = []

    def on_tick(self, listener: Callable[[int, Tick], None]) -> None:
        """`listener(index, tick)` runs on the ticking thread after each tick."""
        self._listeners.append(listener)

    def tick(self, steps: int) -> None:
        tick = Tick(time.perf_counter(), int(steps))
        self.ticks.append(tick)
        if self.start is None and len(self.ticks) >= self.warmup_ticks and self._ready():
            self.start = tick.time
            self.start_index = len(self.ticks) - 1
            self._timer = threading.Timer(self.seconds, self._on_deadline)
            self._timer.daemon = True
            self._timer.start()
        for listener in self._listeners:
            listener(len(self.ticks) - 1, tick)

    def in_interval(self, tick: Tick) -> bool:
        return self.start is not None and self.start <= tick.time <= self.start + self.seconds

    def cancel(self) -> None:
        if self._timer is not None:
            self._timer.cancel()
            self._timer.join(timeout=5.0)

    @property
    def setup_s(self) -> float:
        if self.start is None:
            raise ValueError("set-up never ended: no warm-up completion was seen")
        return self.start - self.process_start

    def rate(self) -> Rate:
        if self.start is None:
            raise ValueError("set-up never ended: no warm-up completion was seen")
        return rate_over_interval(self.ticks, self.start, self.seconds)
