"""The one way the benchmark captures a profiler trace: the traced sessions of
a run (`TraceWindow`) and the recorder of the test fixtures
(tests/benchmark/record_fixture.py) both go through `start` and `stop`.

`start` makes the session `jax.profiler.start_trace` makes
(`jax._src.lib._profiler.ProfilerSession`, with the same options object);
`stop` writes the XSpace it collected, the bytes `stop_trace()` exports as
`*.xplane.pb`. `jax.profiler.stop_trace()` is not used because it also
converts the trace to a trace-viewer JSON, which on a trace of four million
ops whose names are whole HLO instructions took 100 s of the 115 s a traced
run needed after its interval (my chip run, PR 22). The module path is
private to jax: tests/benchmark/test_benchmark_trace_capture.py captures and
reads back a trace through this file and names the jax version it was
written against, so an upgrade that moves it fails there first.
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple


def start() -> Any:
    """A running profiler session: Python tracer off, host tracer at level
    1, so the host lines carry TraceAnnotations only."""
    import jax
    from jax._src.lib import _profiler

    jax.devices()  # the backend exists before the tracer does, as start_trace ensures
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    return _profiler.ProfilerSession(options)


def stop(session: Any, path: str) -> None:
    """Ends the session and writes the collected XSpace to `path`."""
    data = session.stop()
    with open(path, "wb") as handle:
        handle.write(data)


# The most sessions a traced run makes. At the loss rates seen (one session
# in four of the fastest program tried ends without a whole learner
# execution, PERF.md section 6) three all fail in about 2% of runs; the
# run's own time limit (`seconds_left`) cuts in earlier where sessions are dear.
MAX_SESSIONS = 3


class TraceWindow:
    """Profiles whole ticks in sessions of `ticks` ticks each. The first
    opens `start_tick` ticks after set-up ended (the ticks before it are left
    undisturbed for the registry deltas) and is written to
    `trace-0.xplane.pb`.

    The profiler now and then loses a program boundary (trace_reduce.py,
    "unreadable"), and a session of two ticks holds one whole learner
    execution. So each session is judged as soon as it is closed:
    `judge(path)` reads the file and returns (trace, facts), and the file is
    removed. A session is *sound* if it ran its ticks out and `facts` says
    `window_sound` (`trace_reduce.soundness`: a whole readable learner
    execution on every chip, and no unreadable stretch to speak of with
    readable time after it). While none has been sound another session is
    opened, one tick after the close (the tick in which the host stood still
    for the stop refills the pipeline), up to `sessions` of them, and only
    while `seconds_left()` covers what the session before it cost: its
    traced seconds, ending it and reading it, all measured (ending a session
    takes a minute on a trace of four million ops, a minute and a half on
    four chips, four seconds in the token cell: PERF.md section 2). The
    soundest session, the earlier of two alike, is `chosen`. A run whose
    first session is sound, as nearly every run's is, costs what one session
    costs, and a run has to go on while `busy` holds (cell_runner holds its
    stop back)."""

    def __init__(
        self, directory: str, start_tick: int, ticks: int,
        judge: Callable[[str], Tuple[Any, Dict[str, Any]]], sessions: int = MAX_SESSIONS,
        seconds_left: Callable[[], float] = lambda: float("inf"),
    ) -> None:
        self.directory = directory
        self.ticks = int(ticks)
        self.sessions = int(sessions)
        self.judge = judge
        self.seconds_left = seconds_left  # asked before every session but the first
        self.records: List[Dict[str, Any]] = []  # one a session, in order
        self.chosen: Optional[Tuple[Any, Dict[str, Any]]] = None  # (trace, its record)
        self.done = False  # no further session will open
        self._rank = (False, False)
        self._session: Any = None
        self._opened_at = 0
        self._opened_time = 0.0
        self._next_open = int(start_tick)

    @property
    def running(self) -> bool:
        return self._session is not None

    @property
    def busy(self) -> bool:
        """A session is open, or another will open at a coming tick."""
        return self.running or (bool(self.records) and not self.done)

    def path(self, index: int) -> str:
        return os.path.join(self.directory, f"trace-{index}.xplane.pb")

    def on_tick(self, since_setup: int) -> None:
        if self.running:
            if since_setup >= self._opened_at + self.ticks:
                self._end(since_setup, complete=True)
        elif not self.done and since_setup >= self._next_open:
            if self.records and self.seconds_left() < self._cost_s(self.records[-1]):
                self.done = True  # the run has no time left for another session
            else:
                self._session, self._opened_at = start(), since_setup
                self._opened_time = time.perf_counter()

    @staticmethod
    def _cost_s(record: Dict[str, Any]) -> float:
        """What a session costs from its opening tick on, as the last one did."""
        return record["traced_s"] + record["stop_s"] + record["read_s"]

    def close(self) -> None:
        """The run's end: ends an open session, which is then not complete."""
        if self.running:
            self._end(self._opened_at, complete=False)
        self.done = True

    def _end(self, since_setup: int, complete: bool) -> None:
        # `busy` is read from the deadline's thread: the session counts as
        # open until its verdict is in and `done` says whether another follows.
        try:
            index = len(self.records)
            began = time.perf_counter()
            stop(self._session, self.path(index))
            record: Dict[str, Any] = {
                "session": index, "opened_at_tick": self._opened_at, "complete": complete,
                "traced_s": began - self._opened_time, "stop_s": time.perf_counter() - began,
                "bytes": os.path.getsize(self.path(index)),
            }
            began = time.perf_counter()
            trace, facts = self.judge(self.path(index))
            os.remove(self.path(index))
            record.update(facts, read_s=time.perf_counter() - began)
            # First what the shares of the learner's own time need (a whole
            # execution on some chip), then what the window's shares need.
            rank = (
                record.get("chips_with_whole_execution") != 0,
                complete and bool(record.get("window_sound")),
            )
            record["sound"] = all(rank)
            if self.chosen is None or rank > self._rank:
                self.chosen, self._rank = (trace, record), rank
            self.records.append(record)
            self.done = record["sound"] or not complete or len(self.records) >= self.sessions
            self._next_open = since_setup + 1
        finally:
            self._session = None

    def report(self) -> Dict[str, Any]:
        """The run's `trace` object: the sessions made, which was used and
        what it held, and every session's record."""
        used = self.chosen[1] if self.chosen is not None else {}
        return {
            "sessions": len(self.records), "used": used.get("session"),
            **{k: v for k, v in used.items() if k not in ("session", "opened_at_tick", "bytes")},
            "candidates": [{k: v for k, v in r.items() if k != "session"} for r in self.records],
        }
