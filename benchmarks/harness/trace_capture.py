"""The one way the benchmark captures a profiler trace: the traced window of
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
from typing import Any, Optional


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


class TraceWindow:
    """Profiles `ticks` whole ticks, from the tick `start_tick` ticks after
    set-up ended (the ticks before it are left undisturbed for the registry
    deltas)."""

    def __init__(self, directory: str, start_tick: int, ticks: int) -> None:
        self.path = os.path.join(directory, "trace.xplane.pb")
        self.start_tick = int(start_tick)
        self.ticks = int(ticks)
        self._session: Any = None
        self.done = False

    @property
    def running(self) -> bool:
        return self._session is not None

    def on_tick(self, since_setup: int) -> None:
        if self.done:
            return
        if not self.running and since_setup >= self.start_tick:
            self._session = start()
        elif self.running and since_setup >= self.start_tick + self.ticks:
            self.close()

    def close(self) -> None:
        if self.running:
            session, self._session, self.done = self._session, None, True
            stop(session, self.path)

    def xplane(self) -> Optional[str]:
        return self.path if os.path.exists(self.path) else None
