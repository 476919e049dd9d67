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
    window = trace_capture.TraceWindow(str(tmp_path), start_tick=1, ticks=2)
    for tick in range(5):
        window.on_tick(tick)
        assert window.running == (1 <= tick < 3)
        with jax.profiler.TraceAnnotation("learn_dispatch"):
            x = step(x)
        jax.block_until_ready(x)
    window.close()  # closing twice is harmless
    assert window.done and window.xplane() == os.path.join(str(tmp_path), "trace.xplane.pb")
    trace = tr.read_xplane(window.xplane(), host_names=["learn_dispatch"])
    assert [h.name for h in trace.host] == ["learn_dispatch"] * 2  # ticks 1 and 2 only
    assert all(h.dur_ps > 0 for h in trace.host)
    assert tr.busy_and_window(trace) is None  # the CPU backend has no device plane


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
