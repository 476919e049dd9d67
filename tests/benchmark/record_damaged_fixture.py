#!/usr/bin/env python3
"""Cuts a trace in which the profiler lost a program boundary down to
fixture size (data/fixture_lost_boundary_1chip.xplane.pb):

    python3 tests/benchmark/record_damaged_fixture.py <in.xplane.pb> <out.xplane.pb> [min_us]

A damaged trace cannot be recorded small: the loss has only been seen at the
end of an evaluator execution that emits over a million op events in a tenth
of a second (PERF.md section 3), and such a trace is 200 MB. So a real one, of
`anakin_ppo_ant_1chip` on the chip, is thinned: of each device plane the
"XLA Modules" line whole and the "XLA Ops" events of at least `min_us`
microseconds (default 200) — among them the unnamed `region.<n>` events of
the lost execution, as the profiler wrote them, without a `program_id` — with
the event and stat metadata they name; an event's HLO text is cut to the
instruction's name. Host planes and the HLO protos (the framework paths) are
left out, so the fixture tests what a lost boundary looks like and how it is
cut, not the scope shares. Times and durations are the chip's own.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def thin(space, min_ps: int):
    from benchmarks.harness import trace_reduce as tr
    from benchmarks.harness import xplane_proto

    out = xplane_proto.messages()["XSpace"]()
    for plane in space.planes:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        new = out.planes.add(id=plane.id, name=plane.name)
        new.stat_metadata.extend(plane.stat_metadata)
        new.stats.extend(plane.stats)
        used = set()
        for line in plane.lines:
            if line.name not in (tr.MODULES_LINE, tr.OPS_LINE):
                continue
            kept = new.lines.add(id=line.id, name=line.name, timestamp_ns=line.timestamp_ns)
            for event in line.events:
                if line.name == tr.MODULES_LINE or event.duration_ps >= min_ps:
                    kept.events.add(
                        metadata_id=event.metadata_id, offset_ps=event.offset_ps, duration_ps=event.duration_ps
                    )
                    used.add(event.metadata_id)
        for entry in plane.event_metadata:
            if entry.key in used:
                meta = new.event_metadata.add(key=entry.key).value
                meta.CopyFrom(entry.value)
                if " = " in meta.name:
                    meta.name = meta.name.split(" = ", 1)[0] + " = thinned()"
    return out


def main() -> int:
    from benchmarks.harness import xplane_proto

    source, target = sys.argv[1], sys.argv[2]
    min_us = float(sys.argv[3]) if len(sys.argv) > 3 else 200.0
    with open(source, "rb") as handle:
        space = xplane_proto.parse("XSpace", handle.read())
    with open(target, "wb") as handle:
        handle.write(thin(space, int(min_us * 1e6)).SerializeToString())
    print(f"{target}: {os.path.getsize(target)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
