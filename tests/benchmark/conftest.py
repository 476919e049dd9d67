"""The slow end-to-end rehearsal (test_benchmark_rehearsal.py) runs every
cell of BENCHMARK.json through tests/benchmark/rehearse.py with a few
override strings looked up by driver name. A cell whose configuration cannot
be restated by overrides alone — the token policy at its published widths is
0.63 G parameters, and `correct` holds a run to the widths its file states —
names the test file that rehearses it instead (`rehearsed_by` in its config
file), and its rehearsal case is skipped here with that reason. Nothing in
the rehearsal file is edited (PERF.md section 7: a `benchmark` issue can fold
`rehearsed_by`, or tiny overrides a cell brings itself, into it)."""

import pytest

import _paths  # noqa: F401
from benchmarks.harness import loader


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.name.startswith("test_cell_rehearses_end_to_end_on_virtual_devices["):
            cell = loader.load_cell(item.callspec.params["cell"])
            elsewhere = cell.config.get("rehearsed_by")
            if elsewhere:
                item.add_marker(pytest.mark.skip(reason=f"rehearsed at a tiny preset by {elsewhere}"))
