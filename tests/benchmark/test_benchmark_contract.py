"""BENCHMARK.json against the contract's limits, and the files it names."""

import json
import os
import re

import pytest

import _paths
from benchmarks.harness import loader

BENCH = loader.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def _all_names():
    names = [("config", c["name"]) for c in BENCH["configs"]]
    names += [("workload", w["name"]) for w in BENCH["workloads"]]
    names += [("traffic", w["traffic"]) for w in BENCH["workloads"]]
    names += [("metric", m["name"]) for m in METRICS]
    names += [("reduced", k) for c in BENCH["configs"] for k in c["reduced"]]
    return names


@pytest.mark.parametrize("kind,name", _all_names())
def test_every_name_is_within_the_allowed_characters(kind, name):
    assert NAME.match(name), (kind, name)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_every_metric_has_unit_direction_and_source(metric):
    assert UNIT.match(metric["unit"]), metric
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    allowed = {"name", "unit", "better", "source", "workloads"}
    allowed |= {"bound"} if metric in BENCH["end_to_end"] else {"layer", "moves"}
    assert set(metric) <= allowed, set(metric) - allowed
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells


def test_top_level_keys_and_limits():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"
    }
    assert len(json.dumps(BENCH)) < 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    assert 2 <= len(BENCH["workloads"]) <= 24 and 1 <= len(BENCH["configs"]) <= 24
    assert len({m["name"] for m in METRICS}) == len(METRICS)
    for word in BENCH["command"] + [w["why"] for w in BENCH["workloads"]] + [
        c["why"] for c in BENCH["configs"]
    ] + [m["layer"] for m in BENCH["per_layer"]]:
        assert 1 <= len(word) <= 200 and "\n" not in word and "\t" not in word, word
    # The full check has to fit: (2 + 14 * 24) runs of run_seconds + 60, the
    # compile allowance of 24 cells, and the spare.
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_end_to_end_metrics_and_bounds():
    names = [m["name"] for m in BENCH["end_to_end"]]
    assert "setup_s" in names and len(names) >= 2
    for metric in BENCH["end_to_end"]:
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    moves = {m["moves"] for m in BENCH["per_layer"]}
    assert moves <= set(names)


def test_four_chip_share_and_pairs():
    cells = BENCH["workloads"]
    four = [w for w in cells if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in cells)
    assert len(four) <= max(1, len(cells) // 4)
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(pairs)) == len(pairs)
    assert {w["config"] for w in cells} == {c["name"] for c in BENCH["configs"]}


def test_command_and_files_live_under_paths():
    under = lambda p: any(p == d or p.startswith(d + "/") for d in BENCH["paths"])
    assert under(BENCH["command"][1])
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files) and all(under(f) for f in files)
    for path in BENCH["paths"]:
        for folder, _, names in os.walk(os.path.join(loader.ROOT, path)):
            if "__pycache__" in folder:
                continue
            for name in names:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", name), os.path.join(folder, name)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads_with_its_files_and_readers(cell):
    loaded = loader.load_cell(cell)
    assert loaded.config["name"] == loaded.config_name
    assert loaded.overrides and all("=" in o for o in loaded.overrides)
    assert os.path.exists(
        os.path.join(loader.ROOT, "benchmarks", "drivers", loaded.driver + ".py")
    )
    assert loaded.config["reduced"] == next(
        c["reduced"] for c in BENCH["configs"] if c["name"] == loaded.config_name
    )
    readers = loader.load_readers("per_layer", cell)
    assert readers and all(callable(read) for _, read in readers)
    e2e = loader.load_readers("end_to_end", cell)
    assert all(callable(read) for _, read in e2e)
    names = [entry["name"] for entry, _ in e2e]
    assert "setup_s" in names and len(names) >= 2
    # The configuration names its plain reference, a file of the benchmark.
    reference = loader.load_reference(loaded.reference)
    assert callable(getattr(reference, "check_after", None)) or callable(
        getattr(reference, "check_before", None)
    )


def test_no_reader_file_is_without_an_entry():
    """A reader nobody lists measures nothing: every file under end_to_end/
    and layer_metrics/ is a metric of BENCHMARK.json."""
    for kind, folder in loader.READER_DIRS.items():
        files = {
            name[:-3] for name in os.listdir(os.path.join(loader.ROOT, "benchmarks", folder))
            if name.endswith(".py")
        }
        assert files == {m["name"] for m in BENCH[kind]}, kind
