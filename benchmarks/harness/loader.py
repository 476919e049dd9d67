"""Finds a cell's files by the names in BENCHMARK.json. Nothing here knows a
cell, a configuration, a traffic mix, a driver or a per-layer metric by name:
a later PR adds one by adding its file and its BENCHMARK.json entry.

    benchmarks/configs/<config>.json          sizes, system module, driver, reference, source
    benchmarks/traffic/<traffic>.json         the traffic mix: override strings
    benchmarks/workloads/<cell>.json          warm-up, traced ticks, learn_check
    benchmarks/drivers/<driver>.py            run(ctx) for one architecture
    benchmarks/references/<reference>.py      the configuration's plain reference and its
                                              tolerances: check_before(ctx), check_after(ctx)
                                              -> {name: (error, tolerance)}, either optional
    benchmarks/end_to_end/<metric>.py         read(ctx) -> float | None
    benchmarks/layer_metrics/<metric>.py      read(ctx) -> float | None
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = "benchmarks"


class Cell(NamedTuple):
    name: str
    chips: int
    why: str
    config_name: str
    traffic_name: str
    config: Dict[str, Any]  # benchmarks/configs/<config>.json
    traffic: Dict[str, Any]  # benchmarks/traffic/<traffic>.json
    spec: Dict[str, Any]  # benchmarks/workloads/<cell>.json
    root: str = ROOT  # the checkout the files were found in

    @property
    def driver(self) -> str:
        return str(self.config["driver"])

    @property
    def reference(self) -> Optional[str]:
        """The name of the configuration's reference file, if it names one."""
        module = (self.config.get("reference") or {}).get("module")
        return None if module is None else str(module)

    @property
    def overrides(self) -> List[str]:
        """Config overrides first (env, widths), then the traffic's."""
        return list(self.config.get("overrides", [])) + list(self.traffic["overrides"])


def _read_json(path: str) -> Dict[str, Any]:
    with open(path, "r", encoding="utf-8") as handle:
        data = json.load(handle)
    if not isinstance(data, dict):
        raise ValueError(f"{path}: expected a JSON object")
    return data


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def _entry(entries: List[Dict[str, Any]], name: str, what: str) -> Dict[str, Any]:
    for entry in entries:
        if entry["name"] == name:
            return entry
    known = ", ".join(e["name"] for e in entries)
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json (known: {known})")


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    entry = _entry(bench["workloads"], name, "workload")
    config_entry = _entry(bench["configs"], entry["config"], "config")
    base = os.path.join(root, BENCH_DIR)
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        why=entry["why"],
        config_name=entry["config"],
        traffic_name=entry["traffic"],
        config=_read_json(os.path.join(root, config_entry["file"])),
        traffic=_read_json(os.path.join(base, "traffic", entry["traffic"] + ".json")),
        spec=_read_json(os.path.join(base, "workloads", name + ".json")),
        root=root,
    )


def _load_module(path: str, module_name: str) -> Any:
    spec = importlib.util.spec_from_file_location(module_name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_driver(name: str, root: str = ROOT) -> Any:
    path = os.path.join(root, BENCH_DIR, "drivers", name + ".py")
    return _load_module(path, f"_bench_driver_{name}")


def load_reference(name: str, root: str = ROOT) -> Any:
    path = os.path.join(root, BENCH_DIR, "references", name + ".py")
    return _load_module(path, f"_bench_reference_{name}")


def metrics_for(kind: str, cell_name: str, root: str = ROOT) -> List[Dict[str, Any]]:
    """The `end_to_end` or `per_layer` entries that apply to this cell."""
    return [
        entry
        for entry in load_benchmark(root)[kind]
        if "workloads" not in entry or cell_name in entry["workloads"]
    ]


READER_DIRS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


def load_readers(
    kind: str, cell_name: str, root: str = ROOT
) -> List[Tuple[Dict[str, Any], Callable[[Any], Optional[float]]]]:
    """(BENCHMARK.json entry, reader) for every `end_to_end` or `per_layer`
    metric of the cell. The reader is `read(ctx)` of the metric's own file,
    benchmarks/end_to_end/<name>.py or benchmarks/layer_metrics/<name>.py; a
    reader that finds nothing to read returns None."""
    readers = []
    for entry in metrics_for(kind, cell_name, root):
        path = os.path.join(root, BENCH_DIR, READER_DIRS[kind], entry["name"] + ".py")
        module = _load_module(path, f"_bench_{kind}_{entry['name']}")
        readers.append((entry, module.read))
    return readers
