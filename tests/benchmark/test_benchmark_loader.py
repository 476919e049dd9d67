"""Driven by data: a configuration with its reference, a traffic mix, a cell,
a driver, an end-to-end metric and a per-layer metric added as NEW files plus
BENCHMARK.json entries are picked up with no edit to a file that was there —
by the loader, and by `run_cell` and `build_result`, which run the new cell
with a stub system standing where a trainer would."""

import json
import os
import shutil
import time

import pytest

import _paths  # noqa: F401
from benchmarks.harness import cell_runner, loader

DUMMY_DRIVER = '''
import threading, time

def run(ctx):
    """A stub system: a tick every 20 ms until the harness stops it."""
    stop = threading.Event()
    ctx.stop = stop.set  # this system's own graceful stop
    ctx.placement = {"platforms": [ctx.cell.spec["platform"]], "device_ids": [0]}
    steps = 0
    while not stop.wait(0.02):
        steps += 100
        ctx.train.append((len(ctx.clock.ticks), {"loss": 0.5}))
        ctx.clock.tick(steps)
    ctx.health = {"skipped_updates": 0}
    ctx.shapes = {"answer": 42.0}
'''
DUMMY_REFERENCE = '''
def check_before(ctx):
    return {"dummy_table": (0.0, 1e-6)}

def check_after(ctx):
    spec = ctx.cell.config["reference"]
    return {"dummy_output": (float(spec["error"]), float(spec["tol"]))}
'''


@pytest.fixture()
def grown(tmp_path):
    """A copy of the benchmark's data with one of everything added."""
    root = tmp_path / "checkout"
    shutil.copytree(
        os.path.join(loader.ROOT, "benchmarks"), root / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    before = {
        os.path.relpath(os.path.join(folder, name), root): os.path.getmtime(os.path.join(folder, name))
        for folder, _, names in os.walk(root) for name in names
    }
    bench = loader.load_benchmark()
    base = root / "benchmarks"
    (base / "configs" / "dummy_cfg.json").write_text(json.dumps({
        "name": "dummy_cfg", "driver": "dummy_driver", "overrides": ["env=dummy"], "reduced": [],
        "reference": {"module": "dummy_reference", "error": 0.01, "tol": 0.02},
    }))
    (base / "traffic" / "dummy_mix.json").write_text(json.dumps({"overrides": ["arch.total_num_envs=7"]}))
    (base / "workloads" / "dummy_cell.json").write_text(json.dumps({"warmup_ticks": 3, "platform": "cpu"}))
    (base / "drivers" / "dummy_driver.py").write_text(DUMMY_DRIVER)
    (base / "references" / "dummy_reference.py").write_text(DUMMY_REFERENCE)
    (base / "end_to_end" / "dummy_e2e.py").write_text("def read(ctx):\n    return 7.0\n")
    (base / "layer_metrics" / "dummy_metric.py").write_text(
        "def read(ctx):\n    return ctx.shapes.get('answer')\n"
    )
    bench["configs"].append({
        "name": "dummy_cfg", "source": "https://example.org/dummy",
        "file": "benchmarks/configs/dummy_cfg.json", "reduced": [], "why": "dummy",
    })
    bench["workloads"].append({
        "name": "dummy_cell", "config": "dummy_cfg", "traffic": "dummy_mix", "chips": 1, "why": "dummy",
    })
    bench["end_to_end"].append({
        "name": "dummy_e2e", "unit": "ms", "better": "lower", "bound": 0.05, "source": "host_clock",
        "workloads": ["dummy_cell"],
    })
    bench["per_layer"].append({
        "name": "dummy_metric", "unit": "count", "better": "higher", "source": "program_counter",
        "layer": "Dummy", "moves": "dummy_e2e", "workloads": ["dummy_cell"],
    })
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root), before


def _unchanged(root, before):
    return {rel: os.path.getmtime(os.path.join(root, rel)) for rel in before} == before


def test_new_files_are_found_and_nothing_old_was_edited(grown):
    root, before = grown
    cell = loader.load_cell("dummy_cell", root)
    assert cell.config_name == "dummy_cfg" and cell.traffic_name == "dummy_mix"
    assert cell.overrides == ["env=dummy", "arch.total_num_envs=7"]
    assert cell.spec["warmup_ticks"] == 3 and cell.driver == "dummy_driver"
    assert cell.reference == "dummy_reference" and cell.root == root
    assert callable(loader.load_driver(cell.driver, root).run)
    assert callable(loader.load_reference(cell.reference, root).check_after)
    layer = {entry["name"] for entry, _ in loader.load_readers("per_layer", "dummy_cell", root)}
    e2e = {entry["name"] for entry, _ in loader.load_readers("end_to_end", "dummy_cell", root)}
    # Metrics restricted to other cells do not apply; unrestricted ones do.
    assert "dummy_metric" in layer and "device_idle_share" in layer
    assert "sebulba_queue_wait_share" not in layer
    assert e2e == {"env_steps_per_s", "setup_s", "dummy_e2e"}
    assert _unchanged(root, before)


@pytest.fixture()
def one_cpu_device(monkeypatch):
    """The test session has eight virtual CPU devices; the dummy cell asks
    for one chip."""
    import jax

    monkeypatch.setattr(cell_runner, "_gate_devices", lambda cell, platform: jax.devices()[:1])


def test_a_new_cell_runs_through_run_cell_and_build_result(grown, one_cpu_device):
    """The whole path a real cell takes, with the stub system: the clock, the
    stop, the new reference's own tolerance, the new end-to-end reader."""
    root, before = grown
    cell = loader.load_cell("dummy_cell", root)
    result = cell_runner.run_cell(cell, 1, 0.4, False, time.perf_counter(), require_platform="cpu")
    assert result["correct"], result["problems"]
    assert set(result["metrics"]) == {"env_steps_per_s", "setup_s", "dummy_e2e"}
    assert result["metrics"]["dummy_e2e"] == {"value": 7.0, "unit": "ms"}
    # 100 steps every 20 ms or a little more, on one chip.
    assert 1000.0 < result["metrics"]["env_steps_per_s"]["value"] <= 5000.0
    assert result["attempted"] >= 5 and result["failed"] == 0
    assert result["detail"]["errors"] == {"dummy_table": 0.0, "dummy_output": 0.01}
    assert result["detail"]["tolerances"] == {"dummy_table": 1e-6, "dummy_output": 0.02}
    assert result["detail"]["exit_after_interval_s"] < 1.0
    assert _unchanged(root, before)


def test_a_new_cells_traced_run_reads_its_own_per_layer_metric(grown, one_cpu_device, tmp_path):
    root, _ = grown
    cell = loader.load_cell("dummy_cell", root)
    scratch = tmp_path / "traces"
    result = cell_runner.run_cell(
        cell, 1, 0.4, True, time.perf_counter(), require_platform="cpu", scratch_dir=str(scratch),
    )
    assert result["metrics"]["dummy_metric"] == {"value": 42.0, "unit": "count"}
    assert "env_steps_per_s" not in result["metrics"]
    # The older readers found nothing of theirs in this cell and said nothing;
    # a CPU trace has no device plane, and a run without device ops is refused.
    assert set(result["metrics"]) <= {"dummy_metric", "compile_s", "cache_misses", "peak_hbm_mib"}
    assert result["problems"] == ["no operation ran on the device in the traced window"]
    assert not os.listdir(scratch)  # the trace is removed once reduced


@pytest.mark.parametrize("reference,problem", [
    ({"module": "dummy_reference", "error": 0.05, "tol": 0.02}, "reference dummy_output: error 5.000e-02 > 2.0e-02"),
    (None, "the configuration names no reference"),
])
def test_a_new_cell_is_held_to_its_own_reference(grown, one_cpu_device, reference, problem):
    root, _ = grown
    cell = loader.load_cell("dummy_cell", root)
    cell = cell._replace(config={**cell.config, "reference": reference})
    result = cell_runner.run_cell(cell, 1, 0.3, False, time.perf_counter(), require_platform="cpu")
    assert not result["correct"]
    assert any(problem in p for p in result["problems"]), result["problems"]


def test_an_end_to_end_metric_without_a_value_is_a_problem(grown, one_cpu_device):
    root, _ = grown
    with open(os.path.join(root, "benchmarks", "end_to_end", "dummy_e2e.py"), "w") as handle:
        handle.write("def read(ctx):\n    return None\n")
    cell = loader.load_cell("dummy_cell", root)
    result = cell_runner.run_cell(cell, 1, 0.3, False, time.perf_counter(), require_platform="cpu")
    assert "dummy_e2e" not in result["metrics"] and not result["correct"]
    assert "end-to-end metric dummy_e2e has no value" in result["problems"]


def test_old_cells_are_untouched_by_the_additions(grown):
    root, _ = grown
    for entry in loader.load_benchmark()["workloads"]:
        for kind in ("end_to_end", "per_layer"):
            names = [e["name"] for e, _ in loader.load_readers(kind, entry["name"], root)]
            assert "dummy_metric" not in names and "dummy_e2e" not in names


def test_unknown_names_say_what_is_known():
    with pytest.raises(KeyError, match="known: "):
        loader.load_cell("no_such_cell")


# --------------------------------------------------------------------------
# A traced run whose profiler loses a program boundary (PR 27): the stub
# system, faked sessions, and the synthetic traces of _loop_trace.py
# --------------------------------------------------------------------------


@pytest.fixture()
def scripted_traces(monkeypatch):
    """`script` lists what each session's trace holds: (windows, lost)."""
    from _loop_trace import loop_trace
    from benchmarks.harness import trace_capture, trace_reduce

    script, made = [], []

    def stop(session, path):
        with open(path, "w") as handle:
            handle.write(str(session))

    def read_xplane(path, host_names=None):
        with open(path) as handle:
            return loop_trace(*script[int(handle.read())])

    monkeypatch.setattr(trace_capture, "start", lambda: made.append(len(made)) or made[-1])
    monkeypatch.setattr(trace_capture, "stop", stop)
    monkeypatch.setattr(trace_reduce, "read_xplane", read_xplane)
    return script


def _traced_stub_run(root, tmp_path):
    """The stub cell's files name no number of sessions, as no cell's do:
    the cap is trace_capture's own."""
    cell = loader.load_cell("dummy_cell", root)
    programs = {"learn": ["learner_fn"], "eval": ["_shard_eval"]}
    spec = {**cell.spec, "trace_start_tick": 2, "trace_ticks": 2}
    config = {**cell.config, "programs": programs, "scopes": {"update": "ppo_epoch"}}
    cell = cell._replace(config=config, spec=spec)
    return cell_runner.run_cell(
        cell, 1, 0.1, True, time.perf_counter(), require_platform="cpu", scratch_dir=str(tmp_path / "t"),
    )


WINDOW_SHARES = {"eval_device_share", "learn_device_share", "device_idle_share"}


@pytest.mark.parametrize("script,made,used,whole,sound", [
    ([(2, None)], 1, 0, 1, True),  # nothing lost: one session, as before
    ([(2, "first"), (2, None)], 2, 1, 1, True),  # the one whole execution lost: a second session
    ([(2, "last"), (2, None)], 1, 0, 1, True),  # lost at the very end: a shorter session, sound
    ([(2, "first"), (2, "first"), (2, "last")], 3, 2, 1, True),  # the third time lucky
    ([(2, "first"), (3, "first"), (2, "first")], 3, 1, 1, False),  # none clean: the one with a whole execution
    ([(2, "first"), (2, "first"), (2, "first"), (2, None)], 3, 0, 0, False),  # three and no more: nothing whole
])
def test_a_traced_run_survives_a_lost_boundary(grown, one_cpu_device, scripted_traces, tmp_path,
                                               script, made, used, whole, sound):
    root, _ = grown
    scripted_traces.extend(script)
    result = _traced_stub_run(root, tmp_path)
    report = result["trace"]
    assert (report["sessions"], report["used"], report["whole_learner_executions"]) == (made, used, whole)
    assert (report["sound"], report["window_sound"], report["chips_traced"]) == (sound, sound, 1)
    assert result["detail"]["health"]["trace"] == report  # the same on stderr
    assert len(report["candidates"]) == made
    assert report["raw_window_s"] == pytest.approx((1100 * script[used][0]) * 1e-12)
    assert 0.0 < result["device"]["busy_s"] <= result["device"]["window_s"]
    names = WINDOW_SHARES | {"update_share", "dummy_metric"}
    if sound:
        assert result["correct"], result["problems"]
        assert names <= set(result["metrics"])
        assert result["metrics"]["update_share"]["value"] == pytest.approx(30.0)
    else:
        # A damaged window is never read as if whole: its shares are left out
        # and the run says so; what whole executions give is kept.
        unreadable = [0.0] * made
        sessions = f"{made} session(s) of 2 ticks, unreadable seconds {unreadable}"
        assert result["problems"] == ([] if whole else [
            f"no whole learner execution could be read: {sessions}, the per-layer metrics that need one are left out"
        ]) + [
            f"the profiler damaged the traced window: lost_inside_s 0.0000 of 0.0000 s, whole learner "
            f"executions on the chip with fewest {whole} ({sessions}), the window's shares are left out"
        ]
        assert not WINDOW_SHARES & set(result["metrics"]) and "dummy_metric" in result["metrics"]
        assert ("update_share" in result["metrics"]) == bool(whole)
        if whole:
            assert result["metrics"]["update_share"]["value"] == pytest.approx(30.0)
    if script[used][1] is None:
        assert result["metrics"]["eval_device_share"]["value"] == pytest.approx(100.0 * 200 / 2200)
        assert report["unreadable_s"] == 0.0 and report["unnamed_op_events"] == 0
    else:
        assert result["breakdown"]["device_ops"][0][0].startswith("unreadable")
        assert not any("region" in label for label, _ in result["breakdown"]["device_ops"])
    # The stop was held back until the last session had closed: sessions of
    # two 20 ms ticks with a tick between, from tick 2, in a 0.1 s interval.
    if made > 1:
        assert result["detail"]["exit_after_interval_s"] > 0.02 * (3 * made + 1) - 0.1 - 0.02
    assert not os.listdir(tmp_path / "t")
