"""`sebulba_actor_prepare_ms` (PR 28), on a stub context as
test_benchmark_tracing_readers.py tests its siblings: the actors'
`prepare_data` MEDIANS in the MISC log events inside the interval, and
nothing on a program that logs none."""

import types

import pytest

import _paths  # noqa: F401
from benchmarks.harness import loader

CELL = "sebulba_ppo_cartpole_1chip"


def reader(name):
    readers = loader.load_readers("per_layer", CELL)
    return dict((entry["name"], read) for entry, read in readers)[name]


def misc_ctx(misc):
    return types.SimpleNamespace(clock=types.SimpleNamespace(start=100.0, seconds=30.0), misc=misc)


@pytest.mark.parametrize("misc,expected_ms", [
    # Two actors, two events inside the interval; set-up and what follows
    # the interval are left out.
    ([(90.0, {"actor0_prepare_data_p50": 9.0}),
      (110.0, {"actor0_prepare_data_p50": 0.060, "actor1_prepare_data_p50": 0.064,
               "actor0_prepare_data_time": 3.1, "actor0_rollout_time": 2.9}),
      (120.0, {"actor0_prepare_data_p50": 0.062, "actor1_prepare_data_p50": 0.062}),
      (140.0, {"actor0_prepare_data_p50": 7.0})], 62.0),
    # The rolling MEAN still holds the first rollouts' compilation when the
    # interval begins: it is not what is read.
    ([(105.0, {"actor0_prepare_data_time": 1.88, "actor0_prepare_data_p50": 0.68})], 680.0),
    # A program that logs no such timer: nothing to read, nothing raised.
    ([(110.0, {"actor0_rollout_time": 2.9, "learner_learn_time": 2.1})], None),
    ([], None),
])
def test_prepare_reader_is_the_mean_of_the_actors_medians_inside_the_interval(misc, expected_ms):
    value = reader("sebulba_actor_prepare_ms")(misc_ctx(misc))
    assert value is None if expected_ms is None else value == pytest.approx(expected_ms)


def test_prepare_metric_is_declared_for_the_sebulba_cell_alone():
    entry = {m["name"]: m for m in loader.load_benchmark()["per_layer"]}["sebulba_actor_prepare_ms"]
    assert entry["workloads"] == [CELL] and entry["moves"] == "env_steps_per_s"
    assert entry["layer"] == "Sebulba host loop" and entry["source"] == "program_span"
    assert (entry["unit"], entry["better"]) == ("ms", "lower")
