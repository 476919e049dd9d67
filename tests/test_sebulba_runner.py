"""One Sebulba runner (stoix_tpu/sebulba/runner.py, docs/DESIGN.md §3): every
system file hands it a record and keeps no host loop of its own, and the
three batch sources (stoix_tpu/sebulba/sources.py) answer one calling
convention — the next batch, whether it is fresh, the new env steps it
consumed — checked here against fakes. (The loop itself runs in
tests/test_sebulba.py, test_tracing.py, test_replay.py, test_resilience.py.)"""

import ast
import importlib
import pathlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stoix_tpu.sebulba import runner, sources
from stoix_tpu.utils import config as config_lib
from stoix_tpu.utils.timing import TimingTracker

ENTRY_POINTS = {
    "ppo": ("stoix_tpu.systems.ppo.sebulba.ff_ppo", "default_ff_ppo", []),
    "ppo_impact": (
        "stoix_tpu.systems.ppo.sebulba.ff_ppo", "default_ff_ppo", ["system.impact.enabled=true"]
    ),
    "impala": ("stoix_tpu.systems.impala.sebulba.ff_impala", "default_ff_impala", []),
    "impala_shared_torso": (
        "stoix_tpu.systems.impala.sebulba.ff_impala_shared_torso",
        "default_ff_impala_shared_torso", [],
    ),
    "dqn": ("stoix_tpu.systems.q_learning.sebulba.ff_dqn", "default_ff_dqn", []),
}
HOST_LOOP_PARTS = {"Thread", "Queue", "GoodputLedger", "StoixLogger", "PreemptionHandler"}


@pytest.mark.parametrize("entry", sorted(ENTRY_POINTS))
def test_a_system_file_hands_the_runner_a_record_and_keeps_no_loop(entry, monkeypatch):
    module_name, yaml, overrides = ENTRY_POINTS[entry]
    module = importlib.import_module(module_name)
    tree = ast.parse(pathlib.Path(module.__file__).read_text())
    made = {
        getattr(node.func, "attr", getattr(node.func, "id", None))
        for node in ast.walk(tree) if isinstance(node, ast.Call)
    }
    assert not made & HOST_LOOP_PARTS, made & HOST_LOOP_PARTS
    family = module_name.split(".")[2]
    imported = [
        node.module for node in ast.walk(tree)
        if isinstance(node, ast.ImportFrom) and node.module
    ] + [a.name for node in ast.walk(tree) if isinstance(node, ast.Import) for a in node.names]
    other_systems = [
        name for name in imported
        if name.startswith("stoix_tpu.systems.") and name.split(".")[2] != family
    ]
    assert not other_systems, other_systems

    # What it hands over is the runner's record, and its stats are the runner's.
    handed = []
    monkeypatch.setattr(
        runner, "run_experiment", lambda config, system: handed.append(system) or 0.0
    )
    config = config_lib.compose(
        config_lib.default_config_dir(), f"default/sebulba/{yaml}.yaml", overrides
    )
    assert module.run_experiment(config) == 0.0
    assert isinstance(handed[0], runner.SebulbaSystem)
    assert module.LAST_RUN_STATS is runner.LAST_RUN_STATS


class _Ledger:
    def __init__(self):
        self.noted = []

    def note(self, kind, seconds):
        self.noted.append(kind)


class _ParamServer:
    version = 5

    def __init__(self):
        self.lags = []

    def observe_policy_lag(self, behavior_version):
        self.lags.append(self.version - behavior_version)


def _payload(value):
    """One actor's payload: a leaf is the list of the (one) learner device's
    `[T, E]` slice."""
    return {"x": [jnp.full((2, 3), value, jnp.float32)]}


def _ctx(num_actors=2, steps_per_update=12):
    device = jax.devices()[0]
    mesh = jax.sharding.Mesh(np.asarray([device]), ("data",))
    return sources.SourceContext(
        num_actors, [device], mesh, None, TimingTracker(), _Ledger(), steps_per_update
    )


class _Scripted:
    """A pipeline that hands out scripted lists, one a call."""

    def __init__(self, scripted):
        self.scripted, self.heartbeats = list(scripted), None

    def collect_rollouts(self):
        return self.scripted.pop(0)

    def poll(self, max_items=64, timeout=0.0):
        return self.scripted.pop(0) if self.scripted else []

    def wait_for_data(self, timeout=180.0):
        items = self.poll()
        assert items, "the learner blocked with no scripted data"
        return items


def _learn_step(state, *operands):
    return state, {"operands": operands}


def _on_policy():
    ctx = _ctx()
    source = sources.OnPolicySource(ctx)
    source.pipeline = _Scripted([[(4, _payload(1.0)), (3, _payload(2.0))]] * 2)
    server = _ParamServer()
    batches = [source.next_batch(i, server) for i in range(2)]
    # Every consumed rollout's lag is gauged; both payloads tile the env axis.
    assert server.lags == [1, 2, 1, 2] and batches[0].data["x"].shape == (2, 6)
    assert ctx.ledger.noted == ["queue_wait", "compute"] * 2
    state, metrics = source.step(_learn_step, "state", batches[0])
    assert state == "state" and metrics["operands"] == (batches[0].data,)
    assert source.run_stats() == {"impact": None} and source.observe() == {}
    return batches, [True, True], [12, 12]


def _impact():
    ctx = _ctx()
    settings = sources.ImpactSettings(
        target_update_interval=2, rho_clip=2.0, max_staleness=4, max_reuse=1, buffer_size=2
    )
    source = sources.ImpactSource(ctx, settings)
    source._ingest._pipeline = _Scripted(
        [[(0, (4, _payload(1.0))), (1, (5, _payload(2.0)))], [], [(0, (5, _payload(3.0)))],
         [(1, (5, _payload(4.0)))]]
    )
    server = _ParamServer()
    batches = []
    # The target params are the learn step's second operand: the first
    # state's until the refresh after every second update.
    for update_idx, target in enumerate(["p0", "p0", "p2"]):
        batches.append(source.next_batch(update_idx, server))
        state = types.SimpleNamespace(params=f"p{update_idx}")
        _, metrics = source.step(_learn_step, state, batches[-1])
        assert metrics["operands"] == (target, batches[-1].data)
        source.after_update(types.SimpleNamespace(params=f"p{update_idx + 1}"))
    assert batches[1].data is batches[0].data  # fresh was late: the buffered batch again
    assert source._target_params == "p2"
    stats = source.run_stats()["impact"]
    assert (stats["updates"], stats["fresh_updates"], stats["reused_updates"]) == (3, 2, 1)
    assert stats["target_refreshes"] == 1 and stats["max_staleness_seen"] == 1
    assert stats["mean_staleness"] == pytest.approx((1 + 1 + 0) / 3)
    return batches, [True, False, True], [12, 0, 12]


class _Service:
    """The replay service's host side: counts what is added, can sample once
    eight items are in."""

    def __init__(self):
        self.items, self.state, self.committed, self.sample_ops = 0, "replay0", [], 0

    def add(self, batch):
        assert batch["info"] == {}
        self.items += batch["x"].shape[0]

    def can_sample(self):
        return self.items >= 8

    def stats(self):
        return {"added_items": self.items, "sample_ops": self.sample_ops}

    def commit(self, new_state):
        self.state = new_state
        self.committed.append(new_state)

    def note_embedded_samples(self, ops):
        self.sample_ops += ops

    def observe(self):
        return {"fill": 0.5, "per_shard": [1, 2]}


def _replay():
    ctx = _ctx()
    service = _Service()
    source = sources.ReplaySource(ctx, service=service, epochs=4, param_sync_interval=3)
    flat = lambda value: {"x": [jnp.full((6,), value, jnp.float32)], "info": {}}  # noqa: E731
    source.pipeline = _Scripted(
        # Update 0: one chunk polled, not enough to sample: waits for the
        # second. Update 1: nothing new. Update 2: one more chunk.
        [[(0, flat(1.0))], [(1, flat(2.0))], [], [(0, flat(3.0))]]
    )
    batches = []
    for update_idx in range(3):
        batches.append(source.next_batch(update_idx, _ParamServer()))
        assert batches[-1].data == service.state
        state, metrics = source.step(
            lambda state, replay: (state + 1, f"replay{update_idx + 1}", {"q_loss": 0.0}),
            update_idx, batches[-1],
        )
        assert state == update_idx + 1 and metrics == {"q_loss": 0.0}
    assert service.committed == ["replay1", "replay2", "replay3"] and service.sample_ops == 12
    assert ctx.ledger.noted == ["queue_wait"] * 3 and source.param_sync_interval == 3
    assert source.observe() == {"replay_fill": 0.5}
    assert source.run_stats() == {"replay": {"added_items": 18, "sample_ops": 12}}
    return batches, [True, True, True], [12, 0, 6]


@pytest.mark.parametrize("drive", [_on_policy, _impact, _replay], ids=lambda f: f.__name__[1:])
def test_a_source_says_what_is_fresh_and_how_many_env_steps_it_consumed(devices, drive):
    batches, fresh, env_steps = drive()
    assert [b.fresh for b in batches] == fresh
    assert [b.env_steps for b in batches] == env_steps
