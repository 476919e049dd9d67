"""The run host (stoix_tpu/run_host.py, docs/DESIGN.md §2.16): what both
runners open on the host, the order it comes up in, and that it goes down
whatever happened — set-up included. The contract the two runners used to
carry in comments, as cases over both architectures."""

import contextlib
import signal
import sys

import pytest

from stoix_tpu import run_host
from stoix_tpu.observability import get_health_monitor, goodput
from stoix_tpu.sebulba import runner as sebulba_runner
from stoix_tpu.systems import runner as anakin_runner
from stoix_tpu.systems.ppo.anakin import ff_ppo as anakin_ppo
from stoix_tpu.systems.ppo.sebulba import ff_ppo as sebulba_ppo
from stoix_tpu.utils import compilecache
from stoix_tpu.utils import config as config_lib

ARCHITECTURES = ("anakin", "sebulba")
BOARDS = {"anakin": "anakin-host-loop", "sebulba": "sebulba-pipeline"}
TINY = {
    "anakin": ("default/anakin/default_ff_ppo.yaml", [
        "env=identity_game", "arch.total_num_envs=16", "arch.total_timesteps=~",
        "arch.num_updates=4", "arch.num_evaluation=2", "arch.num_eval_episodes=8",
        "system.rollout_length=4", "system.epochs=1", "system.num_minibatches=2",
        "arch.absolute_metric=False", "logger.use_console=False",
    ]),
    "sebulba": ("default/sebulba/default_ff_ppo.yaml", [
        "env=cartpole", "env.backend=cvec", "arch.total_num_envs=16",
        "arch.actor.device_ids=[0]", "arch.actor.actor_per_device=2",
        "arch.learner.device_ids=[0]", "arch.evaluator_device_id=0",
        "arch.total_timesteps=~", "arch.num_updates=4", "arch.num_evaluation=2",
        "arch.num_eval_episodes=4", "system.rollout_length=8", "system.epochs=1",
        "system.num_minibatches=2", "logger.use_console=False",
    ]),
}
HARDENED = ["arch.fleet.enabled=True", "arch.integrity.enabled=True"]


class _Boom(Exception):
    """What an injected failure raises."""


def _config(architecture, extra=()):
    root, overrides = TINY[architecture]
    return config_lib.compose(config_lib.default_config_dir(), root, overrides + list(extra))


def _boom(*args, **kwargs):
    raise _Boom()


def _run(architecture, config, set_up=None):
    """The architecture's PPO through its runner; `set_up` stands in for the
    system's own set-up function (Anakin: `learner_setup`; Sebulba: the
    learn step's builder, which `setup_learner` calls)."""
    if architecture == "anakin":
        return anakin_runner.run_anakin_experiment(config, set_up or anakin_ppo.learner_setup)
    return sebulba_ppo.run_experiment(config, learn_step_builder=set_up)


class _Surroundings:
    """What a run must leave as it found it."""

    def __init__(self):
        self.sigterm, self.excepthook = signal.getsignal(signal.SIGTERM), sys.excepthook

    def check(self, architecture):
        assert goodput.get_active() is None
        assert BOARDS[architecture] not in get_health_monitor().verdict()[1]
        assert signal.getsignal(signal.SIGTERM) is self.sigterm
        assert sys.excepthook is self.excepthook


@contextlib.contextmanager
def _counting_closes(patch):
    """Counts `RunHost.close` calls (and lets them through)."""
    closes, close = [], run_host.RunHost.close

    def counting(self):
        closes.append(self.architecture)
        close(self)

    patch.setattr(run_host.RunHost, "close", counting)
    yield closes


class _FakeFleet:
    def __init__(self, events):
        self.events = events

    def start(self):
        self.events.append("fleet.start")

    def stop(self):
        self.events.append("fleet.stop")


class _FakeSentinel:
    def __init__(self, events):
        self.events = events

    def deactivate(self):
        self.events.append("sentinel.deactivate")


@pytest.fixture(scope="module", params=ARCHITECTURES)
def failed_set_up(request, devices):
    """One run an architecture whose system's set-up function raises, with
    recording stubs around what the host opens before it and fakes for the
    fleet and the sentinel. No learner is built and nothing runs on a device
    beyond the runner's own key."""
    architecture, events = request.param, []

    def recording(name, fn):
        def wrapper(*args, **kwargs):
            events.append(name)
            return fn(*args, **kwargs)
        return wrapper

    def set_up(*args, **kwargs):
        events.append("set_up")
        raise _Boom()

    surroundings = _Surroundings()
    with pytest.MonkeyPatch.context() as patch:
        for module, name in (
            (run_host.faultinject, "configure"),
            (run_host.compilecache, "configure"),
            (run_host.scan_kernels, "configure_from_config"),
        ):
            label = f"{module.__name__.rsplit('.', 1)[-1]}.{name}"
            patch.setattr(module, name, recording(label, getattr(module, name)))
        patch.setattr(run_host.fleet, "fleet_from_config", lambda config: _FakeFleet(events))
        patch.setattr(
            run_host.integrity, "sentinel_from_config", lambda config: _FakeSentinel(events)
        )
        with _counting_closes(patch) as closes, pytest.raises(_Boom):
            _run(architecture, _config(architecture), set_up)
        surroundings.check(architecture)
    return architecture, events, closes


@pytest.fixture(scope="module", params=ARCHITECTURES)
def finished_run(request, devices, tmp_path_factory):
    """One tiny run an architecture that reaches its end, with the fleet
    (one process: no thread) and the integrity sentinel on."""
    architecture = request.param
    surroundings = _Surroundings()
    with pytest.MonkeyPatch.context() as patch:
        patch.chdir(tmp_path_factory.mktemp(f"run_host_{architecture}"))
        with _counting_closes(patch) as closes:
            _run(architecture, _config(architecture, HARDENED))
        surroundings.check(architecture)
    module = anakin_runner if architecture == "anakin" else sebulba_runner
    return architecture, dict(module.LAST_RUN_STATS), closes


def test_the_cache_and_the_fault_plan_are_configured_before_set_up(failed_set_up):
    _, events, _ = failed_set_up
    before = events[: events.index("set_up")]
    # The fault plan before anything is traced, the cache before the first
    # compile, the scan-kernel default before a learner is traced.
    assert before[:3] == [
        "faultinject.configure", "compilecache.configure", "scan_kernels.configure_from_config",
    ], events
    assert "fleet.start" in before


def test_the_sentinel_is_deactivated_before_the_fleet_stops(failed_set_up):
    _, events, _ = failed_set_up
    after = events[events.index("set_up") + 1:]
    assert after == ["sentinel.deactivate", "fleet.stop"], events


def test_a_set_up_that_raises_is_closed_once(failed_set_up):
    architecture, _, closes = failed_set_up
    assert closes == [architecture]


def _fail_anakin_warm_up(patch):
    patch.setattr(compilecache, "warmup_with_export", _boom)


def _fail_anakin_learn(patch):
    # The first statement of the loop after its stop handling is armed; the
    # warm-up hands the learner back uncompiled, so the case costs no compile.
    patch.setattr(
        compilecache, "warmup_with_export",
        lambda fn, args, export_dir, name: (fn, {"source": "compile", "export_path": None}),
    )
    patch.setattr(anakin_runner.faultinject, "maybe_host_stall", _boom)


def _fail_sebulba_threads(patch):
    # After the evaluator's thread started and the board went up, before the
    # first actor.
    patch.setattr(sebulba_runner, "supervisor_from_config", _boom)


def _fail_sebulba_learn(patch):
    patch.setattr(sebulba_runner._Run, "learn", _boom)


@pytest.mark.parametrize(
    "architecture, fail",
    [
        ("anakin", _fail_anakin_warm_up),
        ("anakin", _fail_anakin_learn),
        ("sebulba", _fail_sebulba_threads),
        ("sebulba", _fail_sebulba_learn),
    ],
    ids=["anakin-warm_up", "anakin-learn", "sebulba-threads", "sebulba-learn"],
)
def test_a_failure_after_the_host_opened_leaves_nothing_behind(
    architecture, fail, devices, monkeypatch, tmp_path
):
    """Wherever a run fails once its host is open — the warm-up, the loop,
    between two thread starts — the close is the loop's: once, and the
    process is as it was."""
    monkeypatch.chdir(tmp_path)
    surroundings = _Surroundings()
    fail(monkeypatch)
    with _counting_closes(monkeypatch) as closes, pytest.raises(_Boom):
        _run(architecture, _config(architecture))
    assert closes == [architecture]
    surroundings.check(architecture)


def test_the_close_runs_once_when_learn_returns(finished_run):
    architecture, _, closes = finished_run
    assert closes == [architecture]


@pytest.mark.parametrize("block", ["goodput", "setup_phases", "launch_phases", "integrity"])
def test_last_run_stats_hold_the_shared_blocks(finished_run, block):
    _, stats, _ = finished_run
    assert block in stats
    assert {"update_guard", "skipped_updates", "preempted", "fleet"} <= set(stats["resilience"])
    assert stats["resilience"]["fleet"] is True and stats["resilience"]["preempted"] is False
    assert stats["integrity"]["enabled"] is True


@pytest.mark.parametrize(
    "key",
    ["pipelined", "fused_eval", "compile", "phase_breakdown", "loop_wall_s",
     "steady_state_sps", "gossip"],
)
def test_the_runners_keep_their_own_stats(finished_run, key):
    architecture, stats, _ = finished_run
    if architecture == "anakin":
        assert key in stats
        assert {"resume_capable", "preflight", "fleet_agreed_stop", "restore_skipped"} <= set(
            stats["resilience"]
        )
    else:
        assert key not in stats
        assert stats["resilience"]["actor_restarts"] == 0
        assert stats["resilience"]["resume_capable"] is False


def test_an_opening_that_raises_closes_itself(monkeypatch):
    surroundings = _Surroundings()
    monkeypatch.setattr(run_host.compilecache, "configure", _boom)
    with _counting_closes(monkeypatch) as closes, pytest.raises(_Boom):
        run_host.RunHost(_config("anakin"), "anakin", BOARDS["anakin"])
    assert closes == ["anakin"]
    surroundings.check("anakin")


class _PartitionedFleet(_FakeFleet):
    partition_error = _Boom("partition")

    def __init__(self, events, partitioned):
        super().__init__(events)
        self.partition_event = type("Event", (), {"is_set": lambda self: partitioned})()

    def emergency_save(self):
        self.events.append("fleet.emergency_save")


class _FakePreempt:
    signal_name = "SIGTERM"

    def __init__(self, requested):
        self.requested = requested

    def stop_requested(self):
        return self.requested


@pytest.mark.parametrize("requested", [True, False], ids=["signalled", "quiet"])
def test_a_local_stop_request_becomes_this_hosts_vote(requested):
    """Fleet mode never stops a host alone: its signal is flagged for the
    fleet's next agreement, under the name and the place it arrived at."""
    host, votes = run_host.RunHost(_config("anakin"), "anakin", BOARDS["anakin"]), []
    try:
        host.preempt = _FakePreempt(requested)
        host.fleet = type(
            "Fleet", (), {"request_stop": lambda self, flag, note: votes.append((flag, note))}
        )()
        host.vote_to_stop("at window 3")
    finally:
        host.preempt = host.fleet = None
        host.close()
    expected = [(run_host.fleet.FLAG_PREEMPT, "SIGTERM at window 3")] if requested else []
    assert votes == expected


@pytest.mark.parametrize(
    "partitioned, rescue, raised, events",
    [
        (True, True, _Boom, ["fleet.emergency_save"]),
        (True, False, _Boom, []),
        (False, True, KeyboardInterrupt, []),
    ],
    ids=["partition-rescued", "partition", "operator-interrupt"],
)
def test_the_fleet_monitors_interrupt_becomes_the_partition_error(
    partitioned, rescue, raised, events
):
    host = run_host.RunHost(_config("anakin"), "anakin", BOARDS["anakin"])
    try:
        seen = []
        host.fleet = _PartitionedFleet(seen, partitioned)
        with pytest.raises(raised):
            with host.interrupt_as_partition(rescue=rescue):
                raise KeyboardInterrupt()
        assert seen == events
        host.fleet = None  # an operator's ^C with no fleet at all
        with pytest.raises(KeyboardInterrupt):
            with host.interrupt_as_partition(rescue=rescue):
                raise KeyboardInterrupt()
    finally:
        host.fleet = None
        host.close()
    assert goodput.get_active() is None
