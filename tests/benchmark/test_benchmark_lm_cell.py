"""The token-policy cell (`anakin_ppo_olmoe_tokens_1chip`) on the CPU at the
tiny preset: the whole path a real run takes — driver, reference
comparisons, stated-configuration checks, result line — with the cell's
configuration restated at the tiny widths; the cost functions behind its
roofline readers; and each of its readers on synthetic events."""

import time
import types

import pytest

import _paths  # noqa: F401
from benchmarks.harness import cell_runner, flops_lm, loader, peaks
from benchmarks.harness import trace_reduce as tr
from benchmarks.harness.trace_reduce import Event

CELL = "anakin_ppo_olmoe_tokens_1chip"
TINY_STATED = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "num_experts": 8, "num_experts_per_tok": 2, "intermediate_size": 32, "vocab_size": 97,
    "rollout_length": 16, "num_minibatches": 4,
}
TINY_OVERRIDES = [
    "env=token_task", "network=olmoe", "arch.evaluation_greedy=True", "system.epochs=1",
    "network.actor_network.hidden_size=64", "network.actor_network.num_heads=4",
    "network.actor_network.head_dim=16", "network.actor_network.num_experts=8",
    "network.actor_network.experts_per_token=2", "network.actor_network.expert_width=32",
    "env.kwargs.vocab_size=97", "env.kwargs.length=16", "system.rollout_length=16",
    "system.num_minibatches=4",
]
TINY_TRAFFIC = [
    "arch.total_num_envs=32", "arch.total_timesteps=~", "arch.num_updates=1000000",
    "arch.num_evaluation=1000000", "arch.num_eval_episodes=8",
]


def tiny_cell(second_reading=False, **config):
    """The cell restated at the tiny preset, data-parallel over the test
    session's virtual CPU devices (the program's mesh takes them all)."""
    import jax

    cell = loader.load_cell(CELL)._replace(chips=len(jax.devices()))
    reference = {**cell.config["reference"], "sample_sequences": 4,
                 "lower_precision_update": second_reading}
    return cell._replace(
        config={**cell.config, **TINY_STATED, "overrides": TINY_OVERRIDES, "reference": reference,
                **config},
        traffic={**cell.traffic, "overrides": TINY_TRAFFIC},
    )


@pytest.fixture()
def cpu_devices(monkeypatch):
    import jax

    monkeypatch.setattr(cell_runner, "_gate_devices", lambda cell, platform: jax.devices())


@pytest.fixture(scope="module")
def tiny_run():
    import jax
    from unittest import mock

    with mock.patch.object(cell_runner, "_gate_devices", lambda cell, platform: jax.devices()):
        return cell_runner.run_cell(
            tiny_cell(second_reading=True), 3_000_000_019, 3.0, False, time.perf_counter(), require_platform="cpu"
        )


def test_the_cell_runs_through_run_cell_and_build_result(tiny_run):
    assert tiny_run["correct"], tiny_run["problems"]
    assert set(tiny_run["metrics"]) == {"env_steps_per_s", "setup_s"}
    assert tiny_run["attempted"] >= 1 and tiny_run["failed"] == 0
    assert tiny_run["detail"]["compiles_in_interval"] == 0
    assert tiny_run["detail"]["health"]["preempted"]


@pytest.mark.parametrize("entry", ["tf", "decode"])
@pytest.mark.parametrize("name", [
    "logits_max", "logits_rms", "values_max", "values_rms", "expert_set_disagreement",
    "dropped_pairs",
])
def test_the_reference_compares_both_entry_points(tiny_run, entry, name):
    """On the CPU both sides are float32: far inside the chip's tolerances,
    expert sets identical, nothing dropped."""
    error = tiny_run["detail"]["errors"][f"{entry}_{name}"]
    assert error <= (1e-4 if name.startswith(("logits", "values")) else 0.0)
    assert tiny_run["detail"]["tolerances"][f"{entry}_{name}"] >= 0.0


@pytest.mark.parametrize("part", ["total_loss", "actor_loss", "value_loss", "entropy", "aux_loss",
                                  "expert_load"])
def test_the_timed_windows_logged_losses_match_the_replay(tiny_run, part):
    assert tiny_run["detail"]["errors"][f"update_{part}"] <= 1e-4


@pytest.mark.parametrize("name,limit", [
    ("rollout_log_prob_rms", 1e-4), ("rollout_log_prob_max", 1e-4), ("rollout_values_rms", 1e-4),
    ("rollout_values_max", 1e-4), ("rollout_differs_from_decode", 0.0), ("rollout_returns", 0.0),
    ("rollout_dropped_pairs", 0.0),
    ("update_dropped_pairs", 0.0), ("update_adam_steps", 0.0),
    ("update_params_worst_leaf", 1e-3), ("update_params_all_leaves", 1e-3),
])
def test_the_reference_replays_the_timed_window(tiny_run, name, limit):
    """One more call of the learner the run timed, on the run's final state:
    what its rollout stored and what its Adam steps changed against the plain
    replay (verifier, float64 GAE, the state key's shuffle, `jax.grad` of the
    reference loss, clip, Adam) — float32 on both sides here."""
    assert tiny_run["detail"]["errors"][name] <= limit
    assert tiny_run["detail"]["tolerances"][name] >= 0.0


def test_a_token_the_two_compilations_routed_apart_is_counted_not_compared():
    """The stored record against the reference, where the standalone decode
    gives the same numbers: one token of 64 whose stored numbers are another
    routing's is left out of the extremes and shows as a share of its own."""
    import jax.numpy as jnp
    import numpy as np

    reference = loader.load_reference("ppo_olmoe")
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(4, 16, 97)), jnp.float32)
    values = jnp.asarray(rng.normal(size=(4, 16)), jnp.float32)
    actions = jnp.asarray(rng.integers(0, 97, (4, 16)), jnp.int32)
    decode = {"logits": logits + 1e-3, "values": values + 1e-3}
    record = {
        "log_prob": np.array(reference._log_prob_of(decode["logits"], actions)),
        "value": np.array(decode["values"]),
    }
    agree = jnp.ones((4, 16), bool)
    want = {"logits": logits, "values": values}
    clean = reference.compare_record(record, decode, want, actions, agree)
    assert clean["differs_from_decode"] == 0.0 and clean["values_max"] == pytest.approx(1e-3, rel=0.1)
    record["value"][2, 5] += 0.5
    record["log_prob"][2, 5] -= 0.3
    parted = reference.compare_record(record, decode, want, actions, agree)
    assert parted["differs_from_decode"] == pytest.approx(1 / 64)
    assert parted["values_max"] == clean["values_max"] and parted["log_prob_max"] == clean["log_prob_max"]


def test_the_run_prints_the_lower_precision_reading_and_the_setup_split(tiny_run):
    health = tiny_run["detail"]["health"]
    second = health["reference"]["lower_precision"]
    # bfloat16 is a different result: three decimal digits, not seven.
    assert second["logits_rms"] > 1e-3 and second["record_log_prob_rms"] > 1e-4
    assert second["update_params_worst_leaf"] > 1e-3
    assert set(health["reference"]["update_leaves"]) == set(second["update_leaves"])
    assert {"learner_setup", "aot_warmup", "first_tick"} <= set(health["setup_phases"])


def test_a_learner_that_skips_minibatches_is_not_correct(cpu_devices, monkeypatch):
    """The fault a comparison off the timed path cannot see: the learner
    trains on half of its minibatches. The window's parameter change and its
    Adam count give it away."""
    from stoix_tpu.systems.ppo.anakin import ff_lm_ppo

    real = ff_lm_ppo.shuffled_minibatch_epoch
    monkeypatch.setattr(
        ff_lm_ppo, "shuffled_minibatch_epoch",
        lambda step, carry, data, num_minibatches: real(
            step, carry, jax_tree_half(data), num_minibatches // 2
        ),
    )
    result = cell_runner.run_cell(
        tiny_cell(), 5, 1.0, False, time.perf_counter(), require_platform="cpu"
    )
    assert not result["correct"]
    assert any("update_adam_steps" in p for p in result["problems"]), result["problems"]
    assert any("update_params_worst_leaf" in p for p in result["problems"]), result["problems"]


def jax_tree_half(data):
    import jax

    return jax.tree.map(lambda x: x[: x.shape[0] // 2], data)


@pytest.mark.parametrize("stated,problem", [
    ({"intermediate_size": 64}, "parameter shapes differ from the stated widths"),
    ({"num_minibatches": 2}, "num_minibatches resolved to 4, stated 2"),
    ({"router_precision": "DEFAULT"}, "stated float32 at DEFAULT"),
    ({"parameter_dtype": "bfloat16"}, "parameters are ['float32'], stated bfloat16"),
])
def test_a_run_that_differs_from_what_the_file_states_is_not_correct(cpu_devices, stated, problem):
    result = cell_runner.run_cell(
        tiny_cell(**stated), 1, 1.0, False, time.perf_counter(), require_platform="cpu"
    )
    assert not result["correct"]
    assert any(problem in p for p in result["problems"]), result["problems"]


MODEL = {
    "hidden_size": 2048, "num_heads": 16, "head_dim": 128, "num_experts": 64,
    "experts_per_token": 8, "expert_width": 1024, "num_layers": 1, "vocab_size": 50304,
}


def test_update_cost_counts_the_active_experts_and_the_head():
    cost = flops_lm.update_cost(256, 512, 1, 16, MODEL)
    tokens = 256 * 512
    assert cost["samples"] == tokens
    parts = cost["parts"]
    assert parts["experts"]["flops"] == 3 * 3 * 2.0 * tokens * 8 * 2048 * 1024
    assert parts["head"]["flops"] == 3 * 2.0 * tokens * 2048 * 50304
    assert parts["qkvo"]["flops"] == 4 * 3 * 2.0 * tokens * 2048 * 2048
    # The lower triangle of q k^T and p v: under the full square's 4*T*T*width.
    assert parts["scores"]["flops"] < 3 * 256 * 4.0 * 512 * 512 * 2048
    assert cost["flops"] == sum(p["flops"] for p in parts.values())
    # 134.7 TFLOP: 0.68 s at the v5e's peak, compute-bound.
    least = peaks.least_seconds(cost["flops"], cost["bytes"], "TPU v5 lite")
    assert least["binds"] == "compute" and 0.6 < least["seconds"] < 0.75


def test_a_decode_step_reads_every_expert_and_is_memory_bound():
    assert flops_lm.experts_touched(256, MODEL) == pytest.approx(64.0)
    assert flops_lm.experts_touched(1, MODEL) == pytest.approx(8.0)
    step = flops_lm.expert_cost(256 * 8.0, MODEL, False, 64.0)
    assert step["bytes"] >= 64 * 3 * 2048 * 1024 * 4  # all experts' weights, once
    least = peaks.least_seconds(step["flops"], step["bytes"], "TPU v5 lite")
    assert least["binds"] == "memory"


D0 = "/device:TPU:0"


def op(name, start, dur, path):
    stats = {"tf_op": path, "program": "jit_learner_fn"}
    return Event(D0, tr.OPS_LINE, f"%{name} = f32[8]{{0}} thing()", start, dur, stats)


def lm_trace():
    """Three executions of a 1000 ps learner, the middle one whole. In it the
    rollout takes 500 (attention 100, router 20, dispatch 80, experts 200,
    head 90, env 10), gae 20, the update 480 (flash forward 40, its backward
    60, dispatch 40, experts 200, head 140). As on the chip, the grouped
    matmuls are kernels WITHOUT a framework path, inside the rollout's and
    the epoch's loop ops, which carry their scope."""
    roll = "jit(learner_fn)/while/body/rollout"
    act = f"{roll}/rollout_policy/OlmoeLM.step/layer_0"
    sgd = "jit(learner_fn)/while/body/ppo_epoch/ppo_minibatch"
    fwd = f"{sgd}/jvp(OlmoeLM.forward)/layer_0"
    bwd = f"{sgd}/transpose(jvp(OlmoeLM.forward))/layer_0"
    events = []
    for start in (0, 2000, 4000):
        events.append(Event(D0, tr.MODULES_LINE, "jit_learner_fn(7)", start, 1000, {}))
        events += [
            op("fusion.1", start, 100, f"{act}/attention/dot_general"),
            op("fusion.2", start + 100, 20, f"{act}/moe/moe_router/dot_general"),
            op("sort.3", start + 120, 80, f"{act}/moe/moe_dispatch/sort"),
            op("while.20", start, 500, "jit(learner_fn)/while/body/rollout/while"),
            op("while.21", start + 520, 480, "jit(learner_fn)/while/body/ppo_epoch/while"),
            op("ragged-dot-none.4", start + 200, 200, "ragged-dot-none"),
            op("fusion.5", start + 400, 90, f"{roll}/rollout_policy/lm_head/dot_general"),
            op("fusion.6", start + 490, 10, f"{roll}/rollout_env/rem"),
            op("fusion.7", start + 500, 20, "jit(learner_fn)/while/body/gae/while/body/add"),
            op("custom-call.8", start + 520, 40, f"{fwd}/attention/flash_attention"),
            op("fusion.9", start + 560, 60, f"{bwd}/attention/dot_general"),
            op("gather.10", start + 620, 40, f"{bwd}/moe/moe_dispatch/gather"),
            op("ragged-dot-none.11", start + 660, 150, "ragged-dot-none"),
            op("fusion.13", start + 810, 50, f"{bwd}/moe/transpose(jvp(moe_experts))/mul"),
            op("fusion.12", start + 860, 140, f"{sgd}/transpose(jvp(lm_head))/dot_general"),
        ]
    return tr.Trace.from_events(events)


def lm_ctx(shapes=None):
    cell = loader.load_cell(CELL)
    return types.SimpleNamespace(
        cell=cell, trace_data=lm_trace(), device={"kind": "TPU v5 lite"},
        shapes=shapes or {}, registry_span=lambda: None,
    )


def lm_reader(name):
    readers = loader.load_readers("per_layer", CELL)
    return dict((entry["name"], read) for entry, read in readers)[name]


@pytest.mark.parametrize("name,share", [
    ("decode_share", 50.0), ("moe_share", 54.0), ("moe_dispatch_share", 12.0),
    ("attention_share", 20.0), ("lm_head_share", 23.0), ("update_share", 48.0),
])
def test_share_readers_split_the_whole_execution(name, share):
    assert lm_reader(name)(lm_ctx()) == pytest.approx(share)


def test_roofline_readers_divide_the_least_seconds_by_the_scoped_time():
    ps = 1e-12
    least = lambda cost: peaks.least_seconds(cost["flops"], cost["bytes"], "TPU v5 lite")["seconds"]
    update = {"flops": 197e12 * 100 * ps, "bytes": 0.0}
    step = {"flops": 0.0, "bytes": 819e9 * 10 * ps}
    attention = {"flops": 197e12 * 10 * ps, "bytes": 819e9 * 4 * ps}
    shapes = {
        "experts_update_cost": update, "experts_decode_step_cost": step,
        "attention_forward_cost": attention, "rollout_length": 8, "updates_per_tick": 1,
    }
    ctx = lm_ctx(shapes)
    assert least(update) == pytest.approx(100 * ps)
    # 100 ps of least work in the 200 ps under ppo_epoch/moe_experts.
    assert lm_reader("moe_experts_update_roofline_share")(ctx) == pytest.approx(50.0)
    # 8 steps x 10 ps in the 200 ps under rollout/moe_experts.
    assert lm_reader("moe_experts_decode_roofline_share")(ctx) == pytest.approx(40.0)
    # The flash kernel alone (40 ps), not the plain-JAX backward beside it.
    assert lm_reader("attention_roofline_share")(ctx) == pytest.approx(25.0)


def test_readers_find_nothing_in_a_program_without_the_block(monkeypatch):
    """The parent tree's scope table has none of the block's scopes: every
    new reader returns None and the line leaves the metric out."""
    from benchmarks.harness import program_reads

    table = {"rollout": "rollout", "update_epoch": "ppo_epoch"}
    monkeypatch.setattr(program_reads, "program_scope", table.get)
    ctx = lm_ctx({"experts_update_cost": {"flops": 1.0, "bytes": 1.0}})
    for name in ("moe_share", "moe_dispatch_share", "attention_share", "lm_head_share",
                 "moe_experts_update_roofline_share", "moe_experts_decode_roofline_share",
                 "attention_roofline_share", "expert_load_max_over_mean"):
        assert lm_reader(name)(ctx) is None, name


def test_expert_load_reads_the_programs_logged_metric():
    """Mean of the TRAIN metric over the windows of the interval (records are
    (tick index, metrics); the interval's are first < index <= last)."""
    ctx = lm_ctx()
    ctx.rate = types.SimpleNamespace(first=1, last=3)
    ctx.train = [(i, {"expert_load_max_over_mean": load}) for i, load in enumerate([9.0, 9.0, 1.2, 1.4, 9.0])]
    assert lm_reader("expert_load_max_over_mean")(ctx) == pytest.approx(1.3)
    ctx.train = [(2, {"total_loss": 0.1})]  # a program that logs no such metric
    assert lm_reader("expert_load_max_over_mean")(ctx) is None
