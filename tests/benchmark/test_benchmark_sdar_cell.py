"""The block-diffusion token-policy cell (`anakin_ppo_sdar_blockdiff_1chip`)
on the CPU at a tiny preset: the whole path a real run takes — driver,
reference comparisons, stated-configuration checks, result line — with the
cell's configuration restated at the tiny widths; the cost functions behind
its roofline readers; and each of its new readers on synthetic events."""

import time
import types

import pytest

import _paths  # noqa: F401
from benchmarks.harness import cell_runner, flops_sdar, loader, peaks
from benchmarks.harness import trace_reduce as tr
from benchmarks.harness.trace_reduce import Event

CELL = "anakin_ppo_sdar_blockdiff_1chip"
TINY_STATED = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_experts": 4, "router_experts": 16, "num_experts_per_tok": 4, "moe_intermediate_size": 32,
    "vocab_size": 64, "mask_token_id": 63, "response_length": 16, "num_hidden_layers": 2,
    "num_minibatches": 4,
}
TINY_OVERRIDES = [
    "env=block_token_task", "network=sdar_moe", "arch.evaluation_greedy=True", "system.epochs=1",
    "network.actor_network.hidden_size=64", "network.actor_network.num_heads=4",
    "network.actor_network.num_kv_heads=2", "network.actor_network.head_dim=16",
    "network.actor_network.num_experts=16", "network.actor_network.experts_held=4",
    "network.actor_network.experts_per_token=4", "network.actor_network.expert_width=32",
    "network.actor_network.num_layers=2", "env.kwargs.vocab_size=64", "env.kwargs.length=16",
    "env.kwargs.block_length=4", "env.kwargs.passes=2", "system.rollout_length=8",
    "system.num_minibatches=4", "system.router_aux_loss_coef=0.001",
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


@pytest.mark.parametrize("name", [
    "logits_max", "logits_rms", "values_max", "values_rms", "expert_set_disagreement",
    "dropped_pairs", "expert_sets_over_lower_precision",
])
def test_the_reference_compares_the_teacher_forced_program(tiny_run, name):
    """On the CPU both sides are float32: far inside the chip's tolerances,
    expert sets identical, nothing dropped."""
    error = tiny_run["detail"]["errors"][f"tf_{name}"]
    assert error <= (1e-4 if name.startswith(("logits", "values")) else 0.0)
    assert tiny_run["detail"]["tolerances"][f"tf_{name}"] >= 0.0


@pytest.mark.parametrize("part", ["total_loss", "actor_loss", "value_loss", "entropy", "aux_loss",
                                  "expert_load", "held_pairs"])
def test_the_timed_windows_logged_losses_match_the_replay(tiny_run, part):
    assert tiny_run["detail"]["errors"][f"update_{part}"] <= 1e-4


@pytest.mark.parametrize("name,limit", [
    ("rollout_log_prob_rms", 1e-4), ("rollout_log_prob_max", 1e-4), ("rollout_values_rms", 1e-4),
    ("rollout_values_max", 1e-4), ("rollout_differs_from_program", 0.0), ("rollout_commit_flips", 0.0),
    ("rollout_returns", 0.0), ("rollout_mask_tokens", 0.0), ("rollout_dropped_pairs", 0.0),
    ("rollout_passes_per_token", 1e-6), ("update_dropped_pairs", 0.0), ("update_adam_steps", 0.0),
    ("update_params_worst_leaf", 1e-3), ("update_params_all_leaves", 1e-3),
])
def test_the_reference_replays_the_timed_window(tiny_run, name, limit):
    """One more call of the learner the run timed, on the run's final state:
    what its rollout stored — log-probs, values, commit sets — against the
    reference's full-prefix forwards, and what its Adam steps changed against
    the plain replay (verifier, float64 GAE, the state key's shuffle,
    `jax.grad` of the reference loss, clip, Adam) — float32 on both sides."""
    assert tiny_run["detail"]["errors"][name] <= limit
    assert tiny_run["detail"]["tolerances"][name] >= 0.0


def test_the_run_prints_the_lower_precision_reading_and_the_counters(tiny_run):
    health = tiny_run["detail"]["health"]
    second = health["reference"]["lower_precision"]
    # bfloat16 is a different result: three decimal digits, not seven.
    assert second["logits_rms"] > 1e-3 and second["record_log_prob_rms"] > 1e-4
    assert second["update_params_worst_leaf"] > 1e-3
    assert set(health["reference"]["update_leaves"]) == set(second["update_leaves"])
    counters = health["reference"]["counters"]
    assert counters["decode_passes_per_token"] == pytest.approx((1 + 4 * 3) / 16)
    assert counters["tokens_per_denoise_pass"] == pytest.approx(2.0)
    assert 0.0 < counters["held_pairs_per_token"] < 4.0
    assert {"learner_setup", "aot_warmup", "first_tick"} <= set(health["setup_phases"])


def test_a_learner_that_skips_minibatches_is_not_correct(cpu_devices, monkeypatch):
    """The fault a comparison off the timed path cannot see: the learner
    trains on half of its minibatches."""
    import jax
    from stoix_tpu.systems.ppo.anakin import ff_sdar_ppo

    real = ff_sdar_ppo.shuffled_minibatch_epoch
    monkeypatch.setattr(
        ff_sdar_ppo, "shuffled_minibatch_epoch",
        lambda step, carry, data, num_minibatches: real(
            step, carry, jax.tree.map(lambda x: x[: x.shape[0] // 2], data), num_minibatches // 2
        ),
    )
    result = cell_runner.run_cell(
        tiny_cell(), 5, 1.0, False, time.perf_counter(), require_platform="cpu"
    )
    assert not result["correct"]
    assert any("update_adam_steps" in p for p in result["problems"]), result["problems"]
    assert any("update_params_worst_leaf" in p for p in result["problems"]), result["problems"]


def test_a_rollout_that_commits_the_least_confident_is_not_correct(cpu_devices, monkeypatch):
    """A wrong commit rule moves no stored log-prob (the sum is over the
    stored set, and the update reads the same set): the stored sets against
    the top-k of the reference's confidences give it away."""
    import jax
    import jax.numpy as jnp
    from stoix_tpu.ops.distributions import Categorical
    from stoix_tpu.systems.ppo.anakin import ff_sdar_ppo

    real = ff_sdar_ppo.choose

    def least_confident(logits, block, mask_id, count, key):
        choice = real(logits, block, mask_id, count, key)
        allowed = jnp.arange(logits.shape[-1]) != mask_id
        log_prob = Categorical(logits, mask=allowed).log_prob(choice.token)
        masked = block == mask_id
        _, chosen = jax.lax.top_k(jnp.where(masked, -jnp.exp(log_prob), -2.0), count)
        commit = jnp.any(chosen[..., None] == jnp.arange(block.shape[-1]), axis=-2) & masked
        return choice._replace(
            commit=commit, block=jnp.where(commit, choice.token, block),
            log_prob=jnp.sum(jnp.where(commit, log_prob, 0.0), axis=-1),
        )

    monkeypatch.setattr(ff_sdar_ppo, "choose", least_confident)
    result = cell_runner.run_cell(
        tiny_cell(), 7, 1.0, False, time.perf_counter(), require_platform="cpu"
    )
    assert not result["correct"]
    assert any("rollout_commit_flips" in p for p in result["problems"]), result["problems"]
    # ... and nothing else: the window is self-consistent under the wrong rule
    assert result["detail"]["errors"]["rollout_log_prob_max"] <= 1e-4
    assert result["detail"]["errors"]["update_params_worst_leaf"] <= 1e-3


@pytest.mark.parametrize("stated,problem", [
    ({"moe_intermediate_size": 64}, "parameter shapes differ from the stated widths"),
    ({"router_experts": 32}, "parameter shapes differ from the stated widths"),
    ({"num_minibatches": 2}, "num_minibatches resolved to 4, stated 2"),
    ({"denoise_passes": 4}, "passes resolved to 2, stated 4"),
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
    "hidden_size": 2048, "num_heads": 32, "num_kv_heads": 4, "head_dim": 128, "num_experts": 128,
    "experts_held": 16, "experts_per_token": 8, "expert_width": 768, "num_layers": 4,
    "vocab_size": 18992, "block_length": 4, "passes": 2, "response_length": 512,
}


def test_the_allowed_pairs_are_the_block_masks():
    """Counted from shapes, and by brute force from the rule."""
    small = {**MODEL, "response_length": 16}
    elements = [(0, p) for p in range(20)] + [(c, p) for c in (1, 2) for p in range(4, 20)]
    brute = sum(
        (ck == 0 and pk // 4 < pq // 4) or (ck == cq and pk // 4 == pq // 4)
        for cq, pq in elements for ck, pk in elements
    )
    assert flops_sdar.allowed_pairs(small) == brute
    assert flops_sdar.positions_a_sequence(MODEL) == 1540
    assert flops_sdar.allowed_pairs(MODEL) == 402448  # 17% of the 1540 x 1540 square


def test_update_cost_counts_the_share_this_chip_holds():
    cost = flops_sdar.update_cost(128, 1, 8, MODEL)
    tokens = 128 * 1540
    assert cost["samples"] == tokens
    parts = cost["parts"]
    # one pair a token lands on the 16 held experts of 128 under uniform routing, not 8
    assert parts["experts"]["flops"] == 4 * 3 * 3 * 2.0 * tokens * 2048 * 768
    # grouped-query projections: wq and wo 4096 wide, wk and wv 512
    assert parts["qkvo"]["flops"] == 4 * 3 * 2.0 * tokens * 2048 * (2 * 4096 + 2 * 512)
    # the head over the slice, on the committed positions: every response token once
    assert parts["head"]["flops"] == 3 * 2.0 * 128 * 512 * 2048 * 18992
    assert parts["scores"]["flops"] == 4 * 3 * 128 * 4.0 * 402448 * 4096
    assert parts["router"]["flops"] == 4 * 3 * 2.0 * tokens * 2048 * 128
    assert cost["flops"] == sum(p["flops"] for p in parts.values())
    # the run's own count of held pairs a token takes the place of the uniform 1.0
    skewed = flops_sdar.update_cost(128, 1, 8, MODEL, held_pairs_per_token=1.5)
    assert skewed["parts"]["experts"]["flops"] == 1.5 * parts["experts"]["flops"]
    # 138 TFLOP at depth 4: 0.70 s at the v5e's peak, compute-bound.
    least = peaks.least_seconds(cost["flops"], cost["bytes"], "TPU v5 lite")
    assert least["binds"] == "compute" and 0.65 < least["seconds"] < 0.75


def test_a_block_pass_reads_the_held_experts_and_is_memory_bound():
    a_pass = flops_sdar.expert_cost(flops_sdar.held_rows(128 * 4.0, MODEL, None), MODEL, False, 16)
    assert a_pass["bytes"] >= 16 * 3 * 2048 * 768 * 4  # the held experts' weights, once
    least = peaks.least_seconds(a_pass["flops"], a_pass["bytes"], "TPU v5 lite")
    assert least["binds"] == "memory"


D0 = "/device:TPU:0"


def op(name, start, dur, path):
    stats = {"tf_op": path, "program": "jit_learner_fn"}
    return Event(D0, tr.OPS_LINE, f"%{name} = f32[8]{{0}} thing()", start, dur, stats)


def sdar_trace():
    """Three executions of a 1000 ps learner, the middle one whole. In it the
    rollout takes 500: denoise passes 300 (attention 100 of which the scores
    40, the held experts' loop 120 with a pathless grouped matmul inside, head
    80), commit passes 150 (attention 60, experts' loop 90), env 50; the
    update 500 (attention forward scores 80 and backward 120, experts 200,
    head 100)."""
    roll = "jit(learner_fn)/while/body/rollout/while/body"
    den = f"{roll}/rollout_policy/denoise"
    com = f"{roll}/block_commit"
    sgd = "jit(learner_fn)/while/body/ppo_epoch/ppo_minibatch"
    fwd, bwd = f"{sgd}/jvp(checkpoint)", f"{sgd}/transpose(jvp(checkpoint))/rematted_computation"
    events = []
    for start in (0, 2000, 4000):
        events.append(Event(D0, tr.MODULES_LINE, "jit_learner_fn(7)", start, 1000, {}))
        events += [
            op("while.20", start, 500, "jit(learner_fn)/while/body/rollout/while"),
            op("while.21", start + 500, 500, "jit(learner_fn)/while/body/ppo_epoch/while"),
            op("fusion.1", start, 60, f"{den}/attention/dot_general"),
            op("fusion.2", start + 60, 40, f"{den}/attention/attention_scores/dot_general"),
            op("while.3", start + 100, 120, f"{den}/moe/while"),
            op("ragged-dot-none.4", start + 110, 100, "ragged-dot-none"),
            op("fusion.5", start + 220, 80, f"{den}/lm_head/dot_general"),
            op("fusion.6", start + 300, 60, f"{com}/attention/dynamic_update_slice"),
            op("while.7", start + 360, 90, f"{com}/moe/while"),
            op("ragged-dot-none.8", start + 370, 70, "ragged-dot-none"),
            op("fusion.9", start + 450, 50, f"{roll}/rollout_env/rem"),
            op("fusion.10", start + 500, 80, f"{fwd}/attention/attention_scores/while/body/checkpoint/dot_general"),
            op("fusion.11", start + 580, 120, f"{bwd}/attention/attention_scores/while/body/dot_general"),
            op("while.12", start + 700, 200, f"{bwd}/moe/while"),
            op("ragged-dot-none.13", start + 710, 180, "ragged-dot-none"),
            op("fusion.14", start + 900, 100, f"{sgd}/transpose(jvp(lm_head))/dot_general"),
        ]
    return tr.Trace.from_events(events)


def sdar_ctx(shapes=None):
    cell = loader.load_cell(CELL)
    return types.SimpleNamespace(
        cell=cell, trace_data=sdar_trace(), device={"kind": "TPU v5 lite"},
        shapes=shapes or {}, registry_span=lambda: None,
    )


def sdar_reader(name):
    readers = loader.load_readers("per_layer", CELL)
    return dict((entry["name"], read) for entry, read in readers)[name]


@pytest.mark.parametrize("name,share", [
    ("denoise_share", 30.0), ("block_commit_share", 15.0), ("decode_share", 50.0),
    ("moe_share", 41.0), ("attention_share", 36.0), ("lm_head_share", 18.0), ("update_share", 50.0),
])
def test_share_readers_split_the_whole_execution(name, share):
    """The held experts' loop is an op of its own under `denoise` /
    `block_commit` and `moe`: the pathless grouped matmuls inside it are
    inside its time."""
    assert sdar_reader(name)(sdar_ctx()) == pytest.approx(share)


def test_roofline_readers_divide_the_least_seconds_by_the_scoped_time():
    ps = 1e-12
    attention = {"flops": 197e12 * 50 * ps, "bytes": 819e9 * 4 * ps}
    update = {"flops": 197e12 * 90 * ps, "bytes": 0.0}
    step = {"flops": 0.0, "bytes": 819e9 * 17 * ps}
    shapes = {
        "block_attention_update_cost": attention, "experts_update_cost": update,
        "experts_decode_step_cost": step, "rollout_length": 4, "updates_per_tick": 1,
    }
    ctx = sdar_ctx(shapes)
    # 50 ps of least work in the 200 ps under ppo_epoch/attention_scores, forward and backward
    assert sdar_reader("block_attention_roofline_share")(ctx) == pytest.approx(25.0)
    # 90 ps in the 180 ps of grouped matmuls under ppo_epoch
    assert sdar_reader("moe_experts_update_roofline_share")(ctx) == pytest.approx(50.0)
    # 4 steps x 17 ps in the 170 ps of grouped matmuls under rollout
    assert sdar_reader("moe_experts_decode_roofline_share")(ctx) == pytest.approx(40.0)


def test_new_readers_find_nothing_in_a_program_without_the_scopes(monkeypatch):
    """The parent tree's scope table has none of this PR's scopes: every new
    reader returns None and the line leaves the metric out."""
    from benchmarks.harness import program_reads

    table = {"rollout": "rollout", "update_epoch": "ppo_epoch", "attention": "attention"}
    monkeypatch.setattr(program_reads, "program_scope", table.get)
    ctx = sdar_ctx({"block_attention_update_cost": {"flops": 1.0, "bytes": 1.0}})
    ctx.rate, ctx.train = types.SimpleNamespace(first=0, last=2), [(1, {"total_loss": 0.1})]
    for name in ("denoise_share", "block_commit_share", "block_attention_roofline_share",
                 "decode_passes_per_token"):
        assert sdar_reader(name)(ctx) is None, name


def test_decode_passes_reads_the_programs_logged_counter():
    ctx = sdar_ctx()
    ctx.rate = types.SimpleNamespace(first=1, last=3)
    ctx.train = [(i, {"decode_passes_per_token": v}) for i, v in enumerate([9.0, 9.0, 0.75, 0.76, 9.0])]
    assert sdar_reader("decode_passes_per_token")(ctx) == pytest.approx(0.755)
