"""The latent-attention token-policy cell (`anakin_ppo_kanana2_tokens_1chip`)
on the CPU at a tiny preset: the whole path a real run takes — driver,
reference comparisons, stated-configuration checks, result line — with the
cell's configuration restated at the tiny widths; faults the comparison has to
refuse; the cost functions behind its roofline readers; each of its new
readers on synthetic events; and Mosaic's and XLA:TPU's verdict on the mixer
at the published widths, for a described v5e, at no chip time."""

import time
import types

import pytest

import _paths  # noqa: F401
from benchmarks.harness import cell_runner, flops_mla, loader, peaks
from benchmarks.harness import trace_reduce as tr
from benchmarks.harness.trace_reduce import Event

CELL = "anakin_ppo_kanana2_tokens_1chip"
TINY_STATED = {
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    # (4 heads of 16 + 8 | 12 over a latent of 16: no projection has the router's shape, [64, 32])
    "num_attention_heads": 4, "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 12, "n_routed_experts": 4, "router_experts": 32, "num_experts_per_tok": 3,
    "vocab_size": 64, "num_minibatches": 4, "rollout_length": 16,
}
TINY_OVERRIDES = [
    "env=token_task", "network=kanana2_moe", "arch.evaluation_greedy=True", "system.epochs=1",
    "system.router_aux_loss_coef=0.0",
    "network.actor_network.hidden_size=64", "network.actor_network.dense_width=96",
    "network.actor_network.num_heads=4", "network.actor_network.num_kv_heads=4",
    "network.actor_network.head_dim=8", "network.actor_network.kv_lora_rank=16",
    "network.actor_network.qk_nope_head_dim=16", "network.actor_network.qk_rope_head_dim=8",
    "network.actor_network.v_head_dim=12", "network.actor_network.num_experts=32",
    "network.actor_network.experts_held=4", "network.actor_network.experts_per_token=3",
    "network.actor_network.expert_width=32",
    "env.kwargs.vocab_size=64", "env.kwargs.length=16", "system.rollout_length=16",
    "system.num_minibatches=4",
]
TINY_TRAFFIC = [
    "arch.total_num_envs=32", "arch.total_timesteps=~", "arch.num_updates=1000000",
    "arch.num_evaluation=1000000", "arch.num_eval_episodes=8",
]


def tiny_cell(second_reading=False, tolerances=None, **config):
    """The cell restated at the tiny preset, data-parallel over the test
    session's virtual CPU devices (the program's mesh takes them all)."""
    import jax

    cell = loader.load_cell(CELL)._replace(chips=len(jax.devices()))
    reference = {**cell.config["reference"], "sample_sequences": 4,
                 "lower_precision_update": second_reading, **(tolerances or {})}
    return cell._replace(
        config={**cell.config, **TINY_STATED, "overrides": TINY_OVERRIDES, "reference": reference,
                **config},
        traffic={**cell.traffic, "overrides": TINY_TRAFFIC},
    )


@pytest.fixture()
def cpu_devices(monkeypatch):
    import jax

    monkeypatch.setattr(cell_runner, "_gate_devices", lambda cell, platform: jax.devices())


def run_tiny(seed, seconds=1.0, **config):
    return cell_runner.run_cell(
        tiny_cell(**config), seed, seconds, False, time.perf_counter(), require_platform="cpu"
    )


@pytest.fixture(scope="module")
def tiny_run():
    import jax
    from unittest import mock

    with mock.patch.object(cell_runner, "_gate_devices", lambda cell, platform: jax.devices()):
        return run_tiny(3_000_000_019, 3.0, second_reading=True)


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
    expert sets over all 32 experts identical, nothing dropped — teacher
    forced (keys and values expanded) and decoded through the latent cache
    (absorbed) at every slot."""
    error = tiny_run["detail"]["errors"][f"{entry}_{name}"]
    assert error <= (1e-4 if name.startswith(("logits", "values")) else 0.0)
    assert tiny_run["detail"]["tolerances"][f"{entry}_{name}"] >= 0.0


@pytest.mark.parametrize("part", [
    "total_loss", "actor_loss", "value_loss", "entropy", "aux_loss", "expert_load_max_over_mean",
    "held_pairs_per_token", "router_bias_changed_share",
])
def test_the_timed_windows_logged_losses_and_counters_match_the_replay(tiny_run, part):
    assert tiny_run["detail"]["errors"][f"update_{part}"] <= 1e-4


@pytest.mark.parametrize("name,limit", [
    ("rollout_log_prob_rms", 1e-4), ("rollout_log_prob_max", 1e-4), ("rollout_values_rms", 1e-4),
    ("rollout_values_max", 1e-4), ("rollout_differs_from_decode", 0.0), ("rollout_returns", 0.0),
    ("rollout_dropped_pairs", 0.0), ("rollout_held_pairs_per_token", 0.05),
    ("update_dropped_pairs", 0.0), ("update_dropped_pairs_counted", 0.0),
    ("update_adam_steps", 0.0), ("update_expert_bias_changed", 0.0),
    ("update_params_worst_leaf", 1e-3), ("update_params_all_leaves", 1e-3),
])
def test_the_reference_replays_the_timed_window(tiny_run, name, limit):
    """One more call of the learner the run timed, on the run's final state:
    what its rollout stored against the reference's whole-sequence forward,
    and what its Adam steps changed against the plain replay — float32 on
    both sides; `expert_bias` unchanged to the bit."""
    assert tiny_run["detail"]["errors"][name] <= limit
    assert tiny_run["detail"]["tolerances"][name] >= 0.0


def test_the_run_prints_the_lower_precision_reading_and_the_counters(tiny_run):
    health = tiny_run["detail"]["health"]
    second = health["reference"]["lower_precision"]
    # bfloat16 is a different result: three decimal digits, not seven.
    assert second["logits_rms"] > 1e-3 and second["record_log_prob_rms"] > 1e-4
    assert second["update_params_worst_leaf"] > 1e-3
    leaves = set(health["reference"]["update_leaves"])
    assert leaves == set(second["update_leaves"])
    assert not any("expert_bias" in leaf for leaf in leaves)
    # the latent layer's leaves, the shared expert's and the untied head moved and were compared
    assert {"actor/layer_0/mixer/wkv_a", "actor/layer_0/mixer/kv_norm", "actor/layer_4/mixer/wkv_b",
            "actor/layer_1/ffn/shared/w1", "actor/lm_head", "actor/embed"} <= leaves
    counters = health["reference"]["counters"]
    assert 0.0 < counters["held_pairs_per_token"] < 3.0
    assert 0.0 < counters["router_bias_changed_share"] < 1.0
    assert counters["dropped_pairs"] == 0.0
    assert {"learner_setup", "aot_warmup", "first_tick"} <= set(health["setup_phases"])


def test_the_drivers_shapes_carry_the_held_pairs_the_run_logged(cpu_devices):
    seen = {}
    real = flops_mla.mla_ppo_shapes

    def spy(config, **kwargs):
        seen.update(kwargs["held_pairs"])
        return real(config, **kwargs)

    import unittest.mock as mock

    with mock.patch.object(flops_mla, "mla_ppo_shapes", spy):
        run_tiny(11)
    assert 0.0 < seen["update"] < 3.0 and 0.0 < seen["rollout"] < 3.0


def test_a_decode_that_leaves_the_rotated_part_out_of_the_scores_is_not_correct(cpu_devices, monkeypatch):
    """The absorbed scores are q~ . l^ + q_rope . k_r: a decode that attends
    on the latent alone gives other log-probs than the teacher-forced pass,
    in the standalone decode program and in what the timed rollout stored.
    At hidden 64 and normal(0.02) weights a score is of order 1e-2 (0.6 at
    the published widths), so the fault moves a logit by 5e-3 here: the
    limits are restated for two float32 sides, which agree to 3e-7."""
    import jax.numpy as jnp
    from stoix_tpu.networks import mla

    real = mla.attend_latent

    def latent_only(q, rows, length, rank, scale):
        q = jnp.concatenate([q[..., :rank], jnp.zeros_like(q[..., rank:])], -1)
        return real(q, rows, length, rank, scale)

    monkeypatch.setattr(mla, "attend_latent", latent_only)
    result = run_tiny(3, tolerances={"logits_rms_tol": 1e-4, "log_prob_rms_tol": 1e-4})
    assert not result["correct"]
    assert any("decode_logits_rms" in p for p in result["problems"]), result["problems"]
    assert any("rollout_log_prob_rms" in p for p in result["problems"]), result["problems"]
    # ... while the teacher-forced entry point, which expands, is the reference's own
    assert result["detail"]["errors"]["tf_logits_rms"] <= 1e-4


def test_a_learner_that_skips_minibatches_is_not_correct(cpu_devices, monkeypatch):
    """The fault a comparison off the timed path cannot see: the learner
    trains on half of its minibatches."""
    import jax
    from stoix_tpu.systems.ppo.anakin import ff_lm_ppo

    real = ff_lm_ppo.shuffled_minibatch_epoch
    monkeypatch.setattr(
        ff_lm_ppo, "shuffled_minibatch_epoch",
        lambda step, carry, data, num_minibatches: real(
            step, carry, jax.tree.map(lambda x: x[: x.shape[0] // 2], data), num_minibatches // 2
        ),
    )
    result = run_tiny(5)
    assert not result["correct"]
    assert any("update_adam_steps" in p for p in result["problems"]), result["problems"]
    assert any("update_params_worst_leaf" in p for p in result["problems"]), result["problems"]


def test_a_router_that_weighs_by_score_plus_bias_is_not_correct(cpu_devices, monkeypatch):
    """The published rule keeps the bias out of the weights. A router whose
    weights are the biased scores chooses the same experts and drops nothing:
    what gives it away is that `expert_bias` now takes a gradient — the
    window's Adam steps move it, in every routed layer."""
    import jax.numpy as jnp
    from stoix_tpu.networks import olmoe

    real = olmoe.route

    def biased_weights(x, router, top_k, renormalise=False, **routing):
        probs, weights, index = real(x, router, top_k, renormalise, **routing)
        if routing.get("bias") is not None:
            weights = jnp.take_along_axis(probs + routing["bias"], index, axis=-1)
            weights = routing["scale"] * weights / jnp.sum(weights, axis=-1, keepdims=True)
        return probs, weights, index

    monkeypatch.setattr(olmoe, "route", biased_weights)
    result = run_tiny(7)
    assert not result["correct"]
    assert any("update_expert_bias_changed" in p for p in result["problems"]), result["problems"]
    assert result["detail"]["errors"]["update_expert_bias_changed"] == 4.0
    # ... while the chosen sets are the reference's own, and nothing is dropped
    assert result["detail"]["errors"]["tf_expert_set_disagreement"] == 0.0
    assert result["detail"]["errors"]["update_dropped_pairs"] == 0.0


@pytest.mark.parametrize("stated,problem", [
    ({"moe_intermediate_size": 64}, "parameter shapes differ from the stated layers and widths"),
    ({"router_experts": 64}, "parameter shapes differ from the stated layers and widths"),
    ({"n_routed_experts": 8}, "parameter shapes differ from the stated layers and widths"),
    ({"kv_lora_rank": 24}, "parameter shapes differ from the stated layers and widths"),
    ({"v_head_dim": 16}, "parameter shapes differ from the stated layers and widths"),
    ({"n_shared_experts": 1}, "parameter shapes differ from the stated layers and widths"),
    ({"first_k_dense_replace": 2}, "parameter shapes differ from the stated layers and widths"),
    ({"num_minibatches": 2}, "num_minibatches resolved to 4, stated 2"),
    ({"router_precision": "DEFAULT"}, "stated float32 at DEFAULT"),
    ({"parameter_dtype": "bfloat16"}, "parameters are ['float32'], stated bfloat16"),
])
def test_a_run_that_differs_from_what_the_file_states_is_not_correct(cpu_devices, stated, problem):
    result = run_tiny(1, **stated)
    assert not result["correct"]
    assert any(problem in p for p in result["problems"]), result["problems"]


def test_the_stated_tree_is_the_published_layer_and_the_share():
    reference = loader.load_reference("ppo_kanana2")
    config = loader.load_cell(CELL).config
    want = reference.expected_shapes(config)
    assert want["embed"] == (16032, 2048) and want["lm_head"] == (2048, 16032)  # untied
    assert want["layer_0/mixer/wq"] == (2048, 32 * 192) and want["layer_0/mixer/wkv_a"] == (2048, 576)
    assert want["layer_3/mixer/wkv_b"] == (512, 32 * 256) and want["layer_3/mixer/wo"] == (4096, 2048)
    assert want["layer_0/ffn/w1"] == (2048, 6144) and want["layer_1/ffn/gate"] == (16, 2048, 768)
    assert want["layer_4/ffn/router"] == (2048, 128) and want["layer_4/ffn/shared/w2"] == (1536, 2048)
    parameters = sum(int(__import__("numpy").prod(shape)) for shape in want.values())
    assert parameters == 575_955_968  # 576.0 M, and the value head's 2,049 beside them
    # every number of the published config that the share does not cut, under its own key
    published = {
        "first_k_dense_replace": 1, "head_dim": 64, "hidden_size": 2048, "intermediate_size": 6144,
        "kv_lora_rank": 512, "max_position_embeddings": 32768, "moe_intermediate_size": 768,
        "moe_layer_freq": 1, "n_group": 1, "n_shared_experts": 2, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_key_value_heads": 32, "qk_head_dim": 192,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_theta": 1000000, "routed_scaling_factor": 2.448, "topk_group": 1, "v_head_dim": 128,
    }
    assert {key: config[key] for key in published} == published
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 48, "n_routed_experts": 128, "vocab_size": 128256}
    assert config["vocab_size"] * 8 == 128256 and config["n_routed_experts"] * 8 == 128


MODEL = {
    "hidden_size": 2048, "num_layers": 5, "num_dense_layers": 1, "dense_width": 6144,
    "num_heads": 32, "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "num_experts": 128, "experts_held": 16, "experts_per_token": 6,
    "expert_width": 768, "shared_width": 1536, "vocab_size": 16032,
}


def test_update_cost_counts_the_share_this_chip_holds():
    cost = flops_mla.update_cost(128, 512, 1, 8, MODEL)
    tokens = 128 * 512
    assert cost["samples"] == tokens
    parts = cost["parts"]
    # five latent layers: W_q [2048, 6144], W_kva [2048, 576], W_o [4096, 2048] ...
    assert parts["latent_projections"]["flops"] == 5 * 3 * 2.0 * tokens * (2048 * 6144 + 2048 * 576 + 4096 * 2048)
    # ... and the expansion W_kvb [512, 8192]
    assert parts["latent_expansion"]["flops"] == 5 * 3 * 2.0 * tokens * 512 * 8192
    # causal scores at 192 and values at 128, the lower triangle
    assert parts["scores"]["flops"] == 5 * 3 * 128 * 2.0 * (512 * 513 / 2) * 32 * (192 + 128)
    # 0.75 pairs a token land on the 16 held experts of 128 under uniform routing, not 6
    assert parts["experts"]["flops"] == 4 * 3 * 3 * 2.0 * 0.75 * tokens * 2048 * 768
    assert parts["shared_experts"]["flops"] == 4 * 3 * 3 * 2.0 * tokens * 2048 * 1536
    assert parts["dense_mlps"]["flops"] == 3 * 3 * 2.0 * tokens * 2048 * 6144
    assert parts["head"]["flops"] == 3 * 2.0 * tokens * 2048 * 16032
    assert parts["router"]["flops"] == 4 * 3 * 2.0 * tokens * 2048 * 128
    assert cost["flops"] == sum(p["flops"] for p in parts.values())
    skewed = flops_mla.update_cost(128, 512, 1, 8, MODEL, held_pairs_per_token=1.5)
    assert skewed["parts"]["experts"]["flops"] == 2.0 * parts["experts"]["flops"]
    # ISSUE 38's arithmetic: about 100 TFLOP an update, half of it latent attention's
    least = peaks.least_seconds(cost["flops"], cost["bytes"], "TPU v5 lite")
    assert least["binds"] == "compute" and 0.45 < least["seconds"] < 0.60
    share = lambda *names: sum(parts[n]["flops"] for n in names) / cost["flops"]
    assert 0.45 < share("latent_projections", "latent_expansion", "scores") < 0.60
    assert 0.10 < share("shared_experts") < 0.16 and 0.05 < share("experts") < 0.09


def test_a_decode_step_of_latent_attention_is_memory_bound_and_the_update_compute_bound():
    step = flops_mla.latent_attend_decode_step_cost(128, 512, MODEL)
    # the live rows, (512 + 1) / 2 a sequence on average, read once in float32 ...
    assert step["bytes"] >= 128 * 256.5 * 576 * 4
    # ... W_kvb once as bfloat16 operands, the queries in and the result out
    assert step["bytes"] - 128 * 256.5 * 576 * 4 == 2 * 512 * 8192 + 4 * 128 * 32 * (192 + 128)
    assert step["flops"] == 128 * 32 * 2.0 * (128 * 512 + (2 * 512 + 64) * 256.5 + 512 * 128)
    assert peaks.least_seconds(step["flops"], step["bytes"], "TPU v5 lite")["binds"] == "memory"
    # thirty operations a byte of cache: far above a matrix-vector product's one half
    assert 25 < step["flops"] / (128 * 256.5 * 576 * 4) < 50
    whole = flops_mla.update_cost(128, 512, 1, 8, MODEL)
    assert peaks.least_seconds(whole["flops"], whole["bytes"], "TPU v5 lite")["binds"] == "compute"
    # ... of which the expansion binds on compute and the float32 scores, at
    # 512 / 8 = 64 operations a byte of q, k, v and the result, on memory
    parts = whole["parts"]
    binds = lambda part: peaks.least_seconds(part["flops"], part["bytes"], "TPU v5 lite")["binds"]
    assert binds(parts["latent_expansion"]) == "compute" and binds(parts["scores"]) == "memory"


def test_a_decode_step_reads_the_held_experts_and_is_memory_bound():
    from benchmarks.harness.flops_lfm2 import held_rows
    from benchmarks.harness.flops_lm import expert_cost

    step = expert_cost(held_rows(128.0, MODEL, None), MODEL, False, 16)
    assert step["bytes"] >= 16 * 3 * 2048 * 768 * 4  # the held experts' weights, once
    assert peaks.least_seconds(step["flops"], step["bytes"], "TPU v5 lite")["binds"] == "memory"


D0 = "/device:TPU:0"


def op(name, start, dur, path):
    stats = {"tf_op": path, "program": "jit_learner_fn"}
    return Event(D0, tr.OPS_LINE, f"%{name} = f32[8]{{0}} thing()", start, dur, stats)


def kanana2_trace():
    """Three executions of a 1000 ps learner, the middle one whole. In it the
    rollout takes 400: latent attention 160 (projection 30, the decode kernel
    and the absorbed products 80), the dense feed-forward 40, the routed
    layer 70 with a pathless grouped matmul inside, the shared expert 50,
    head 50, env 30; the update 600: latent attention 300 (projection 40, the
    flash kernel 60, its plain backward and the expansion 100), the dense
    feed-forward 60, experts 80, the shared expert 90, head 70."""
    roll = "jit(learner_fn)/while/body/rollout/while/body/rollout_policy"
    sgd = "jit(learner_fn)/while/body/ppo_epoch/ppo_minibatch"
    fwd, bwd = f"{sgd}/jvp(Lfm2LM)", f"{sgd}/transpose(jvp(Lfm2LM))"
    events = []
    for start in (0, 2000, 4000):
        events.append(Event(D0, tr.MODULES_LINE, "jit_learner_fn(7)", start, 1000, {}))
        events += [
            op("while.20", start, 400, "jit(learner_fn)/while/body/rollout/while"),
            op("while.21", start + 400, 600, "jit(learner_fn)/while/body/ppo_epoch/while"),
            op("fusion.1", start, 50, f"{roll}/Lfm2LM/layer_0/attention/mixer/dot_general"),
            op("fusion.2", start + 50, 30, f"{roll}/Lfm2LM/layer_0/attention/mixer/latent_project/dynamic_update_slice"),
            op("custom-call.3", start + 80, 60, f"{roll}/Lfm2LM/layer_0/attention/mixer/latent_attend/latent_decode_attention/pallas_call"),
            op("fusion.4", start + 140, 20, f"{roll}/Lfm2LM/layer_0/attention/mixer/latent_attend/bhn,chn->bhc/dot_general"),
            op("fusion.5", start + 160, 40, f"{roll}/Lfm2LM/layer_0/ffn/dense_mlp/dot_general"),
            op("while.6", start + 200, 70, f"{roll}/Lfm2LM/layer_1/ffn/moe/while"),
            op("ragged-dot-none.7", start + 210, 40, "ragged-dot-none"),
            op("fusion.8", start + 270, 50, f"{roll}/Lfm2LM/layer_1/ffn/shared/shared_expert/dot_general"),
            op("fusion.9", start + 320, 50, f"{roll}/Lfm2LM/lm_head/dot_general"),
            op("fusion.10", start + 370, 30, "jit(learner_fn)/while/body/rollout/while/body/rollout_env/rem"),
            op("fusion.11", start + 400, 100, f"{fwd}/layer_0/attention/mixer/dot_general"),
            op("fusion.12", start + 500, 40, f"{fwd}/layer_0/attention/mixer/latent_project/mul"),
            op("custom-call.13", start + 540, 60, f"{fwd}/layer_0/attention/mixer/latent_attend/flash_attention/pallas_call"),
            op("fusion.14", start + 600, 100, f"{bwd}/layer_0/attention/mixer/latent_attend/dot_general"),
            op("fusion.15", start + 700, 60, f"{bwd}/layer_0/ffn/dense_mlp/dot_general"),
            op("while.16", start + 760, 80, f"{bwd}/layer_1/ffn/moe/while"),
            op("ragged-dot-none.17", start + 770, 60, "ragged-dot-none"),
            op("fusion.18", start + 840, 90, f"{bwd}/layer_1/ffn/shared/shared_expert/dot_general"),
            op("fusion.19", start + 930, 70, f"{sgd}/transpose(jvp(lm_head))/dot_general"),
        ]
    return tr.Trace.from_events(events)


def kanana2_ctx(shapes=None):
    cell = loader.load_cell(CELL)
    return types.SimpleNamespace(
        cell=cell, trace_data=kanana2_trace(), device={"kind": "TPU v5 lite"},
        shapes=shapes or {}, registry_span=lambda: None, registry_marks=[],
    )


def kanana2_reader(name):
    readers = loader.load_readers("per_layer", CELL)
    return dict((entry["name"], read) for entry, read in readers)[name]


@pytest.mark.parametrize("name,share", [
    ("shared_expert_share", 14.0), ("dense_mlp_share", 10.0), ("decode_share", 40.0),
    ("moe_share", 15.0), ("attention_share", 46.0), ("lm_head_share", 12.0), ("update_share", 60.0),
])
def test_share_readers_split_the_whole_execution(name, share):
    assert kanana2_reader(name)(kanana2_ctx()) == pytest.approx(share)


def test_roofline_readers_divide_the_least_seconds_by_the_scoped_time():
    ps = 1e-12
    shapes = {
        "latent_attend_update_cost": {"flops": 197e12 * 40 * ps, "bytes": 819e9 * 4 * ps},
        "latent_attend_decode_step_cost": {"flops": 0.0, "bytes": 819e9 * 10 * ps},
        "attention_forward_cost": {"flops": 197e12 * 15 * ps, "bytes": 0.0},
        "experts_update_cost": {"flops": 197e12 * 30 * ps, "bytes": 0.0},
        "experts_decode_step_cost": {"flops": 0.0, "bytes": 819e9 * 5 * ps},
        "rollout_length": 4, "updates_per_tick": 1,
    }
    ctx = kanana2_ctx(shapes)
    # 40 ps of least work in the 160 ps under ppo_epoch/latent_attend: kernel and plain backward
    assert kanana2_reader("latent_attend_update_roofline_share")(ctx) == pytest.approx(25.0)
    # 4 steps x 10 ps in the 80 ps under rollout/latent_attend: kernel and absorbed products
    assert kanana2_reader("latent_attend_decode_roofline_share")(ctx) == pytest.approx(50.0)
    # the flash kernel alone: 15 ps in its 60
    assert kanana2_reader("attention_roofline_share")(ctx) == pytest.approx(25.0)
    assert kanana2_reader("moe_experts_update_roofline_share")(ctx) == pytest.approx(50.0)
    assert kanana2_reader("moe_experts_decode_roofline_share")(ctx) == pytest.approx(50.0)


def test_the_carry_reader_reads_the_latent_kind():
    ctx = kanana2_ctx()
    gauge = lambda kind, value: (("stoix_tpu_lm_carry_bytes", (("kind", kind),), "value"), value)
    ctx.registry_marks = [(0, 0.0, dict([gauge("latent", 720 * 2**20), (("other", (), "value"), 7.0)]))]
    assert kanana2_reader("decode_carry_mib")(ctx) == pytest.approx(720.0)
    assert 5 * 128 * 512 * 576 * 4 == 720 * 2**20  # the cell's five caches, no row padded


def test_new_readers_find_nothing_in_a_program_without_the_scopes(monkeypatch):
    """The parent tree's scope table has none of this PR's scopes: every new
    reader returns None and the line leaves the metric out."""
    from benchmarks.harness import program_reads

    table = {"rollout": "rollout", "update_epoch": "ppo_epoch", "attention": "attention"}
    monkeypatch.setattr(program_reads, "program_scope", table.get)
    ctx = kanana2_ctx({
        "latent_attend_update_cost": {"flops": 1.0, "bytes": 1.0},
        "latent_attend_decode_step_cost": {"flops": 1.0, "bytes": 1.0}, "rollout_length": 4,
    })
    for name in ("shared_expert_share", "latent_attend_update_roofline_share",
                 "latent_attend_decode_roofline_share"):
        assert kanana2_reader(name)(ctx) is None, name


# --------------------------------------------------------------------------- #
# The mixer at the published widths and the timed batch, compiled for a
# described v5e: what the compiler refuses here costs no chip time.
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu from describing a chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("entry", ["forward", "gradient", "step", "evaluator_step"])
def test_the_latent_mixer_compiles_for_the_v5e_at_the_published_widths(one_chip, entry, monkeypatch):
    """A minibatch of 16 sequences of 512 tokens teacher-forced through the
    flash kernel at head sizes 192 | 128 (and its gradient), and one decode
    step of 128 sequences (the evaluator's 32) against their latent rows
    through the decode kernel: Mosaic takes both, and the expansion is not on
    the decode path."""
    import jax
    import jax.numpy as jnp
    from stoix_tpu.networks import mla

    # (code that asks `jax.default_backend()` sees the CPU here: steer it)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mixer = mla.LatentAttention(2048, 32, 512, 128, 64, 128, 1000000.0, 1e-6)
    struct = lambda *shape, dtype=jnp.float32: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    params = {"params": {"wq": struct(2048, 6144), "wkv_a": struct(2048, 576), "kv_norm": struct(512),
                         "wkv_b": struct(512, 8192), "wo": struct(4096, 2048)}}
    forward = lambda p, u: mixer.apply(p, u, method="forward")
    if entry == "forward":
        fn, args = forward, (params, struct(16, 512, 2048))
    elif entry == "gradient":
        fn = jax.grad(lambda p, u: forward(p, u).sum(), argnums=(0, 1))
        args = (params, struct(16, 512, 2048))
    else:
        batch = 128 if entry == "step" else 32
        fn = lambda p, u, rows, at: mixer.apply(p, u, mla.Latent(rows), at, method="step")
        args = (params, struct(batch, 2048), struct(batch, 512, 576), struct(dtype=jnp.int32))
    compiled = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert "latent_attend" in text and "latent_project" in text
    if entry in ("forward", "gradient"):
        assert "flash_attention" in text and "latent_decode_attention" not in text
        assert compiled.memory_analysis().temp_size_in_bytes < 2**31
    else:
        assert "latent_decode_attention" in text and "flash_attention" not in text
        # nothing of the expanded cache's size ([batch, 512, 32, 256]) is ever made
        assert compiled.memory_analysis().temp_size_in_bytes < 2**28
