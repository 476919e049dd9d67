"""The window-and-full attention token-policy cell
(`anakin_ppo_laguna_tokens_1chip`) on the CPU at a tiny preset: the whole path
a real run takes — driver, reference comparisons, stated-configuration checks,
result line — with the cell's configuration restated at the tiny widths;
faults the comparison has to refuse (a window layer that attends causally, a
ring written at the position, a full layer rotated over the whole head, a
missing gate); the cost functions behind its roofline readers; each of its
new readers on synthetic events; and XLA:TPU's and Mosaic's verdict on the
window layer at the published widths, for a described v5e, at no chip time."""

import time
import types

import pytest

import _paths  # noqa: F401
from benchmarks.harness import cell_runner, flops_swa, loader, peaks
from benchmarks.harness import trace_reduce as tr
from benchmarks.harness.trace_reduce import Event

CELL = "anakin_ppo_laguna_tokens_1chip"
TINY_ROPE = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 16, "beta_slow": 1, "beta_fast": 4,
        "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5,
    },
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
}
TINY_STATED = {
    # (6 | 8 query heads on 2 key/value heads of 16, a window of 6 in sequences of 20; 16
    # experts 48 wide: no projection has the router's shape, [64, 16])
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 48,
    "shared_expert_intermediate_size": 48, "num_attention_heads": 6,
    "num_attention_heads_per_layer": [6, 8, 8, 8, 6], "num_key_value_heads": 2, "head_dim": 16,
    "sliding_window": 6, "rope_parameters": TINY_ROPE, "num_experts": 4, "router_experts": 16,
    "num_experts_per_tok": 3, "vocab_size": 64, "num_minibatches": 4, "rollout_length": 20,
}
TINY_OVERRIDES = [
    "env=token_task", "network=laguna_xs2_moe", "arch.evaluation_greedy=True", "system.epochs=1",
    "system.router_aux_loss_coef=0.0",
    "network.actor_network.hidden_size=64", "network.actor_network.dense_width=96",
    "network.actor_network.num_heads=6", "network.actor_network.num_heads_per_layer=[6,8,8,8,6]",
    "network.actor_network.num_kv_heads=2", "network.actor_network.head_dim=16",
    "network.actor_network.sliding_window=6",
    "network.actor_network.rope_parameters.full_attention.original_max_position_embeddings=16",
    "network.actor_network.rope_parameters.full_attention.beta_fast=4",
    "network.actor_network.num_experts=16", "network.actor_network.experts_held=4",
    "network.actor_network.experts_per_token=3", "network.actor_network.expert_width=48",
    # (three windows and a remainder: the rings wrap three times)
    "env.kwargs.vocab_size=64", "env.kwargs.length=20", "system.rollout_length=20",
    "system.num_minibatches=4",
]
TINY_TRAFFIC = [
    "arch.total_num_envs=32", "arch.total_timesteps=~", "arch.num_updates=1000000",
    "arch.num_evaluation=1000000", "arch.num_eval_episodes=8",
]

# The faults and the stated keys are tried on the shortest stack that has both
# mixers and both feed-forwards (full + dense, window + routed): under half
# the five-layer stack's compile time a run.
SHALLOW_STATED = {
    "num_hidden_layers": 2, "layer_types": ["full_attention", "sliding_attention"],
    "mlp_layer_types": ["dense", "sparse"], "num_attention_heads_per_layer": [6, 8],
}
SHALLOW_OVERRIDES = [
    "network.actor_network.layer_types=[full_attention,sliding_attention]",
    "network.actor_network.num_heads_per_layer=[6,8]",
]


def tiny_cell(second_reading=False, tolerances=None, shallow=False, **config):
    """The cell restated at the tiny preset, data-parallel over the test
    session's virtual CPU devices (the program's mesh takes them all)."""
    import jax

    cell = loader.load_cell(CELL)._replace(chips=len(jax.devices()))
    reference = {**cell.config["reference"], "sample_sequences": 4,
                 "lower_precision_update": second_reading, **(tolerances or {})}
    return cell._replace(
        config={**cell.config, **TINY_STATED, **(SHALLOW_STATED if shallow else {}),
                "overrides": TINY_OVERRIDES + (SHALLOW_OVERRIDES if shallow else []),
                "reference": reference, **config},
        traffic={**cell.traffic, "overrides": TINY_TRAFFIC},
    )


@pytest.fixture()
def cpu_devices(monkeypatch):
    import jax

    monkeypatch.setattr(cell_runner, "_gate_devices", lambda cell, platform: jax.devices())


# Two float32 sides: the limits that tell one forward from another, restated.
TIGHT = {"logits_rms_tol": 1e-4, "log_prob_rms_tol": 1e-4, "values_rms_tol": 1e-4}


def run_tiny(seed, seconds=3.0, shallow=True, **config):
    # (an interval has to hold two whole windows on a machine that six test workers share)
    return cell_runner.run_cell(
        tiny_cell(shallow=shallow, **config), seed, seconds, False, time.perf_counter(),
        require_platform="cpu",
    )


@pytest.fixture(scope="module")
def tiny_run():
    import jax
    from unittest import mock

    with mock.patch.object(cell_runner, "_gate_devices", lambda cell, platform: jax.devices()):
        return run_tiny(3_000_000_019, 8.0, shallow=False, second_reading=True, tolerances=TIGHT)


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
    expert sets over all 16 experts identical, nothing dropped — teacher
    forced under the banded and the causal mask, and decoded through three
    rings and two caches at every slot and every position (so past the
    wrap), against the reference's explicit [T, T] masked softmax."""
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
    ("rollout_values_max", 1e-4), ("rollout_differs_from_decode", 0.0), ("rollout_returns", 1e-6),
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


def test_the_run_prints_both_further_readings_and_what_refuses_them(tiny_run):
    health = tiny_run["detail"]["health"]
    second = health["reference"]["lower_precision"]
    # bfloat16 is a different result: three decimal digits, not seven.
    assert second["logits_rms"] > 1e-3 and second["record_log_prob_rms"] > 1e-4
    assert second["update_params_worst_leaf"] > 1e-3
    # the window ignored is another model from the first wrapped position on
    ignored = health["reference"]["window_ignored"]
    assert ignored["logits_rms"] > 1e-2 and ignored["dropped_pairs"] == 0.0
    refused = health["reference"]["refused_by"]
    assert "logits_rms" in refused["lower_precision"] and "logits_rms" in refused["window_ignored"]
    leaves = set(health["reference"]["update_leaves"])
    assert leaves == set(second["update_leaves"])
    assert not any("expert_bias" in leaf for leaf in leaves)
    # both kinds' projections at their own head counts, the gates, the shared expert's and
    # the untied head moved and were compared
    assert {"actor/layer_0/mixer/wq", "actor/layer_0/mixer/wg", "actor/layer_1/mixer/wq",
            "actor/layer_2/mixer/wg", "actor/layer_3/mixer/k_norm", "actor/layer_4/mixer/wo",
            "actor/layer_1/ffn/shared/w1", "actor/layer_0/ffn/w2", "actor/lm_head",
            "actor/embed"} <= leaves
    counters = health["reference"]["counters"]
    assert 0.0 < counters["held_pairs_per_token"] < 3.0
    assert counters["router_bias_changed_share"] == 0.0  # no selection bias is published
    assert counters["dropped_pairs"] == 0.0
    assert {"learner_setup", "aot_warmup", "first_tick"} <= set(health["setup_phases"])


def test_a_reading_is_refused_by_the_limits_it_passes():
    reference = loader.load_reference("ppo_laguna", loader.load_cell(CELL).root)
    ref = {"max_tol": 0.4, "logits_rms_tol": 0.02, "values_rms_tol": 0.03, "expert_set_tol": 0.02,
           "log_prob_rms_tol": 0.0185, "log_prob_max_tol": 0.15}
    assert reference.refused_by({"logits_rms": 0.021, "values_rms": 0.01, "logits_max": 0.4}, ref) == ["logits_rms"]
    assert reference.refused_by({"record_log_prob_rms": 0.02, "expert_set_disagreement": 0.5}, ref) == [
        "expert_set_disagreement", "record_log_prob_rms",
    ]
    assert reference.refused_by({"logits_rms": float("nan")}, ref) == ["logits_rms"]
    assert reference.refused_by({"dropped_pairs": 1.0}, ref) == []


def test_the_drivers_shapes_carry_the_held_pairs_the_run_logged(cpu_devices):
    seen = {}
    real = flops_swa.swa_ppo_shapes

    def spy(config, **kwargs):
        seen.update(kwargs["held_pairs"])
        return real(config, **kwargs)

    import unittest.mock as mock

    with mock.patch.object(flops_swa, "swa_ppo_shapes", spy):
        run_tiny(11)
    assert 0.0 < seen["update"] < 3.0 and 0.0 < seen["rollout"] < 3.0


def _refused(result, *names):
    assert not result["correct"]
    for name in names:
        assert any(name in p for p in result["problems"]), (name, result["problems"])


def test_a_window_layer_that_attends_causally_is_not_correct(cpu_devices, monkeypatch):
    """The update's window layers under the causal mask (the band dropped):
    other teacher-forced logits than the reference's from the first position
    past the window on, and other parameters after the update; the decode,
    which reads its ring, is the reference's own."""
    from stoix_tpu.networks import lfm2

    real = lfm2.best_attention
    monkeypatch.setattr(lfm2, "best_attention", lambda q, k, v, causal, window=None: real(q, k, v, causal=causal))
    result = run_tiny(3, tolerances=TIGHT)
    _refused(result, "tf_logits_rms", "tf_values_rms", "reference update_")
    assert result["detail"]["errors"]["decode_logits_rms"] <= 1e-4


def test_a_ring_written_at_the_position_is_not_correct(cpu_devices, monkeypatch):
    """A window layer's decode that writes position t's row at t and not at t
    % W (past the ring, the write is clipped to its last row): other
    log-probs than the teacher-forced pass from the first wrapped position
    on, in the standalone decode program and in what the timed rollout
    stored."""
    import jax.numpy as jnp
    from stoix_tpu.networks import lfm2

    mixer = lfm2.GroupedQueryAttention
    real_step = mixer.step

    def step(self, u, state, length):
        if not self.window:
            return real_step(self, u, state, length)
        # the real step with the ring's row not wrapped
        batch = u.shape[0]
        q, k, v = self._qkv(u, jnp.broadcast_to(length, (batch,)))
        last = jnp.minimum(length, self.window - 1)
        state = type(state)(*lfm2.write_cache_rows(state.k, state.v, k, v, last))
        grouped = q.reshape(batch, self.num_kv_heads, -1, self.head_dim)
        attended = lfm2._attend_cache(grouped, state.k, state.v, last)
        attended = self._gated(attended.reshape(batch, self.num_heads, self.head_dim), u)
        return attended.reshape(batch, -1) @ self.wo, state

    monkeypatch.setattr(mixer, "step", step)
    result = run_tiny(9, tolerances=TIGHT)
    _refused(result, "decode_logits_rms", "rollout_log_prob_rms")
    assert result["detail"]["errors"]["tf_logits_rms"] <= 1e-4


def test_a_full_layer_rotated_over_the_whole_head_is_not_correct(cpu_devices, monkeypatch):
    """`partial_rotary_factor` 0.5 ignored: the full layers' second half of a
    head turns too, in both entry points alike — which agree with one another
    and not with the reference."""
    from stoix_tpu.networks import lfm2

    real = lfm2.Lfm2LM._rotation
    monkeypatch.setattr(
        lfm2.Lfm2LM, "_rotation", lambda self, kind: (real(self, kind)[0], None, real(self, kind)[2])
    )
    result = run_tiny(5, tolerances=TIGHT)
    _refused(result, "tf_logits_rms", "decode_logits_rms", "rollout_log_prob_rms")
    assert result["detail"]["errors"]["rollout_differs_from_decode"] == 0.0


def test_a_missing_gate_is_not_correct(cpu_devices, monkeypatch):
    from stoix_tpu.networks import lfm2

    monkeypatch.setattr(lfm2.GroupedQueryAttention, "_gated", lambda self, attended, u: attended)
    result = run_tiny(7, tolerances=TIGHT)
    _refused(result, "tf_logits_rms", "decode_logits_rms")


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
    _refused(run_tiny(5), "update_adam_steps", "update_params_all_leaves")


SHAPES = "parameter shapes differ from the stated layers and widths"


@pytest.mark.parametrize("stated,problem", [
    ({"moe_intermediate_size": 64}, SHAPES),
    ({"router_experts": 64}, SHAPES),
    ({"num_experts": 8}, SHAPES),
    ({"head_dim": 8}, SHAPES),
    ({"num_attention_heads_per_layer": [8, 8]}, SHAPES),
    ({"num_key_value_heads": 4}, SHAPES),
    ({"gating": False}, SHAPES),
    ({"mlp_layer_types": ["sparse", "sparse"]}, SHAPES),
    ({"shared_expert_intermediate_size": 96}, SHAPES),
    ({"num_hidden_layers": 1}, SHAPES),
    ({"sliding_window": 8}, "the decode carry holds"),
    ({"layer_types": ["full_attention", "full_attention"]}, "the decode carry holds"),
    ({"num_minibatches": 2}, "num_minibatches resolved to 4, stated 2"),
    ({"router_precision": "DEFAULT"}, "stated float32 at DEFAULT"),
    ({"parameter_dtype": "bfloat16"}, "parameters are ['float32'], stated bfloat16"),
])
def test_a_run_that_differs_from_what_the_file_states_is_not_correct(cpu_devices, stated, problem):
    result = run_tiny(1, **stated)
    assert not result["correct"]
    assert any(problem in p for p in result["problems"]), result["problems"]


def test_the_stated_carry_is_two_caches_and_three_rings():
    reference = loader.load_reference("ppo_laguna")
    config = loader.load_cell(CELL).config
    want = reference.expected_carry(config, 32)
    full, ring = (1024, 32, 8, 128), (512, 32, 8, 128)
    assert want == [full] * 2 + [ring] * 6 + [full] * 2
    mib = sum(4 * __import__("numpy").prod(shape) for shape in want) / 2**20
    assert mib == 512 + 384  # (1,280 without the ring)


def test_the_stated_tree_is_the_published_layer_and_the_share():
    import numpy as np

    reference = loader.load_reference("ppo_laguna")
    config = loader.load_cell(CELL).config
    want = reference.expected_shapes(config)
    count = lambda prefix: sum(int(np.prod(s)) for name, s in want.items() if name.startswith(prefix))
    assert want["embed"] == (12544, 2048) and want["lm_head"] == (2048, 12544)  # untied
    for layer, heads in enumerate([48, 64, 64, 64, 48]):
        assert want[f"layer_{layer}/mixer/wq"] == (2048, heads * 128)
        assert want[f"layer_{layer}/mixer/wo"] == (heads * 128, 2048)
        assert want[f"layer_{layer}/mixer/wg"] == (2048, heads)
        assert want[f"layer_{layer}/mixer/wk"] == want[f"layer_{layer}/mixer/wv"] == (2048, 1024)
        assert want[f"layer_{layer}/mixer/q_norm"] == want[f"layer_{layer}/mixer/k_norm"] == (128,)
    assert want["layer_0/ffn/w1"] == (2048, 8192) and want["layer_1/ffn/gate"] == (8, 2048, 512)
    assert want["layer_4/ffn/router"] == (2048, 256) and want["layer_4/ffn/shared/w2"] == (512, 2048)
    assert "layer_0/ffn/router" not in want and "layer_1/ffn/w1" not in want
    # ISSUE 44's table, leaf by leaf
    assert count("layer_0/mixer/") == 29_458_688 == count("layer_4/mixer/")
    assert count("layer_1/mixer/") == 37_880_064 == count("layer_3/mixer/")
    assert count("layer_0/ffn/") == 50_331_648 and count("layer_2/ffn/") == 28_836_096
    assert count("embed") + count("lm_head") == 51_380_224
    norms = sum(count(f"layer_{i}/{n}") for i in range(5) for n in ("operator_norm", "ffn_norm"))
    assert norms + count("final_norm") == 22_528
    assert sum(int(np.prod(shape)) for shape in want.values()) + 2049 == 389_638_401
    # every number of the published config that the share does not cut, under its own key
    published = {
        "hidden_size": 2048, "intermediate_size": 8192, "num_attention_heads": 48,
        "num_key_value_heads": 8, "head_dim": 128, "max_position_embeddings": 262144,
        "rms_norm_eps": 1e-06, "num_experts_per_tok": 8, "moe_intermediate_size": 512,
        "shared_expert_intermediate_size": 512, "sliding_window": 512, "partial_rotary_factor": 0.5,
        "moe_routed_scaling_factor": 2.5,
    }
    assert {key: config[key] for key in published} == published
    assert config["model_type"] == "laguna" and config["gating"] is True
    assert config["attention_bias"] is False and config["tie_word_embeddings"] is False
    assert config["moe_apply_router_weight_on_input"] is False
    assert config["rope_parameters"] == {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1, "beta_fast": 64,
            "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5,
        },
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096,
    }
    assert config["layer_types"] == ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert config["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert config["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types", "num_attention_heads_per_layer",
        "num_experts", "vocab_size",
    ]
    assert config["published"]["num_hidden_layers"] == 40 and config["published"]["num_experts"] == 256
    assert config["vocab_size"] * 8 == 100352 == config["published"]["vocab_size"]
    assert config["num_experts"] * 32 == 256 == config["router_experts"]
    readings = ("gating", "per-head RMSNorm", "Sigmoid router", "norm_topk_prob", "selection bias",
                "soft-capping", "shared expert", "rope_parameters", "head_dim 128", "float32",
                "value head", "1,024")
    assert all(any(reading in line for line in config["assumed"]) for reading in readings)


def test_the_benchmark_names_the_cell_its_traffic_and_its_metrics():
    bench = loader.load_benchmark()
    cells = [w["name"] for w in bench["workloads"]]
    assert len(cells) == 9 and cells[-1] == CELL
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    cell = loader.load_cell(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (1, "ppo_laguna_xs2_ep32_share", "gen1024_x32")
    assert "arch.total_num_envs=32" in cell.overrides and "arch.num_eval_episodes=16" in cell.overrides
    assert "system.rollout_length=1024" in cell.overrides and "env.kwargs.length=1024" in cell.overrides
    assert "system.num_minibatches=8" in cell.overrides
    assert cell.spec["warmup_ticks"] == 1 and cell.spec["trace_start_tick"] == 2
    assert cell.spec["trace_ticks"] == 2 and cell.spec["learn_check"] is None
    mine = {m["name"]: m for m in bench["per_layer"] if m.get("workloads") == [CELL]}
    assert sorted(mine) == [
        "window_attend_decode_roofline_share", "window_attend_update_roofline_share", "window_mixer_share",
    ]
    assert all(m["moves"] == "env_steps_per_s" and m["source"] == "device_trace" for m in mine.values())
    joined = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    assert {"decode_share", "moe_share", "moe_dispatch_share", "attention_share", "lm_head_share",
            "dense_mlp_share", "shared_expert_share", "expert_load_max_over_mean", "decode_carry_mib",
            "moe_experts_update_roofline_share", "moe_experts_decode_roofline_share",
            "attention_roofline_share", "setup_build_s", "setup_first_tick_s"} <= joined


MODEL = {
    "hidden_size": 2048, "layer_types": ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"],
    "num_heads_per_layer": [48, 64, 64, 64, 48], "num_kv_heads": 8, "head_dim": 128,
    "sliding_window": 512, "num_dense_layers": 1, "dense_width": 8192, "num_experts": 256,
    "experts_held": 8, "experts_per_token": 8, "expert_width": 512, "shared_width": 512,
    "vocab_size": 12544,
}


def test_flops_swa_counts_a_bands_pairs():
    assert flops_swa.band_pairs(1024, 512) == 393_472 == sum(min(t + 1, 512) for t in range(1024))
    assert flops_swa.triangle_pairs(1024) == 524_800
    assert flops_swa.band_pairs(512, 512) == flops_swa.triangle_pairs(512)  # one window IS causal
    assert flops_swa.band_pairs(20, 6) == sum(min(t + 1, 6) for t in range(20))
    assert flops_swa.band_pairs(8192, 512) / flops_swa.triangle_pairs(8192) < 0.13


def test_update_cost_counts_each_layer_at_its_own_head_count():
    cost = flops_swa.update_cost(32, 1024, 1, 8, MODEL)
    tokens = 32 * 1024
    assert cost["samples"] == tokens
    parts = cost["parts"]
    full = 2 * 2048 * 6144 + 2 * 2048 * 1024 + 2048 * 48
    window = 2 * 2048 * 8192 + 2 * 2048 * 1024 + 2048 * 64
    assert parts["projections"]["flops"] == 3 * 2.0 * tokens * (2 * full + 3 * window)
    assert parts["full_scores"]["flops"] == 2 * 3 * 32 * 4.0 * 524_800 * 48 * 128
    assert parts["window_scores"]["flops"] == 3 * 3 * 32 * 4.0 * 393_472 * 64 * 128
    # 0.25 pairs a token land on the 8 held experts of 256 under uniform routing, not 8
    assert parts["experts"]["flops"] == 4 * 3 * 3 * 2.0 * 0.25 * tokens * 2048 * 512
    assert parts["shared_experts"]["flops"] == 4 * 3 * 3 * 2.0 * tokens * 2048 * 512
    assert parts["dense_mlps"]["flops"] == 3 * 3 * 2.0 * tokens * 2048 * 8192
    assert parts["head"]["flops"] == 3 * 2.0 * tokens * 2048 * 12544
    assert parts["router"]["flops"] == 4 * 3 * 2.0 * tokens * 2048 * 256
    assert cost["flops"] == sum(p["flops"] for p in parts.values())
    # ISSUE 44's arithmetic: 6.0e13 operations an update, 0.7e13 of them attention pairs
    assert 5.5e13 < cost["flops"] < 6.5e13
    pairs = parts["full_scores"]["flops"] + parts["window_scores"]["flops"]
    assert 0.6e13 < pairs < 0.8e13
    mixers = (parts["projections"]["flops"] + pairs) / cost["flops"]
    assert 0.60 < mixers < 0.70


def test_a_ring_read_is_memory_bound_and_the_count_knows_no_tile():
    """The same work whatever computes it: the cost functions take shapes
    alone (no tile among them), and the kernel pair at two tile sizes gives
    one result (the count is of that result)."""
    import inspect

    for fn in (flops_swa.attend_update_cost, flops_swa.attend_decode_step_cost, flops_swa.update_cost):
        assert "tile" not in inspect.signature(fn).parameters and "tile" not in inspect.getsource(fn)
    rows = flops_swa.mean_live_rows("sliding_attention", 1024, MODEL)
    assert rows == 393_472 / 1024 and flops_swa.mean_live_rows("full_attention", 1024, MODEL) == 512.5
    step = flops_swa.attend_decode_step_cost(32, rows, 64, MODEL)
    # min(t + 1, 512) rows x 32 sequences x 8 KiB, each read once ...
    assert step["bytes"] - 32 * rows * 8192 == 4 * 32 * 2 * 64 * 128
    # ... three layers of it are ISSUE 44's 0.30 GB = 0.37 ms a step
    least = peaks.least_seconds(3 * step["flops"], 3 * step["bytes"], "TPU v5 lite")
    assert least["binds"] == "memory" and 0.36e-3 < least["seconds"] < 0.385e-3
    assert 0.30e9 < 3 * step["bytes"] < 0.31e9
    whole = flops_swa.attend_update_cost(32, 1024, 64, 393_472, MODEL)
    assert whole["flops"] == 3 * 32 * 4.0 * 393_472 * 64 * 128
    assert whole["bytes"] == 4 * 32 * 1024 * 128 * ((2 * 64 + 2 * 8) + (4 * 64 + 4 * 8))
    # float32 operands in and out: at 384 keys a query the band's products (6.3 ms a layer an
    # update) take less than moving q, k, v, the result and their gradients once (8.8 ms)
    least = peaks.least_seconds(whole["flops"], whole["bytes"], "TPU v5 lite")
    assert least["binds"] == "memory" and 0.6 < least["by_compute_s"] / least["by_memory_s"] < 0.8


def test_a_decode_steps_expert_weights_are_float32_less_what_the_chip_holds(cpu_devices):
    """32 tokens, each with 8 DIFFERENT experts of 256, reach 5.10 of the 8
    held ones; their weights are float32, flops_lm.py's count, and of the 403
    MB the four routed layers hold a v5e's 128 MiB of vector memory can keep
    a third from step to step of the rollout's loop (the compiled learner
    keeps three operands of twelve there, and a count that read every one
    from HBM every step read 107% on the chip: PERF.md section 6, PR 44)."""
    reached = flops_swa.held_experts_reached(32.0, MODEL)
    assert reached == pytest.approx(8 * (1 - (31 / 32) ** 32)) and 5.09 < reached < 5.11
    assert flops_swa.held_experts_reached(4096.0, MODEL) == pytest.approx(8.0)  # a minibatch reaches all
    share = flops_swa.from_hbm_share(MODEL, 4)
    assert share == pytest.approx(1 - 128 * 2**20 / (4 * 4 * 3 * 2048 * 512 * 8)) and 0.66 < share < 0.67
    assert flops_swa.from_hbm_share({**MODEL, "experts_held": 2}, 4) == 0.0  # all of it fits
    from stoix_tpu.utils import config as config_lib

    cell = loader.load_cell(CELL)
    config = config_lib.compose(config_lib.default_config_dir(), cell.config["default_yaml"], cell.overrides)
    shapes = flops_swa.swa_ppo_shapes(config, envs_per_chip=32, updates_per_tick=1)
    step = shapes["experts_decode_step_cost"]
    assert step["flops"] == 4 * 3 * 2.0 * 8 * 2048 * 512
    assert step["bytes"] == pytest.approx(4 * 4 * (3 * 2048 * 512 * reached * share + 8 * (2 * 2560 + 2560)))
    assert peaks.least_seconds(step["flops"], step["bytes"], "TPU v5 lite")["binds"] == "memory"
    # the update's count is flops_lm.py's over every held expert, untouched by the share
    update = shapes["experts_update_cost"]
    assert update["bytes"] == pytest.approx(
        8 * 4 * 4 * (3 * 2048 * 512 * 8 * 3 + 2 * 1024 * (2 * 2560 + 2560)), rel=1e-6
    )


@pytest.mark.parametrize("bias,worst", [
    (3e-5, 0.2),   # a remainder three times the reference's: a fifth of a step apart
    (1.1e-4, 1.0),  # one step of 1e-4 more than the reference took: not correct under 0.4
    (1e-5, 0.1),   # the bias right: the matrices' tenth is the worst leaf
], ids=["a_remainder", "a_step_more", "right"])
def test_a_leaf_of_one_number_is_held_to_an_adam_steps_size(bias, worst):
    """The critic's bias stays in the worst leaf: read as |got - want| over
    the critic's learning rate, where the matrices are read as before."""
    import numpy as np

    reference = loader.load_reference("ppo_laguna")
    tree = lambda w, b: ({"params": {"embed": np.full((4, 2), w, np.float32)}},
                         {"params": {"kernel": np.full((2, 1), w, np.float32), "bias": np.full((1,), b, np.float32)}})
    before, want = tree(1.0, 0.0), tree(2.0, 1e-5)
    got = tree(2.1, bias)  # the matrices a tenth off
    update, leaves = reference.update_errors(before, got, want, step_sizes=(3e-4, 1e-4))
    assert leaves["critic/bias"][1] == pytest.approx(abs(bias - 1e-5) / 1e-5, rel=1e-3)
    assert update["worst_leaf"] == pytest.approx(worst, rel=1e-3)
    assert update["all_leaves"] == pytest.approx(0.1, rel=1e-2)


@pytest.mark.parametrize("tile", [16, 32])
def test_two_tile_sizes_compute_the_same_band(tile):
    import jax
    import numpy as np
    from stoix_tpu.ops import pallas_attention

    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(key, (1, 70, 2, 32)) for key in keys)
    band = lambda t: pallas_attention.flash_attention(
        q, k, v, causal=True, block_q=t, block_k=t, interpret=True, window=24
    )
    np.testing.assert_allclose(np.asarray(band(tile)), np.asarray(band(8)), rtol=1e-5, atol=1e-5)


def test_the_shapes_hand_every_reader_its_cost(cpu_devices):
    from stoix_tpu.utils import config as config_lib

    cell = loader.load_cell(CELL)
    config = config_lib.compose(config_lib.default_config_dir(), cell.config["default_yaml"], cell.overrides)
    shapes = flops_swa.swa_ppo_shapes(config, envs_per_chip=32, updates_per_tick=1)
    assert shapes["model"] == MODEL and shapes["rollout_length"] == 1024 and shapes["num_minibatches"] == 8
    for key in ("update_cost", "experts_update_cost", "experts_decode_step_cost", "attention_forward_cost",
                "window_attend_update_cost", "window_attend_decode_step_cost"):
        assert shapes[key]["flops"] > 0 and shapes[key]["bytes"] > 0, key
    assert shapes["attention_forward_cost"]["flops"] == 2 * 32 * 4.0 * 524_800 * 48 * 128  # the full layers'
    assert shapes["window_attend_update_cost"] == shapes["update_cost"]["parts"]["window_scores"]


D0 = "/device:TPU:0"


def op(name, start, dur, path):
    stats = {"tf_op": path, "program": "jit_learner_fn"}
    return Event(D0, tr.OPS_LINE, f"%{name} = f32[8]{{0}} thing()", start, dur, stats)


def laguna_trace():
    """Three executions of a 1000 ps learner, the middle one whole. In it the
    rollout takes 400: a window layer 150 (its ring's read 60), a full layer
    80 (its cache's read 30), the dense feed-forward 30, the routed layer 50
    with a pathless grouped matmul inside, the shared expert 20, head 50, env
    20; the update 600: a window layer 250 (the banded kernel forward 40 and
    backward 80), a full layer 100 (the causal kernel forward 20, backward
    40), the dense feed-forward 50, experts 60, the shared expert 40, head
    100."""
    roll = "jit(learner_fn)/while/body/rollout/while/body/rollout_policy"
    sgd = "jit(learner_fn)/while/body/ppo_epoch/ppo_minibatch"
    fwd, bwd = f"{sgd}/jvp(Lfm2LM)", f"{sgd}/transpose(jvp(Lfm2LM))"
    events = []
    for start in (0, 2000, 4000):
        events.append(Event(D0, tr.MODULES_LINE, "jit_learner_fn(7)", start, 1000, {}))
        events += [
            op("while.20", start, 400, "jit(learner_fn)/while/body/rollout/while"),
            op("while.21", start + 400, 600, "jit(learner_fn)/while/body/ppo_epoch/while"),
            op("fusion.1", start, 90, f"{roll}/Lfm2LM/layer_1/window_mixer/mixer/dot_general"),
            op("fusion.2", start + 90, 60, f"{roll}/Lfm2LM/layer_1/window_mixer/mixer/window_attend/reduce_sum"),
            op("fusion.3", start + 150, 50, f"{roll}/Lfm2LM/layer_0/attention/mixer/dot_general"),
            op("fusion.4", start + 200, 30, f"{roll}/Lfm2LM/layer_0/attention/mixer/attention_scores/reduce_sum"),
            op("fusion.5", start + 230, 30, f"{roll}/Lfm2LM/layer_0/ffn/dense_mlp/dot_general"),
            op("while.6", start + 260, 50, f"{roll}/Lfm2LM/layer_1/ffn/moe/while"),
            op("ragged-dot-none.7", start + 270, 30, "ragged-dot-none"),
            op("fusion.8", start + 310, 20, f"{roll}/Lfm2LM/layer_1/ffn/shared/shared_expert/dot_general"),
            op("fusion.9", start + 330, 50, f"{roll}/Lfm2LM/lm_head/dot_general"),
            op("fusion.10", start + 380, 20, "jit(learner_fn)/while/body/rollout/while/body/rollout_env/rem"),
            op("fusion.11", start + 400, 130, f"{fwd}/layer_1/window_mixer/mixer/dot_general"),
            op("flash_attention.12", start + 530, 40, f"{fwd}/layer_1/window_mixer/mixer/window_attend/jit(flash_attention)/pallas_call"),
            op("flash_attention_bwd.13", start + 570, 80, f"{bwd}/layer_1/window_mixer/mixer/window_attend/jit(flash_attention)/pallas_call"),
            op("fusion.14", start + 650, 40, f"{bwd}/layer_0/attention/mixer/dot_general"),
            op("flash_attention.15", start + 690, 20, f"{fwd}/layer_0/attention/mixer/attention_scores/jit(flash_attention)/pallas_call"),
            op("flash_attention_bwd.16", start + 710, 40, f"{bwd}/layer_0/attention/mixer/attention_scores/jit(flash_attention)/pallas_call"),
            op("fusion.17", start + 750, 50, f"{bwd}/layer_0/ffn/dense_mlp/dot_general"),
            op("while.18", start + 800, 60, f"{bwd}/layer_1/ffn/moe/while"),
            op("ragged-dot-none.19", start + 810, 30, "ragged-dot-none"),
            op("fusion.22", start + 860, 40, f"{bwd}/layer_1/ffn/shared/shared_expert/dot_general"),
            op("fusion.23", start + 900, 100, f"{sgd}/transpose(jvp(lm_head))/dot_general"),
        ]
    return tr.Trace.from_events(events)


def laguna_ctx(shapes=None):
    cell = loader.load_cell(CELL)
    return types.SimpleNamespace(
        cell=cell, trace_data=laguna_trace(), device={"kind": "TPU v5 lite"},
        shapes=shapes or {}, registry_span=lambda: None, registry_marks=[],
    )


def laguna_reader(name):
    readers = loader.load_readers("per_layer", CELL)
    return dict((entry["name"], read) for entry, read in readers)[name]


@pytest.mark.parametrize("name,share", [
    ("window_mixer_share", 40.0), ("attention_share", 18.0), ("dense_mlp_share", 8.0),
    ("shared_expert_share", 6.0), ("decode_share", 40.0), ("moe_share", 11.0),
    ("lm_head_share", 15.0), ("update_share", 60.0),
])
def test_share_readers_split_the_whole_execution(name, share):
    """A window layer's time is `window_mixer_share`'s and no part of
    `attention_share`, which stays the full layers'."""
    assert laguna_reader(name)(laguna_ctx()) == pytest.approx(share)


def test_roofline_readers_divide_the_least_seconds_by_the_scoped_time():
    ps = 1e-12
    shapes = {
        "window_attend_update_cost": {"flops": 197e12 * 30 * ps, "bytes": 0.0},
        "window_attend_decode_step_cost": {"flops": 0.0, "bytes": 819e9 * 9 * ps},
        "attention_forward_cost": {"flops": 197e12 * 15 * ps, "bytes": 0.0},
        "rollout_length": 4, "updates_per_tick": 1,
    }
    ctx = laguna_ctx(shapes)
    # 30 ps of least work in the 120 ps under ppo_epoch/window_attend: forward and backward
    assert laguna_reader("window_attend_update_roofline_share")(ctx) == pytest.approx(25.0)
    # 4 steps x 9 ps in the 60 ps under rollout/window_attend
    assert laguna_reader("window_attend_decode_roofline_share")(ctx) == pytest.approx(60.0)
    # the full layers' kernels alone (60 ps under ppo_epoch/attention), not the window layers'
    assert laguna_reader("attention_roofline_share")(ctx) == pytest.approx(25.0)


def test_the_carry_reader_adds_the_ring_and_the_cache():
    ctx = laguna_ctx()
    gauge = lambda kind, value: (("stoix_tpu_lm_carry_bytes", (("kind", kind),), "value"), value)
    ctx.registry_marks = [(0, 0.0, dict([
        gauge("kv", 512 * 2**20), gauge("window_kv", 384 * 2**20), (("other", (), "value"), 7.0),
    ]))]
    assert laguna_reader("decode_carry_mib")(ctx) == pytest.approx(896.0)
    assert 2 * 2 * 1024 * 32 * 8 * 128 * 4 == 512 * 2**20 and 3 * 2 * 512 * 32 * 8 * 128 * 4 == 384 * 2**20


def test_new_readers_find_nothing_in_a_program_without_the_scopes(monkeypatch):
    """The parent tree's scope table has none of this PR's scopes: every new
    reader returns None and the line leaves the metric out."""
    from benchmarks.harness import program_reads

    table = {"rollout": "rollout", "update_epoch": "ppo_epoch", "attention": "attention"}
    monkeypatch.setattr(program_reads, "program_scope", table.get)
    ctx = laguna_ctx({
        "window_attend_update_cost": {"flops": 1.0, "bytes": 1.0},
        "window_attend_decode_step_cost": {"flops": 1.0, "bytes": 1.0}, "rollout_length": 4,
    })
    for name in ("window_mixer_share", "window_attend_update_roofline_share",
                 "window_attend_decode_roofline_share"):
        assert laguna_reader(name)(ctx) is None, name


# --------------------------------------------------------------------------- #
# The window layer at the published widths and the timed batch, compiled for a
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


@pytest.mark.parametrize("entry", ["forward", "gradient", "step", "evaluator_step", "decode_scan"])
def test_the_window_layer_compiles_for_the_v5e_at_the_published_widths(one_chip, entry, monkeypatch):
    """A minibatch of 4 sequences of 1,024 tokens through the norm-and-rotate
    kernel at 64 heads and the banded flash kernel pair (and their gradient),
    and one decode step of 32 sequences (the evaluator's 16) against their
    rings: XLA:TPU and Mosaic take both; the decode makes no copy of the ring
    beside the row it writes in place."""
    import jax
    import jax.numpy as jnp
    from stoix_tpu.networks import lfm2

    # (code that asks `jax.default_backend()` sees the CPU here: steer it)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mixer = lfm2.GroupedQueryAttention(2048, 64, 8, 128, 10000.0, 1e-6, window=512, gate=True)
    struct = lambda *shape, dtype=jnp.float32: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    params = {"params": {
        "wq": struct(2048, 8192), "wk": struct(2048, 1024), "wv": struct(2048, 1024),
        "wo": struct(8192, 2048), "wg": struct(2048, 64), "q_norm": struct(128), "k_norm": struct(128),
    }}
    forward = lambda p, u: mixer.apply(p, u, method="forward")
    if entry == "forward":
        fn, args = forward, (params, struct(4, 1024, 2048))
    elif entry == "gradient":
        fn = jax.grad(lambda p, u: forward(p, u).sum(), argnums=(0, 1))
        args = (params, struct(4, 1024, 2048))
    else:
        batch = 16 if entry == "evaluator_step" else 32
        step = lambda p, u, k, v, length: mixer.apply(p, u, lfm2.WindowKV(k, v), length, method="step")
        # `decode_scan`: the ring as a scan's carry, as the rollout holds it
        def scan(p, u, k, v, length):
            def one(carry, _):
                k, v, length, u = carry
                out, state = step(p, u, k, v, length)
                return (state.k, state.v, length + 1, out), None

            return jax.lax.scan(one, (k, v, length, u), None, 4)[0]

        fn = scan if entry == "decode_scan" else step
        ring = struct(512, batch, 8, 128)
        args = (params, struct(batch, 2048), ring, ring, struct(dtype=jnp.int32))
    compiled = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert "window_attend" in text
    if entry in ("forward", "gradient"):
        assert "flash_attention" in text and "qk_norm_rope" in text and "tpu_custom_call" in text
        assert ("flash_attention_bwd" in text) == (entry == "gradient")
        assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2**30
    else:
        # as a loop's carry the ring is updated in place: one copy at most, of the argument
        # (not donated here) before the loop, none inside it
        ring_shape = f"f32[512,{batch},8,128]"
        copies = [line for line in text.splitlines() if " copy(" in line and ring_shape in line]
        assert len(copies) <= 2 and not any("while" in line for line in copies), copies
