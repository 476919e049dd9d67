"""The prefilled-prompt token-policy cell (`anakin_ppo_mellum2_prompt_1chip`)
on the CPU at a tiny preset: the whole path a real run takes — driver,
reference comparisons, stated-configuration checks, result line — with the
cell's configuration restated at the tiny widths (a prompt of 15 tokens, no
multiple of the window of 6, before 9 generated ones); the further readings
and the faults the comparison has to refuse (a window layer that attends
causally, a ring filled with the first rows, a prefill that kept its keys
unrotated, a rollout that decodes from empty, a softmax router scored as a
sigmoid); the cost functions behind its roofline readers; each of its new
readers on synthetic events; and XLA:TPU's and Mosaic's verdict on the prefill
and on the decode at four key/value heads at the published widths, for a
described v5e, at no chip time."""

import time
import types

import pytest

import _paths  # noqa: F401
from benchmarks.harness import cell_runner, flops_mellum2, flops_swa, loader, peaks
from benchmarks.harness import trace_reduce as tr
from benchmarks.harness.trace_reduce import Event

CELL = "anakin_ppo_mellum2_prompt_1chip"
PROMPT, RESPONSE, WINDOW = 15, 9, 6
TINY_ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 16, "beta_fast": 4, "beta_slow": 1,
        "attention_factor": 1.2772588722239782,
    },
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
}
TINY_STATED = {
    # (16 query heads on 2 key/value heads of 16, eight queries a key/value head as published; 16
    # experts 48 wide: no projection has the router's shape, [64, 16])
    "hidden_size": 64, "moe_intermediate_size": 48, "num_attention_heads": 16,
    "num_key_value_heads": 2, "head_dim": 16, "sliding_window": WINDOW, "rope_parameters": TINY_ROPE,
    "num_experts": 4, "router_experts": 16, "num_experts_per_tok": 3, "vocab_size": 64,
    "num_minibatches": 4, "rollout_length": RESPONSE, "prompt_length": PROMPT,
    "attention_query_block": 8,
}
TINY_OVERRIDES = [
    "env=token_task", "network=mellum2_moe", "arch.evaluation_greedy=True", "system.epochs=1",
    "system.router_aux_loss_coef=0.0",
    "network.actor_network.hidden_size=64", "network.actor_network.num_heads=16",
    "network.actor_network.num_kv_heads=2", "network.actor_network.head_dim=16",
    f"network.actor_network.sliding_window={WINDOW}",
    "network.actor_network.rope_parameters.full_attention.original_max_position_embeddings=16",
    "network.actor_network.rope_parameters.full_attention.beta_fast=4",
    "network.actor_network.num_experts=16", "network.actor_network.experts_held=4",
    "network.actor_network.experts_per_token=3", "network.actor_network.expert_width=48",
    "env.kwargs.vocab_size=64", f"env.kwargs.length={RESPONSE}", f"env.kwargs.prompt_length={PROMPT}",
    f"system.rollout_length={RESPONSE}", "system.num_minibatches=4",
]
TINY_TRAFFIC = [
    "arch.total_num_envs=32", "arch.total_timesteps=~", "arch.num_updates=1000000",
    "arch.num_evaluation=1000000", "arch.num_eval_episodes=8",
]
# The faults and the stated keys are tried on the shortest stack that has both
# mixers (window, full): half the four-layer stack's compile time a run.
SHALLOW_STATED = {
    "num_hidden_layers": 2, "layer_types": ["sliding_attention", "full_attention"],
    "mlp_layer_types": ["sparse", "sparse"],
}
SHALLOW_OVERRIDES = ["network.actor_network.layer_types=[sliding_attention,full_attention]"]


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


# The limits a reading of the forward is held to (references/ppo_mellum2.py `_LIMITS`).
FORWARD_LIMITS = ("max_tol", "logits_rms_tol", "values_rms_tol", "expert_set_tol", "all_expert_set_tol",
                  "log_prob_rms_tol", "log_prob_max_tol")
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

    # (the driver's cost function, spied on: what it was handed and what it handed on)
    seen, real = {}, flops_mellum2.mellum2_ppo_shapes

    def spy(config, **kwargs):
        seen.update(kwargs["held_pairs"], shapes=real(config, **kwargs))
        return seen["shapes"]

    with mock.patch.object(cell_runner, "_gate_devices", lambda cell, platform: jax.devices()), \
            mock.patch.object(flops_mellum2, "mellum2_ppo_shapes", spy):
        result = run_tiny(3_000_000_019, 8.0, shallow=False, second_reading=True, tolerances=TIGHT)
    return {**result, "seen_by_the_cost_function": seen}


def test_the_cell_runs_through_run_cell_and_build_result(tiny_run):
    assert tiny_run["correct"], tiny_run["problems"]
    assert set(tiny_run["metrics"]) == {"env_steps_per_s", "setup_s"}
    assert tiny_run["attempted"] >= 1 and tiny_run["failed"] == 0
    assert tiny_run["detail"]["compiles_in_interval"] == 0
    assert tiny_run["detail"]["health"]["preempted"]
    # an env step is one GENERATED token: 32 sequences x 9 a window, the prompt's 15 uncounted
    assert tiny_run["detail"]["interval_steps"] % (32 * RESPONSE) == 0


@pytest.mark.parametrize("entry", ["tf", "decode"])
@pytest.mark.parametrize("name", [
    "logits_max", "logits_rms", "values_max", "values_rms", "expert_set_disagreement",
    "dropped_pairs",
])
def test_the_reference_compares_both_entry_points_at_the_responses_positions(tiny_run, entry, name):
    """On the CPU both sides are float32: far inside the chip's tolerances,
    expert sets over all 16 experts identical, nothing dropped — teacher
    forced over [prefix ; response] under the banded and the causal mask with
    the head on the response, and decoded through three rings and a cache
    that ONE prefill filled, at every slot and every response position,
    against the reference's one forward over the whole sequence."""
    error = tiny_run["detail"]["errors"][f"{entry}_{name}"]
    assert error <= (1e-4 if name.startswith(("logits", "values")) else 2.5e-7 if "dropped" in name else 0.0)
    assert tiny_run["detail"]["tolerances"][f"{entry}_{name}"] >= 0.0


@pytest.mark.parametrize("name", [
    "tf_all_expert_set_disagreement", "decode_all_expert_set_disagreement", "prefill_dropped_pairs",
    "prefill_dropped_pairs_timed", "rollout_prompts_alike", "rollout_prompt_outside_slice",
])
def test_the_reference_compares_the_prefixs_positions_too(tiny_run, name):
    # (a ratio of pairs to positions is a float32 quotient: a unit in the last place of 3.0 at most)
    assert tiny_run["detail"]["errors"][name] <= (2.5e-7 if "dropped" in name else 0.0)


@pytest.mark.parametrize("part", [
    "total_loss", "actor_loss", "value_loss", "entropy", "aux_loss", "expert_load_max_over_mean",
    "held_pairs_per_token",
])
def test_the_timed_windows_logged_losses_and_counters_match_the_replay(tiny_run, part):
    assert tiny_run["detail"]["errors"][f"update_{part}"] <= 1e-4


@pytest.mark.parametrize("name,limit", [
    ("rollout_log_prob_rms", 1e-4), ("rollout_log_prob_max", 1e-4), ("rollout_values_rms", 1e-4),
    ("rollout_values_max", 1e-4), ("rollout_differs_from_decode", 0.0), ("rollout_returns", 1e-6),
    ("rollout_dropped_pairs", 2.5e-7), ("rollout_held_pairs_per_token", 0.05),
    ("update_dropped_pairs", 2.5e-7), ("update_dropped_pairs_counted", 0.0),
    ("update_adam_steps", 0.0), ("update_params_worst_leaf", 1e-3), ("update_params_all_leaves", 1e-3),
])
def test_the_reference_replays_the_timed_window(tiny_run, name, limit):
    """One more call of the learner the run timed, on the run's final state:
    what its rollout stored — decoded through the prefilled state — against
    the reference's whole-sequence forward, and what its Adam steps changed
    against the plain replay over [prefix ; response] — float32 on both
    sides."""
    assert tiny_run["detail"]["errors"][name] <= limit
    assert tiny_run["detail"]["tolerances"][name] >= 0.0


def test_the_run_prints_three_further_readings_and_what_refuses_them(tiny_run):
    health = tiny_run["detail"]["health"]
    second = health["reference"]["lower_precision"]
    # bfloat16 is a different result: three decimal digits, not seven.
    assert second["logits_rms"] > 1e-3 and second["record_log_prob_rms"] > 1e-4
    assert second["update_params_worst_leaf"] > 1e-3
    # the window ignored is another model from the first wrapped position on; so is the prefix dropped
    ignored, dropped = health["reference"]["window_ignored"], health["reference"]["prefix_dropped"]
    # (logits are compared where the expert sets agree: where none does the reading is NaN,
    # which no limit passes either)
    assert not ignored["logits_rms"] <= 1e-2 and ignored["dropped_pairs"] == 0.0
    assert not dropped["logits_rms"] <= 1e-2 and not dropped["record_log_prob_rms"] <= 1e-2
    assert ignored["expert_set_disagreement"] > 0.05 and dropped["expert_set_disagreement"] > 0.05
    assert ignored["all_expert_set_disagreement"] > 0.05 and "all_expert_set_disagreement" not in dropped
    assert second["agreeing_tokens"] > 0.5
    refused = health["reference"]["refused_by"]
    assert "logits_rms" in refused["lower_precision"]
    assert all("expert_set_disagreement" in refused[name] for name in ("window_ignored", "prefix_dropped"))
    assert len(health["reference"]["reference_gradient_norms"]) == 4  # a minibatch: (trunk, value head)
    leaves = set(health["reference"]["update_leaves"])
    assert leaves == set(second["update_leaves"])
    assert not any("expert_bias" in leaf or "shared" in leaf or "wg" in leaf for leaf in leaves)
    assert {"actor/layer_0/mixer/wq", "actor/layer_1/mixer/wk", "actor/layer_2/mixer/k_norm",
            "actor/layer_3/mixer/wo", "actor/layer_0/ffn/router", "actor/layer_3/ffn/gate",
            "actor/lm_head", "actor/embed", "critic/bias"} <= leaves
    counters = health["reference"]["counters"]
    for phase in ("", "rollout_", "prefill_"):
        assert 0.0 < counters[phase + "held_pairs_per_token"] < 3.0
    assert counters["dropped_pairs"] == 0.0
    assert {"learner_setup", "aot_warmup", "first_tick"} <= set(health["setup_phases"])


def test_a_reading_is_refused_by_the_limits_it_passes():
    reference = loader.load_reference("ppo_mellum2", loader.load_cell(CELL).root)
    ref = {"max_tol": 0.4, "logits_rms_tol": 0.02, "values_rms_tol": 0.03, "expert_set_tol": 0.02,
           "all_expert_set_tol": 0.01, "log_prob_rms_tol": 0.0185, "log_prob_max_tol": 0.15}
    assert reference.refused_by({"logits_rms": 0.021, "values_rms": 0.01, "logits_max": 0.4}, ref) == ["logits_rms"]
    assert reference.refused_by({"record_log_prob_rms": 0.02, "expert_set_disagreement": 0.5}, ref) == [
        "expert_set_disagreement", "record_log_prob_rms",
    ]
    # (nothing to compare — no token's expert sets agree — is no reading: the sets refuse it)
    assert reference.refused_by({"logits_rms": float("nan"), "expert_set_disagreement": 0.6}, ref) == [
        "expert_set_disagreement",
    ]
    assert reference.refused_by({"dropped_pairs": 1.0}, ref) == []


def test_the_drivers_shapes_carry_the_held_pairs_the_run_logged_and_the_prompt(tiny_run):
    seen = tiny_run["seen_by_the_cost_function"]
    assert all(0.0 < seen[phase] < 3.0 for phase in ("update", "rollout", "prefill"))
    assert seen["shapes"]["prompt_length"] == PROMPT and seen["shapes"]["rollout_length"] == RESPONSE


def _refused(result, *names):
    assert not result["correct"]
    for name in names:
        assert any(name in p for p in result["problems"]), (name, result["problems"])


def test_a_window_layer_that_attends_causally_is_not_correct(cpu_devices, monkeypatch):
    """The teacher-forced window layers under the causal mask (the band
    dropped), in the update and in the prefill alike: other logits than the
    reference's, another prefilled state (the full layer's keys come from
    other hidden states), other parameters after the update."""
    from stoix_tpu.networks import lfm2

    real = lfm2.best_attention
    monkeypatch.setattr(lfm2, "best_attention", lambda q, k, v, causal, window=None: real(q, k, v, causal=causal))
    _refused(run_tiny(3, tolerances=TIGHT), "tf_logits_rms", "decode_logits_rms", "reference update_")


def _prefill_with(monkeypatch, rows):
    """`GroupedQueryAttention.prefill` with the state's rows chosen by
    `rows(mixer, k [B, P, kv, hd], size) -> [rows, B, kv, hd]`."""
    import jax
    import jax.numpy as jnp
    from stoix_tpu.networks import lfm2

    def prefill(self, u, state):
        out, k, v = self._sequence(u)
        write = lambda cache, new: jax.lax.dynamic_update_slice(
            cache, rows(self, jnp.swapaxes(new, 0, 1), cache.shape[0]), (0, 0, 0, 0)
        )
        return out, type(state)(write(state.k, k), write(state.v, v))

    monkeypatch.setattr(lfm2.GroupedQueryAttention, "prefill", prefill)


def test_a_ring_filled_with_the_first_rows_is_not_correct(cpu_devices, monkeypatch):
    """A prefill that keeps a window layer's FIRST W positions (position t at
    t) and not its newest W at t % W: the teacher-forced pass is the
    reference's own, the decode through that ring and what the timed rollout
    stored are not."""
    _prefill_with(monkeypatch, lambda mixer, k, size: k[:size])
    result = run_tiny(9, tolerances=TIGHT)
    _refused(result, "decode_logits_rms", "rollout_log_prob_rms")
    assert result["detail"]["errors"]["tf_logits_rms"] <= 1e-4
    assert result["detail"]["errors"]["tf_all_expert_set_disagreement"] == 0.0


def test_a_prefill_that_keeps_its_keys_unrotated_is_not_correct(cpu_devices, monkeypatch):
    import jax.numpy as jnp
    from stoix_tpu.networks import lfm2

    real = lfm2.GroupedQueryAttention._sequence

    def unrotated(self, u):
        out, _, v = real(self, u)
        k = lfm2.rms_norm((u @ self.wk).reshape(*v.shape), self.k_norm, self.rms_eps)
        return out, k.astype(jnp.float32), v

    monkeypatch.setattr(lfm2.GroupedQueryAttention, "_sequence", unrotated)
    result = run_tiny(5, tolerances=TIGHT)
    _refused(result, "decode_logits_rms", "rollout_log_prob_rms")
    assert result["detail"]["errors"]["tf_logits_rms"] <= 1e-4


def test_a_rollout_that_decodes_from_empty_is_not_correct(cpu_devices, monkeypatch):
    """The fault the prompt was written to make visible: a rollout (and a
    standalone decode) that starts at position P with nothing in its rings
    and cache — what every token cell before this one did at P = 0."""
    import jax
    from stoix_tpu.networks import lfm2

    real = lfm2.Lfm2LM.prefill

    def forgetful(self, carry, tokens):
        filled, stats = real(self, carry, tokens)
        emptied = jax.tree.map(lambda fresh, _: fresh, carry.layers, filled.layers)
        return filled._replace(layers=emptied), stats

    monkeypatch.setattr(lfm2.Lfm2LM, "prefill", forgetful)
    result = run_tiny(7, tolerances=TIGHT)
    _refused(result, "decode_logits_rms", "rollout_log_prob_rms", "rollout_values_rms")
    assert result["detail"]["errors"]["rollout_differs_from_decode"] == 0.0  # (both forgot alike)


def test_a_softmax_router_scored_as_a_sigmoid_is_not_correct(cpu_devices):
    """The same experts chosen (both are monotone in the logits), other
    weights on them: the expert sets agree and the logits do not."""
    result = run_tiny(13, tolerances=TIGHT, overrides=TINY_OVERRIDES + SHALLOW_OVERRIDES + [
        "network.actor_network.router_scoring=sigmoid",
    ])
    # (the stored action's log-prob moves a ninth as far as the logits do, 1.2e-4
    # at this seed: too near the tight limit to ask for by name)
    _refused(result, "tf_logits_rms", "decode_logits_rms")


def test_a_learner_that_skips_minibatches_is_not_correct(cpu_devices, monkeypatch):
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


def test_a_learner_that_leaves_half_of_every_minibatch_out_is_not_correct(cpu_devices, monkeypatch):
    """Every Adam step is taken, on half the minibatch's sequences: nothing
    but the parameter change tells, and the limits AS COMMITTED refuse it
    (no override: the file's `update_all_leaves_tol` and
    `update_worst_leaf_tol` are what the chip's runs are held to)."""
    import jax
    from stoix_tpu.systems.ppo.anakin import ff_lm_ppo

    real = ff_lm_ppo.lm_ppo_loss

    def half(networks, params, batch, **kwargs):
        sequences = batch["token"].shape[0]
        if sequences > 1:
            return real(networks, params, jax.tree.map(lambda x: x[: sequences // 2], batch), **kwargs)
        # (one sequence a shard: the odd shards' are left out of the mean)
        total, info = real(networks, params, batch, **kwargs)
        return total * 2.0 * (jax.lax.axis_index("data") % 2 == 0), info

    monkeypatch.setattr(ff_lm_ppo, "lm_ppo_loss", half)
    # (the forward's limits opened wide, so that the bfloat16 reading passes them all: see below)
    result = run_tiny(5, tolerances={name: 1.0 for name in FORWARD_LIMITS})
    _refused(result, "update_params_all_leaves")
    assert result["compared"]["update_adam_steps"]["value"] == 0.0
    # ... and a bfloat16 reading that NO limit refuses is a problem of the run, like a
    # window-ignored or a prefix-dropped one: the comparison then parts no precision
    assert result["detail"]["health"]["reference"]["refused_by"]["lower_precision"] == []
    _refused(result, "lower precision reading passes every limit")


SHAPES = "parameter shapes differ from the stated layers and widths"


@pytest.mark.parametrize("stated,problem", [
    ({"moe_intermediate_size": 64}, SHAPES),
    ({"num_key_value_heads": 4}, SHAPES),
    ({"sliding_window": 8}, "the decode carry holds"),
    ({"prompt_length": 12}, "the decode carry holds"),
    ({"num_minibatches": 2}, "num_minibatches resolved to 4, stated 2"),
    ({"router_precision": "DEFAULT"}, "stated float32 at DEFAULT"),
    ({"parameter_dtype": "bfloat16"}, "parameters are ['float32'], stated bfloat16"),
])
def test_a_run_that_differs_from_what_the_file_states_is_not_correct(cpu_devices, stated, problem):
    result = run_tiny(1, **stated)
    assert not result["correct"]
    assert any(problem in p for p in result["problems"]), result["problems"]


def test_the_stated_carry_is_three_rings_and_one_cache_of_prompt_and_response():
    import numpy as np

    reference = loader.load_reference("ppo_mellum2")
    config = loader.load_cell(CELL).config
    want = reference.expected_carry(config, 16)
    full, ring = (3584, 16, 4, 128), (1024, 16, 4, 128)
    assert want == [ring] * 6 + [full] * 2
    mib = sum(4 * np.prod(shape) for shape in want) / 2**20
    assert mib == 192 + 224  # (896 without the ring)


def test_the_stated_tree_is_the_published_layer_and_the_share():
    import json

    import numpy as np

    reference = loader.load_reference("ppo_mellum2")
    config = loader.load_cell(CELL).config
    want = reference.expected_shapes(config)
    count = lambda prefix: sum(int(np.prod(s)) for name, s in want.items() if name.startswith(prefix))
    assert want["embed"] == (12288, 2304) and want["lm_head"] == (2304, 12288)  # untied
    for layer in range(4):
        assert want[f"layer_{layer}/mixer/wq"] == (2304, 4096) and want[f"layer_{layer}/mixer/wo"] == (4096, 2304)
        assert want[f"layer_{layer}/mixer/wk"] == want[f"layer_{layer}/mixer/wv"] == (2304, 512)
        assert want[f"layer_{layer}/mixer/q_norm"] == want[f"layer_{layer}/mixer/k_norm"] == (128,)
        assert want[f"layer_{layer}/ffn/router"] == (2304, 64)
        assert want[f"layer_{layer}/ffn/gate"] == (8, 2304, 896) and want[f"layer_{layer}/ffn/down"] == (8, 896, 2304)
    assert not any("expert_bias" in name or "shared" in name or "/wg" in name or "/w1" in name for name in want)
    # the count the configuration file states, leaf by leaf
    assert count("layer_0/mixer/") == 21_233_920 and count("layer_3/ffn/") == 49_692_672
    assert count("layer_2/") == 70_931_200 and count("embed") + count("lm_head") == 56_623_104
    assert sum(int(np.prod(shape)) for shape in want.values()) + 2305 == 340_352_513
    # every number of the catalog's config under its own key, the cut ones as `reduced` names them
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as handle:
        rows = [json.loads(line) for line in handle if line.strip()]
    (published,) = [row for row in rows if row["name"] == "Mellum2-12B-A2.5B-Instruct"]
    assert config["source"] == published["source_url"]
    assert config["reduced"] == ["num_hidden_layers", "layer_types", "mlp_layer_types", "num_experts", "vocab_size"]
    for key, value in published["config"].items():
        if key in config["reduced"]:
            assert config[key] != value
        else:
            assert config[key] == value, key
    assert config["layer_types"] == published["config"]["layer_types"][:4]
    assert config["mlp_layer_types"] == published["config"]["mlp_layer_types"][:4] == ["sparse"] * 4
    assert config["published"]["num_hidden_layers"] == 28 and config["published"]["num_experts"] == 64
    assert config["vocab_size"] * 8 == 98304 == config["published"]["vocab_size"]
    assert config["num_experts"] * 8 == 64 == config["router_experts"]
    assert (config["prompt_length"], config["rollout_length"]) == (3072, 512)
    readings = ("per-head RMSNorm", "softmax", "norm_topk_prob", "selection bias", "shared expert",
                "rope_parameters", "intermediate_size", "MTP", "float32", "value head", "3,072")
    assert all(any(reading in line for line in config["assumed"]) for reading in readings)
    assert config["rehearsed_by"] == "tests/benchmark/test_benchmark_mellum2_cell.py"


def test_the_cell_draws_its_embedding_at_one_and_says_why():
    """What gives every seed the same work (PERF.md section 6, PR 47): the
    cell's own override of the network's default, stated under `assumed`;
    the tiny preset keeps the default (a prompt of 15 tokens has no mean to
    route by, and at hidden 64 a unit embedding hides the experts)."""
    cell = loader.load_cell(CELL)
    assert "network.actor_network.embedding_init_std=1.0" in cell.overrides
    assert not any("embedding_init_std" in o for o in TINY_OVERRIDES)
    stated = [a for a in cell.config["assumed"] if a.startswith("Initialisation")]
    assert len(stated) == 1 and "embedding_init_std=1.0" in stated[0] and "same work" in stated[0]


def test_the_benchmark_names_the_cell_its_traffic_and_its_metrics():
    """Membership, not place or count: a later cell leaves this true."""
    bench = loader.load_benchmark()
    cells = [w["name"] for w in bench["workloads"]]
    assert CELL in cells
    cell = loader.load_cell(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (1, "ppo_mellum2_moe_ep8_share", "prompt3072_gen512_x16")
    for override in ("arch.total_num_envs=16", "arch.num_eval_episodes=16", "system.rollout_length=512",
                     "env.kwargs.length=512", "env.kwargs.prompt_length=3072", "system.num_minibatches=8",
                     "system.epochs=1", "env.kwargs.vocab_size=12288", "network=mellum2_moe"):
        assert override in cell.overrides, override
    assert cell.spec["warmup_ticks"] == 1 and cell.spec["trace_start_tick"] == 2
    assert cell.spec["trace_ticks"] == 2 and cell.spec["learn_check"] is None
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, layer in (("prefill_share", "Learner program"), ("prefill_roofline_share", "Kernels"),
                        ("full_attend_decode_roofline_share", "Kernels")):
        entry = entries[name]
        assert CELL in entry["workloads"] and entry["layer"] == layer and entry["unit"] == "%"
        assert entry["moves"] == "env_steps_per_s" and entry["source"] == "device_trace"
    joined = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert {"decode_share", "moe_share", "moe_dispatch_share", "attention_share", "lm_head_share",
            "expert_load_max_over_mean", "decode_carry_mib", "moe_experts_update_roofline_share",
            "moe_experts_decode_roofline_share", "attention_roofline_share", "window_mixer_share",
            "window_attend_update_roofline_share", "window_attend_decode_roofline_share",
            "update_roofline_share", "peak_hbm_mib", "device_idle_share", "setup_build_s",
            "setup_first_tick_s", "setup_unspanned_s"} <= joined
    assert not {"dense_mlp_share", "shared_expert_share"} & joined  # no dense layer, no shared expert
    # every set-up reader still lists the cells in order
    for name, entry in entries.items():
        if name.startswith("setup_"):
            assert entry["workloads"] == cells, name


# --------------------------------------------------------------------------- #
# The cost functions
# --------------------------------------------------------------------------- #

MODEL = {
    "hidden_size": 2304, "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "num_heads": 32, "num_heads_per_layer": [32] * 4, "num_kv_heads": 4, "head_dim": 128,
    "sliding_window": 1024, "num_experts": 64, "experts_held": 8, "experts_per_token": 8,
    "expert_width": 896, "vocab_size": 12288,
}
P, G, W = 3072, 512, 1024


def test_the_decode_means_run_over_the_positions_after_the_prompt():
    """flops_swa.py counts min(t + 1, W) from t = 0 and stays as it is; here
    the inputs sit at P .. P + G - 1: every ring is full at every step, the
    cache holds 3,328.5 rows at the mean step."""
    assert flops_mellum2.mean_live_rows("sliding_attention", P, G, MODEL) == W
    assert flops_mellum2.mean_live_rows("full_attention", P, G, MODEL) == P + (G + 1) / 2 == 3328.5
    # from an empty carry they are flops_swa.py's
    swa = {**MODEL, "sliding_window": 512}
    assert flops_mellum2.mean_live_rows("sliding_attention", 0, 1024, swa) == flops_swa.mean_live_rows("sliding_attention", 1024, swa)
    assert flops_mellum2.mean_live_rows("full_attention", 0, 1024, swa) == 512.5
    # a prompt shorter than the window: the ring is still filling
    assert flops_mellum2.mean_live_rows("sliding_attention", 4, 4, {**MODEL, "sliding_window": 6}) == (5 + 6 + 6 + 6) / 4
    step = flops_swa.attend_decode_step_cost(16, 3328.5, 32, MODEL)
    # ISSUE 47: the full cache's live rows 13.6 MB a sequence, three rings 12.6 MB
    assert 13.5e6 < (step["bytes"] - 4 * 16 * 2 * 32 * 128) / 16 < 13.7e6
    assert 12.5e6 < 3 * 1024 * 2 * 4 * 128 * 4 < 12.7e6


def test_update_cost_passes_prompt_and_response_through_the_stack_and_the_response_through_the_head():
    cost = flops_mellum2.update_cost(16, P, G, 1, 8, MODEL)
    positions, response = 16 * (P + G), 16 * G
    assert cost["samples"] == response  # an env step is one GENERATED token
    parts = cost["parts"]
    mixer = 2 * 2304 * 4096 + 2 * 2304 * 512
    assert parts["projections"]["flops"] == 4 * 3 * 2.0 * positions * mixer
    band, triangle = flops_swa.band_pairs(P + G, W), flops_swa.triangle_pairs(P + G)
    assert band == W * (W + 1) / 2 + (P + G - W) * W and triangle == 3584 * 3585 / 2
    assert 0.48 < band / triangle < 0.50  # ISSUE 47: 49% of the triangle where Laguna's band visits 75%
    assert parts["window_scores"]["flops"] == 3 * 3 * 16 * 4.0 * band * 32 * 128
    assert parts["full_scores"]["flops"] == 3 * 16 * 4.0 * triangle * 32 * 128
    # one held pair a token a layer under uniform routing (8 x 8 / 64), not 8
    assert parts["experts"]["flops"] == 4 * 3 * 3 * 2.0 * 1.0 * positions * 2304 * 896
    assert parts["router"]["flops"] == 4 * 3 * 2.0 * positions * 2304 * 64
    assert parts["head"]["flops"] == 3 * 2.0 * response * 2304 * 12288  # not on P + G
    assert cost["flops"] == sum(p["flops"] for p in parts.values())
    # ISSUE 47's arithmetic: 5.4e13 operations an update, 1.45e13 of them attention pairs
    assert 5.0e13 < cost["flops"] < 5.8e13
    pairs = parts["full_scores"]["flops"] + parts["window_scores"]["flops"]
    assert 1.2e13 < pairs < 1.3e13  # (ISSUE 47 wrote 1.45e13: 15.86 M pairs a head a sequence x 32 x 16 x 1,536)


def test_prefill_cost_is_one_forward_over_the_prompt_with_no_head_and_the_state_written_once():
    cost = flops_mellum2.prefill_cost(16, P, MODEL)
    parts, positions = cost["parts"], 16 * P
    assert "head" not in parts
    mixer = 2 * 2304 * 4096 + 2 * 2304 * 512
    assert parts["projections"]["flops"] == 4 * 2.0 * positions * mixer  # forward alone
    assert parts["window_scores"]["flops"] == 3 * 16 * 4.0 * flops_swa.band_pairs(P, W) * 32 * 128
    assert parts["full_scores"]["flops"] == 16 * 4.0 * flops_swa.triangle_pairs(P) * 32 * 128
    assert parts["experts"]["flops"] == 4 * 3 * 2.0 * positions * 2304 * 896
    # three rings of 1,024 rows and 3,072 rows of the cache, keys and values: 384 MiB at 16
    assert parts["state"]["bytes"] == 16 * 2 * 4 * 128 * 4 * (3 * 1024 + 3072) == 384 * 2**20
    # ISSUE 47: 1.4e13 operations, 3.3e12 of them attention pairs; compute binds
    assert 1.3e13 < cost["flops"] < 1.5e13
    pairs = parts["full_scores"]["flops"] + parts["window_scores"]["flops"]
    assert 3.0e12 < pairs < 3.6e12
    assert peaks.least_seconds(cost["flops"], cost["bytes"], "TPU v5 lite")["binds"] == "compute"
    # a prompt under the window keeps what it has
    short = flops_mellum2.prefill_cost(2, 4, {**MODEL, "sliding_window": 6})
    assert short["parts"]["state"]["bytes"] == 2 * 2 * 4 * 128 * 4 * (3 * 4 + 4)


def test_the_shapes_hand_every_reader_its_cost(cpu_devices):
    from stoix_tpu.utils import config as config_lib

    cell = loader.load_cell(CELL)
    config = config_lib.compose(config_lib.default_config_dir(), cell.config["default_yaml"], cell.overrides)
    shapes = flops_mellum2.mellum2_ppo_shapes(config, envs_per_chip=16, updates_per_tick=1)
    assert shapes["model"] == MODEL
    assert (shapes["rollout_length"], shapes["prompt_length"], shapes["num_minibatches"]) == (G, P, 8)
    for key in ("update_cost", "experts_update_cost", "experts_decode_step_cost", "attention_forward_cost",
                "window_attend_update_cost", "window_attend_decode_step_cost", "full_attend_decode_step_cost",
                "prefill_cost"):
        assert shapes[key]["flops"] > 0 and shapes[key]["bytes"] > 0, key
    assert shapes["attention_forward_cost"]["flops"] == 16 * 4.0 * flops_swa.triangle_pairs(P + G) * 32 * 128
    assert shapes["window_attend_update_cost"] == shapes["update_cost"]["parts"]["window_scores"]
    # a decode step's reads are the decode's alone: the prefill has its own scope beside `rollout`
    full = flops_swa.attend_decode_step_cost(16, 3328.5, 32, MODEL)
    assert shapes["full_attend_decode_step_cost"] == full
    ring = flops_swa.attend_decode_step_cost(16, 1024, 32, MODEL)
    assert shapes["window_attend_decode_step_cost"]["bytes"] == pytest.approx(3 * ring["bytes"])
    assert shapes["window_attend_decode_step_cost"]["flops"] == pytest.approx(3 * ring["flops"])
    # a decode step's expert weights: float32, of the held experts 16 tokens reach, less what the
    # chip's vector memory can keep of the four layers' 793 MB from step to step
    reached, share = flops_swa.held_experts_reached(16.0, MODEL), flops_swa.from_hbm_share(MODEL, 4)
    assert 6.9 < reached < 7.1 and share == pytest.approx(1 - 128 * 2**20 / (4 * 4 * 3 * 2304 * 896 * 8))
    assert shapes["experts_decode_step_cost"]["bytes"] == pytest.approx(4 * 4 * (3 * 2304 * 896 * reached * share + 16 * (2 * 3200 + 3200)))
    # ISSUE 47's step: 0.79 GB of held experts, 0.42 GB of state at the mean position
    assert 0.74e9 < 4 * 4 * 3 * 2304 * 896 * 8 < 0.80e9
    state = shapes["full_attend_decode_step_cost"]["bytes"] + 3 * ring["bytes"]
    assert 0.41e9 < state < 0.43e9
    # no share's numerator can pass the time its kernel needs: each is memory- or compute-bound least work
    for key in ("full_attend_decode_step_cost", "window_attend_decode_step_cost", "experts_decode_step_cost"):
        assert peaks.least_seconds(shapes[key]["flops"], shapes[key]["bytes"], "TPU v5 lite")["binds"] == "memory"


# --------------------------------------------------------------------------- #
# The readers, on synthetic events
# --------------------------------------------------------------------------- #

D0 = "/device:TPU:0"


def op(name, start, dur, path, program="jit_learner_fn"):
    stats = {"tf_op": path, "program": program}
    return Event(D0, tr.OPS_LINE, f"%{name} = f32[8]{{0}} thing()", start, dur, stats)


def mellum2_trace():
    """Three executions of a 1000 ps learner, the middle one whole, each
    followed by a 300 ps evaluator. In the learner the prefill takes 100,
    beside the rollout and not under it (a window layer's banded kernel 20,
    the full layer's causal kernel 10, the held experts' loop 30 with a
    pathless grouped matmul inside), then the rollout's decode 400 (a window
    layer 150 with its ring's read 60,
    the full layer 100 with its cache's read 50, experts 60, head 50, env
    20); the update 500. The evaluator's prefill takes 60 of its 300."""
    roll = "jit(learner_fn)/while/body/rollout/while/body/rollout_policy"
    pre = "jit(learner_fn)/while/body/prefill/Lfm2LM"
    sgd = "jit(learner_fn)/while/body/ppo_epoch/ppo_minibatch"
    events = []
    for start in (0, 2000, 4000):
        events.append(Event(D0, tr.MODULES_LINE, "jit_learner_fn(7)", start, 1000, {}))
        events.append(Event(D0, tr.MODULES_LINE, "jit__shard_eval(9)", start + 1000, 300, {}))
        events += [
            op("fusion.30", start, 40, f"{pre}/layer_0/window_mixer/mixer/dot_general"),
            op("flash_attention.31", start + 40, 20, f"{pre}/layer_0/window_mixer/mixer/window_attend/jit(flash_attention)/pallas_call"),
            op("flash_attention.32", start + 60, 10, f"{pre}/layer_3/attention/mixer/attention_scores/jit(flash_attention)/pallas_call"),
            op("while.33", start + 70, 30, f"{pre}/layer_0/ffn/moe/moe_experts/while"),
            op("ragged-dot-none.34", start + 75, 20, "ragged-dot-none"),
            op("while.20", start + 100, 400, "jit(learner_fn)/while/body/rollout/while"),
            op("fusion.1", start + 100, 90, f"{roll}/Lfm2LM/layer_0/window_mixer/mixer/dot_general"),
            op("gqa_decode_attention.2", start + 190, 60, f"{roll}/Lfm2LM/layer_0/window_mixer/mixer/window_attend/pallas_call"),
            op("fusion.3", start + 250, 50, f"{roll}/Lfm2LM/layer_3/attention/mixer/dot_general"),
            op("gqa_decode_attention.4", start + 300, 50, f"{roll}/Lfm2LM/layer_3/attention/mixer/attention_scores/pallas_call"),
            op("while.6", start + 350, 60, f"{roll}/Lfm2LM/layer_0/ffn/moe/moe_experts/while"),
            op("ragged-dot-none.7", start + 360, 40, "ragged-dot-none"),
            op("fusion.9", start + 410, 50, f"{roll}/Lfm2LM/lm_head/dot_general"),
            op("fusion.10", start + 460, 20, "jit(learner_fn)/while/body/rollout/while/body/rollout_env/rem"),
            op("while.21", start + 500, 500, "jit(learner_fn)/while/body/ppo_epoch/while"),
            op("fusion.11", start + 500, 500, f"{sgd}/jvp(Lfm2LM)/layer_0/window_mixer/mixer/dot_general"),
            op("fusion.40", start + 1000, 60, "jit(_shard_eval)/prefill/Lfm2LM/layer_0/window_mixer/mixer/dot_general", "jit__shard_eval"),
            op("while.41", start + 1060, 240, "jit(_shard_eval)/while", "jit__shard_eval"),
        ]
    return tr.Trace.from_events(events)


def mellum2_ctx(shapes=None):
    cell = loader.load_cell(CELL)
    return types.SimpleNamespace(
        cell=cell, trace_data=mellum2_trace(), device={"kind": "TPU v5 lite"},
        shapes=shapes or {}, registry_span=lambda: None, registry_marks=[],
    )


def mellum2_reader(name):
    readers = loader.load_readers("per_layer", CELL)
    return dict((entry["name"], read) for entry, read in readers)[name]


def test_the_prefill_share_is_of_the_traced_window_learner_and_evaluator_together():
    ctx = mellum2_ctx()
    window = tr.busy_and_window(ctx.trace_data)["window_s"]
    # 100 ps in each learner execution and 60 in each evaluator's, three of each in the window
    assert mellum2_reader("prefill_share")(ctx) == pytest.approx(100.0 * 3 * 160e-12 / window)
    # the rollout's share is decode steps alone, as without a prompt: 400 of the learner's 1000
    assert mellum2_reader("decode_share")(ctx) == pytest.approx(40.0)
    assert mellum2_reader("window_mixer_share")(ctx) == pytest.approx(21.0 + 50.0)


def test_roofline_readers_divide_the_least_seconds_by_the_scoped_time():
    ps = 1e-12
    shapes = {
        "prefill_cost": {"flops": 197e12 * 25 * ps, "bytes": 0.0},
        "full_attend_decode_step_cost": {"flops": 0.0, "bytes": 819e9 * 5 * ps},
        "window_attend_decode_step_cost": {"flops": 0.0, "bytes": 819e9 * 10 * ps},
        "rollout_length": 4, "updates_per_tick": 1,
    }
    ctx = mellum2_ctx(shapes)
    # 25 ps of least work in the 100 ps under prefill (the pathless kernel inside its loop)
    assert mellum2_reader("prefill_roofline_share")(ctx) == pytest.approx(25.0)
    # 4 steps x 5 ps in the 50 ps under rollout/attention_scores: the prefill's 10 are not there
    assert mellum2_reader("full_attend_decode_roofline_share")(ctx) == pytest.approx(40.0)
    # nor is its banded kernel in the accepted reader's time: 4 x 10 in the ring's 60
    assert mellum2_reader("window_attend_decode_roofline_share")(ctx) == pytest.approx(100.0 * 40 / 60)


def test_new_readers_find_nothing_in_a_program_without_the_scope(monkeypatch):
    """The parent tree's scope table has no `prefill`: the readers that name
    it return None and the line leaves the metric out."""
    from benchmarks.harness import program_reads

    table = {"rollout": "rollout", "update_epoch": "ppo_epoch", "attention_scores": "attention_scores"}
    monkeypatch.setattr(program_reads, "program_scope", table.get)
    ctx = mellum2_ctx({"prefill_cost": {"flops": 1.0, "bytes": 1.0}, "rollout_length": 4})
    for name in ("prefill_share", "prefill_roofline_share"):
        assert mellum2_reader(name)(ctx) is None, name
    # ... and a cell whose driver hands over no such cost reads nothing either
    assert mellum2_reader("full_attend_decode_roofline_share")(mellum2_ctx({"rollout_length": 4})) is None


# --------------------------------------------------------------------------- #
# The prefill and the decode at four key/value heads at the published widths
# and the timed batch, compiled for a described v5e: what the compiler refuses
# here costs no chip time.
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


@pytest.mark.parametrize("kind", ["window", "full"])
@pytest.mark.parametrize("entry", ["prefill", "decode_scan"])
def test_a_layer_compiles_for_the_v5e_at_the_published_widths(one_chip, kind, entry, monkeypatch):
    """The prefill of 16 prompts of 3,072 positions through the flash kernel
    (banded | causal) into a ring of 1,024 rows | a cache of 3,584, and decode
    steps of 16 sequences against it as a scan's carry, at 32 query heads on
    FOUR key/value heads: XLA:TPU and Mosaic take both; the decode makes no
    copy of the state beside the row it writes in place."""
    import jax
    import jax.numpy as jnp
    from stoix_tpu.networks import lfm2
    from stoix_tpu.networks.olmoe import Yarn

    # (code that asks `jax.default_backend()` sees the CPU here: steer it)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    window = 1024 if kind == "window" else None
    yarn = None if window else Yarn(16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    mixer = lfm2.GroupedQueryAttention(2304, 32, 4, 128, 500000.0, 1e-6, window=window, yarn=yarn)
    state_of = lfm2.WindowKV if window else lfm2.KV
    struct = lambda *shape, dtype=jnp.float32: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    params = {"params": {
        "wq": struct(2304, 4096), "wk": struct(2304, 512), "wv": struct(2304, 512),
        "wo": struct(4096, 2304), "q_norm": struct(128), "k_norm": struct(128),
    }}
    rows = 1024 if window else 3584
    state = struct(rows, 16, 4, 128)
    if entry == "prefill":
        fn = lambda p, u, k, v: mixer.apply(p, u, state_of(k, v), method="prefill")
        args = (params, struct(16, 3072, 2304), state, state)
    else:
        def fn(p, u, k, v, length):
            def one(carry, _):
                k, v, length, u = carry
                out, new = mixer.apply(p, u, state_of(k, v), length, method="step")
                return (new.k, new.v, length + 1, out), None

            return jax.lax.scan(one, (k, v, length, u), None, 4)[0]

        args = (params, struct(16, 2304), state, state, struct(dtype=jnp.int32))
    compiled = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert ("window_attend" if window else "attention_scores") in text and "tpu_custom_call" in text
    if entry == "prefill":
        assert "flash_attention" in text and "flash_attention_bwd" not in text
        assert ("qk_norm_rope" in text) == bool(window)  # (YaRN's rotation is the plain path's)
        # (q, the result and the keys and values repeated for their groups, 0.8 GB each at 49,152 positions)
        assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2**30
    else:
        assert "gqa_decode_attention" in text
        shape = f"f32[{rows},16,4,128]"
        copies = [line for line in text.splitlines() if " copy(" in line and shape in line]
        assert len(copies) <= 2 and not any("while" in line for line in copies), copies
