"""The hybrid token-policy cell (`anakin_ppo_lfm2_tokens_1chip`) on the CPU at
a tiny preset: the whole path a real run takes — driver, reference
comparisons, stated-configuration checks, result line — with the cell's
configuration restated at the tiny widths; the cost functions behind its
roofline readers; each of its new readers on synthetic events; and Mosaic's
and XLA:TPU's verdict on the mixer at the published widths, for a described
v5e, at no chip time."""

import time
import types

import pytest

import _paths  # noqa: F401
from benchmarks.harness import cell_runner, flops_lfm2, loader, peaks
from benchmarks.harness import trace_reduce as tr
from benchmarks.harness.trace_reduce import Event

CELL = "anakin_ppo_lfm2_tokens_1chip"
TINY_STATED = {
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    # (8 x 16 over 4: no projection then has the router's shape, [64, 32])
    "num_attention_heads": 8, "num_key_value_heads": 4, "head_dim": 16, "vocab_size": 64,
    "num_minibatches": 4, "rollout_length": 16,
}
TINY_OVERRIDES = [
    "env=token_task", "network=lfm2_moe", "arch.evaluation_greedy=True", "system.epochs=1",
    "system.router_aux_loss_coef=0.0",
    "network.actor_network.hidden_size=64", "network.actor_network.dense_width=96",
    "network.actor_network.num_heads=8", "network.actor_network.num_kv_heads=4",
    "network.actor_network.head_dim=16", "network.actor_network.expert_width=32",
    "env.kwargs.vocab_size=64", "env.kwargs.length=16", "system.rollout_length=16",
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
            tiny_cell(second_reading=True), 3_000_000_019, 3.0, False, time.perf_counter(),
            require_platform="cpu",
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
    expert sets over all 32 experts identical, nothing dropped — teacher
    forced and decoded through the hybrid carry at every slot."""
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
    and what its Adam steps changed against the plain replay (verifier,
    float64 GAE, the state key's shuffle, `jax.grad` of the reference loss,
    clip, Adam) — float32 on both sides; `expert_bias` unchanged to the bit."""
    assert tiny_run["detail"]["errors"][name] <= limit
    assert tiny_run["detail"]["tolerances"][name] >= 0.0


def test_the_run_prints_the_lower_precision_reading_and_the_counters(tiny_run):
    health = tiny_run["detail"]["health"]
    second = health["reference"]["lower_precision"]
    # bfloat16 is a different result: three decimal digits, not seven.
    assert second["logits_rms"] > 1e-3 and second["record_log_prob_rms"] > 1e-4
    assert second["update_params_worst_leaf"] > 1e-3
    assert set(health["reference"]["update_leaves"]) == set(second["update_leaves"])
    assert not any("expert_bias" in leaf for leaf in second["update_leaves"])
    counters = health["reference"]["counters"]
    assert 0.0 < counters["held_pairs_per_token"] < 4.0
    assert 0.0 < counters["router_bias_changed_share"] < 1.0
    assert counters["dropped_pairs"] == 0.0
    assert {"learner_setup", "aot_warmup", "first_tick"} <= set(health["setup_phases"])


def test_the_drivers_shapes_carry_the_held_pairs_the_run_logged(cpu_devices):
    ctx_shapes = {}
    real = flops_lfm2.lfm2_ppo_shapes

    def spy(config, **kwargs):
        ctx_shapes.update(kwargs["held_pairs"])
        return real(config, **kwargs)

    import unittest.mock as mock

    with mock.patch.object(flops_lfm2, "lfm2_ppo_shapes", spy):
        cell_runner.run_cell(tiny_cell(), 11, 1.0, False, time.perf_counter(), require_platform="cpu")
    assert 0.0 < ctx_shapes["update"] < 4.0 and 0.0 < ctx_shapes["rollout"] < 4.0


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
    result = cell_runner.run_cell(
        tiny_cell(), 5, 1.0, False, time.perf_counter(), require_platform="cpu"
    )
    assert not result["correct"]
    assert any("update_adam_steps" in p for p in result["problems"]), result["problems"]
    assert any("update_params_worst_leaf" in p for p in result["problems"]), result["problems"]


def test_a_router_that_weights_by_score_plus_bias_is_not_correct(cpu_devices, monkeypatch):
    """The published rule keeps the bias out of the weights. A router whose
    weights are the biased scores chooses the same experts and drops nothing,
    and at this bias (normal(0.01)) its logits stay inside the chip's
    tolerances: what gives it away is that `expert_bias` now takes a gradient
    — the window's Adam steps move it, in every routed layer."""
    import jax.numpy as jnp
    from stoix_tpu.networks import olmoe

    real = olmoe.route

    def biased_weights(x, router, top_k, renormalise=False, **routing):
        probs, weights, index = real(x, router, top_k, renormalise, **routing)
        if routing.get("bias") is not None:
            weights = jnp.take_along_axis(probs + routing["bias"], index, axis=-1)
            weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + routing["epsilon"])
        return probs, weights, index

    monkeypatch.setattr(olmoe, "route", biased_weights)
    result = cell_runner.run_cell(
        tiny_cell(), 7, 1.0, False, time.perf_counter(), require_platform="cpu"
    )
    assert not result["correct"]
    assert any("update_expert_bias_changed" in p for p in result["problems"]), result["problems"]
    assert result["detail"]["errors"]["update_expert_bias_changed"] == 4.0
    # ... while the chosen sets are the reference's own, and nothing is dropped
    assert result["detail"]["errors"]["tf_expert_set_disagreement"] == 0.0
    assert result["detail"]["errors"]["update_dropped_pairs"] == 0.0


@pytest.mark.parametrize("stated,problem", [
    ({"moe_intermediate_size": 64}, "parameter shapes differ from the stated layers and widths"),
    ({"router_experts": 64}, "parameter shapes differ from the stated layers and widths"),
    ({"num_experts": 4}, "parameter shapes differ from the stated layers and widths"),
    ({"layer_types": ["conv", "conv", "conv", "full_attention", "conv", "conv"]},
     "parameter shapes differ from the stated layers and widths"),
    ({"num_dense_layers": 1}, "parameter shapes differ from the stated layers and widths"),
    ({"conv_L_cache": 4}, "parameter shapes differ from the stated layers and widths"),
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


def test_an_untied_head_is_a_leaf_the_file_does_not_state():
    reference = loader.load_reference("ppo_lfm2")
    want = reference.expected_shapes(loader.load_cell(CELL).config)
    assert "lm_head" not in want and want["embed"] == (16384, 2048)
    assert want["layer_2/mixer/wk"] == (2048, 512) and want["layer_0/mixer/in_proj"] == (2048, 6144)
    assert want["layer_1/ffn/w1"] == (2048, 7168) and want["layer_2/ffn/gate"] == (8, 2048, 1792)
    assert want["layer_5/ffn/router"] == (2048, 32) and want["layer_5/ffn/expert_bias"] == (32,)
    assert sum(1 for name in want if name.endswith("mixer/conv")) == 5
    parameters = sum(int(__import__("numpy").prod(shape)) for shape in want.values())
    assert parameters == 568_647_936  # 568.6 M, and the value head's 2,049 beside them


MODEL = {
    "hidden_size": 2048, "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv"],
    "num_dense_layers": 2, "dense_width": 7168, "conv_kernel": 3, "num_heads": 32,
    "num_kv_heads": 8, "head_dim": 64, "num_experts": 32, "experts_held": 8,
    "experts_per_token": 4, "expert_width": 1792, "vocab_size": 16384,
}


def test_update_cost_counts_the_share_this_chip_holds():
    cost = flops_lfm2.update_cost(128, 512, 1, 8, MODEL)
    tokens = 128 * 512
    assert cost["samples"] == tokens
    parts = cost["parts"]
    # five conv mixers: [2048, 6144] in, [2048, 2048] out, and 8 operations a channel between
    assert parts["conv_mixers"]["flops"] == 5 * 3 * tokens * (2.0 * 2048 * 8192 + 8.0 * 2048)
    # one pair a token lands on the 8 held experts of 32 under uniform routing, not 4
    assert parts["experts"]["flops"] == 4 * 3 * 3 * 2.0 * tokens * 2048 * 1792
    # two dense SwiGLUs of width 7168
    assert parts["dense_mlps"]["flops"] == 2 * 3 * 3 * 2.0 * tokens * 2048 * 7168
    # one attention layer, grouped-query: wq and wo 2048 wide, wk and wv 512
    assert parts["qkvo"]["flops"] == 3 * 2.0 * tokens * 2048 * (2 * 2048 + 2 * 512)
    # the tied head over the slice
    assert parts["head"]["flops"] == 3 * 2.0 * tokens * 2048 * 16384
    assert parts["router"]["flops"] == 4 * 3 * 2.0 * tokens * 2048 * 32
    assert cost["flops"] == sum(p["flops"] for p in parts.values())
    # the run's own count of held pairs a token takes the place of the uniform 1.0
    skewed = flops_lfm2.update_cost(128, 512, 1, 8, MODEL, held_pairs_per_token=1.5)
    assert skewed["parts"]["experts"]["flops"] == 1.5 * parts["experts"]["flops"]
    # ISSUE 33's arithmetic: 102 TFLOP, 0.52 s at the v5e's bf16 peak; conv
    # mixers a third of it, the dense feed-forwards a third, the held experts a sixth.
    least = peaks.least_seconds(cost["flops"], cost["bytes"], "TPU v5 lite")
    assert least["binds"] == "compute" and 0.50 < least["seconds"] < 0.54
    share = lambda part: parts[part]["flops"] / cost["flops"]
    assert 0.31 < share("conv_mixers") < 0.34 and 0.33 < share("dense_mlps") < 0.35
    assert 0.16 < share("experts") < 0.18 and 0.12 < share("head") < 0.14


def test_the_conv_mixers_update_is_compute_bound_and_their_decode_step_memory_bound():
    update = flops_lfm2.update_cost(128, 512, 1, 8, MODEL)["parts"]["conv_mixers"]
    assert peaks.least_seconds(update["flops"], update["bytes"], "TPU v5 lite")["binds"] == "compute"
    step = flops_lfm2.conv_mixer_decode_step_cost(128, MODEL)
    # the projections' weights once, as the bfloat16 operands of one MXU pass ...
    assert step["bytes"] >= 2 * 4 * 2048 * 2048
    # ... and in float32 the taps, the tails (read and written), a row in, a row out
    assert step["bytes"] - 2 * 4 * 2048 * 2048 == 4 * (3 * 2048 + 128 * 2048 * (2 * 2 + 2))
    assert peaks.least_seconds(step["flops"], step["bytes"], "TPU v5 lite")["binds"] == "memory"


def test_a_decode_step_reads_the_held_experts_and_is_memory_bound():
    step = flops_lfm2.expert_cost(flops_lfm2.held_rows(128.0, MODEL, None), MODEL, False, 8)
    assert step["bytes"] >= 8 * 3 * 2048 * 1792 * 4  # the held experts' weights, once
    assert peaks.least_seconds(step["flops"], step["bytes"], "TPU v5 lite")["binds"] == "memory"


D0 = "/device:TPU:0"


def op(name, start, dur, path):
    stats = {"tf_op": path, "program": "jit_learner_fn"}
    return Event(D0, tr.OPS_LINE, f"%{name} = f32[8]{{0}} thing()", start, dur, stats)


def lfm2_trace():
    """Three executions of a 1000 ps learner, the middle one whole. In it the
    rollout takes 400: conv mixers 120 (of which the conv itself 20), the
    attention layer 40, dense feed-forwards 100, the held experts' loop 60
    with a pathless grouped matmul inside, head 50, env 30; the update 600:
    conv mixers 200 forward and backward (conv itself 30), attention 50,
    dense feed-forwards 180, experts 100, head 70."""
    roll = "jit(learner_fn)/while/body/rollout/while/body/rollout_policy"
    sgd = "jit(learner_fn)/while/body/ppo_epoch/ppo_minibatch"
    fwd, bwd = f"{sgd}/jvp(Lfm2LM)", f"{sgd}/transpose(jvp(Lfm2LM))"
    events = []
    for start in (0, 2000, 4000):
        events.append(Event(D0, tr.MODULES_LINE, "jit_learner_fn(7)", start, 1000, {}))
        events += [
            op("while.20", start, 400, "jit(learner_fn)/while/body/rollout/while"),
            op("while.21", start + 400, 600, "jit(learner_fn)/while/body/ppo_epoch/while"),
            op("fusion.1", start, 100, f"{roll}/Lfm2LM/layer_0/conv_mixer/mixer/dot_general"),
            op("fusion.2", start + 100, 20, f"{roll}/Lfm2LM/layer_0/conv_mixer/mixer/conv_mixer_conv/mul"),
            op("fusion.3", start + 120, 40, f"{roll}/Lfm2LM/layer_2/attention/mixer/reduce_sum"),
            op("fusion.4", start + 160, 100, f"{roll}/Lfm2LM/layer_0/ffn/dense_mlp/dot_general"),
            op("while.5", start + 260, 60, f"{roll}/Lfm2LM/layer_2/ffn/moe/while"),
            op("ragged-dot-none.6", start + 270, 40, "ragged-dot-none"),
            op("fusion.7", start + 320, 50, f"{roll}/Lfm2LM/lm_head/dot_general"),
            op("fusion.8", start + 370, 30, "jit(learner_fn)/while/body/rollout/while/body/rollout_env/rem"),
            op("fusion.9", start + 400, 70, f"{fwd}/layer_0/conv_mixer/mixer/dot_general"),
            op("fusion.10", start + 470, 30, f"{bwd}/layer_0/conv_mixer/mixer/conv_mixer_conv/mul"),
            op("fusion.11", start + 500, 100, f"{bwd}/layer_0/conv_mixer/mixer/dot_general"),
            op("fusion.12", start + 600, 50, f"{bwd}/layer_2/attention/mixer/dot_general"),
            op("fusion.13", start + 650, 180, f"{bwd}/layer_0/ffn/dense_mlp/dot_general"),
            op("while.14", start + 830, 100, f"{bwd}/layer_2/ffn/moe/while"),
            op("ragged-dot-none.15", start + 840, 80, "ragged-dot-none"),
            op("fusion.16", start + 930, 70, f"{sgd}/transpose(jvp(lm_head))/dot_general"),
        ]
    return tr.Trace.from_events(events)


def lfm2_ctx(shapes=None):
    cell = loader.load_cell(CELL)
    return types.SimpleNamespace(
        cell=cell, trace_data=lfm2_trace(), device={"kind": "TPU v5 lite"},
        shapes=shapes or {}, registry_span=lambda: None, registry_marks=[],
    )


def lfm2_reader(name):
    readers = loader.load_readers("per_layer", CELL)
    return dict((entry["name"], read) for entry, read in readers)[name]


@pytest.mark.parametrize("name,share", [
    ("conv_mixer_share", 32.0), ("dense_mlp_share", 28.0), ("decode_share", 40.0),
    ("moe_share", 16.0), ("attention_share", 9.0), ("lm_head_share", 12.0), ("update_share", 60.0),
])
def test_share_readers_split_the_whole_execution(name, share):
    assert lfm2_reader(name)(lfm2_ctx()) == pytest.approx(share)


def test_roofline_readers_divide_the_least_seconds_by_the_scoped_time():
    ps = 1e-12
    shapes = {
        "conv_mixer_update_cost": {"flops": 197e12 * 50 * ps, "bytes": 819e9 * 4 * ps},
        "conv_mixer_decode_step_cost": {"flops": 0.0, "bytes": 819e9 * 15 * ps},
        "experts_update_cost": {"flops": 197e12 * 40 * ps, "bytes": 0.0},
        "experts_decode_step_cost": {"flops": 0.0, "bytes": 819e9 * 5 * ps},
        "rollout_length": 4, "updates_per_tick": 1,
    }
    ctx = lfm2_ctx(shapes)
    # 50 ps of least work in the 200 ps under ppo_epoch/conv_mixer, forward and backward
    assert lfm2_reader("conv_mixer_update_roofline_share")(ctx) == pytest.approx(25.0)
    # 4 steps x 15 ps in the 120 ps under rollout/conv_mixer
    assert lfm2_reader("conv_mixer_decode_roofline_share")(ctx) == pytest.approx(50.0)
    # 40 ps in the 80 ps of grouped matmuls under ppo_epoch; 4 x 5 ps in the 40 ps under rollout
    assert lfm2_reader("moe_experts_update_roofline_share")(ctx) == pytest.approx(50.0)
    assert lfm2_reader("moe_experts_decode_roofline_share")(ctx) == pytest.approx(50.0)


def test_the_carry_reader_adds_up_the_gauges_kinds():
    ctx = lfm2_ctx()
    gauge = lambda kind, value: (("stoix_tpu_lm_carry_bytes", (("kind", kind),), "value"), value)
    ctx.registry_marks = [(0, 0.0, dict([gauge("conv_tail", 10 * 2**20), gauge("kv", 256 * 2**20),
                                         (("other", (), "value"), 7.0)]))]
    assert lfm2_reader("decode_carry_mib")(ctx) == pytest.approx(266.0)


def test_new_readers_find_nothing_in_a_program_without_the_scopes(monkeypatch):
    """The parent tree's scope table has none of this PR's scopes and its
    registry no carry gauge: every new reader returns None and the line
    leaves the metric out."""
    from benchmarks.harness import program_reads

    table = {"rollout": "rollout", "update_epoch": "ppo_epoch", "attention": "attention"}
    monkeypatch.setattr(program_reads, "program_scope", table.get)
    ctx = lfm2_ctx({
        "conv_mixer_update_cost": {"flops": 1.0, "bytes": 1.0},
        "conv_mixer_decode_step_cost": {"flops": 1.0, "bytes": 1.0}, "rollout_length": 4,
    })
    ctx.registry_marks = [(0, 0.0, {("stoix_tpu_setup_phase_seconds", (("phase", "x"),), "value"): 1.0})]
    for name in ("conv_mixer_share", "dense_mlp_share", "conv_mixer_update_roofline_share",
                 "conv_mixer_decode_roofline_share", "decode_carry_mib"):
        assert lfm2_reader(name)(ctx) is None, name


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


@pytest.mark.parametrize("entry", ["forward", "gradient", "step"])
def test_the_conv_mixer_compiles_for_the_v5e_at_the_published_widths(one_chip, entry):
    """A minibatch of 16 sequences of 512 tokens teacher-forced (and its
    gradient), and one decode step of 128 sequences against their tails."""
    import jax
    import jax.numpy as jnp
    from stoix_tpu.networks import lfm2

    mixer = lfm2.ShortConv(hidden_size=2048, kernel=3)
    struct = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    params = {"params": {"in_proj": struct(2048, 6144), "conv": struct(3, 2048),
                         "out_proj": struct(2048, 2048)}}
    forward = lambda p, u: mixer.apply(p, u, method="forward")
    if entry == "forward":
        fn, args = forward, (params, struct(16, 512, 2048))
    elif entry == "gradient":
        fn = jax.grad(lambda p, u: forward(p, u).sum(), argnums=(0, 1))
        args = (params, struct(16, 512, 2048))
    else:
        fn = lambda p, u, z: mixer.apply(p, u, lfm2.ConvTail(z), None, method="step")
        args = (params, struct(128, 2048), struct(128, 2, 2048))
    compiled = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert "conv_mixer_conv" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30
