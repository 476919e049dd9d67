"""The plain reference (benchmarks/references/ppo_mlp.py) against the
program's own networks and GAE, at tiny size on the CPU, and against
deliberately wrong variants: a tolerance that a wrong activation or a dropped
bias passes, or a `correct` that a bfloat16 cast or a drifted width passes,
would check nothing."""

import jax
import numpy as np
import pytest

import _paths  # noqa: F401
from benchmarks.harness import loader
from benchmarks.harness import reference as compare

reference = loader.load_reference("ppo_mlp")
CONFIGS = ["ppo_ant_mlp256", "sebulba_ppo_cartpole_mlp"]


def _program_networks(config_name):
    """The program's actor and critic for a benchmark configuration, built
    by its own builder, with seeded non-trivial weights."""
    from stoix_tpu import envs
    from stoix_tpu.envs.types import Observation
    from stoix_tpu.systems.ppo.anakin import ff_ppo
    from stoix_tpu.utils import config as config_lib

    spec = loader._read_json(f"{loader.ROOT}/benchmarks/configs/{config_name}.json")
    default_yaml = "default/anakin/default_ff_ppo_continuous.yaml"
    overrides = [o for o in spec["overrides"] if not o.startswith("env.backend")]
    if spec["reference"]["action_head"] == "categorical":
        default_yaml = "default/anakin/default_ff_ppo.yaml"
    config = config_lib.compose(
        config_lib.default_config_dir(), default_yaml,
        overrides + ["arch.total_num_envs=8"],
    )
    env, _ = envs.make(config)
    config.system.action_dim = env.num_actions
    actor, critic = ff_ppo.build_networks(env, config)
    obs_dim = int(np.prod(env.observation_value().agent_view.shape))

    def make_observation(obs):
        return Observation(
            agent_view=obs,
            action_mask=np.ones((obs.shape[0], env.num_actions), np.float32),
            step_count=np.zeros((obs.shape[0],), np.int32),
        )

    dummy = make_observation(np.zeros((1, obs_dim), np.float32))
    rng = np.random.default_rng(0)
    randomize = lambda tree: jax.tree.map(
        lambda x: (np.asarray(x) + 0.1 * rng.normal(size=x.shape)).astype(np.float32), tree
    )
    actor_vars = randomize(actor.init(jax.random.PRNGKey(1), dummy))
    critic_vars = randomize(critic.init(jax.random.PRNGKey(2), dummy))
    return spec, actor, critic, actor_vars, critic_vars, make_observation, obs_dim


@pytest.mark.parametrize("config_name", CONFIGS)
def test_reference_mlp_agrees_with_the_programs_networks(config_name):
    spec, actor, critic, a_vars, c_vars, make_obs, obs_dim = _program_networks(config_name)
    errors, dtypes = reference.check_networks(
        actor.apply, critic.apply, a_vars, c_vars, make_obs, obs_dim, spec["reference"], seed=3
    )
    assert set(errors) >= {"critic_value"} and len(errors) >= 2
    assert max(errors.values()) < 1e-5, errors  # float32 on the CPU: no MXU rounding
    assert dtypes == {"outputs": ["float32"], "matmul_operands": ["float32"]}
    # The run agrees with everything the configuration file states.
    shapes = {k: spec[k] for k in ("rollout_length", "epochs", "num_minibatches")}
    assert reference.stated_mismatches(spec, a_vars, c_vars, dtypes, shapes) == []


@pytest.mark.parametrize("config_name", CONFIGS)
def test_every_stated_key_is_read_and_the_tolerances_are_the_files(config_name):
    spec = loader._read_json(f"{loader.ROOT}/benchmarks/configs/{config_name}.json")
    assert spec["reference"]["module"] == "ppo_mlp"
    # 4.4e-2 was read on the chip on a right run; a wrong network starts at 1e-1.
    assert 4.5e-2 <= spec["reference"]["mlp_tol"] <= 5e-2
    assert spec["reference"]["gae_tol"] == 1e-4
    source = open(f"{loader.ROOT}/benchmarks/references/ppo_mlp.py", encoding="utf-8").read()
    for key in ("observation_dim", "action_dim", "actor_hidden_sizes", "critic_hidden_sizes",
                "parameter_dtype", "compute_dtype", "rollout_length", "epochs",
                "num_minibatches", "multistep_impl", "mlp_tol", "gae_tol"):
        assert key in spec or key in spec["reference"], key
        assert f'"{key}"' in source, f"{key} is stated and nothing reads it"


def _cast(tree, dtype):
    return jax.tree.map(lambda x: np.asarray(x).astype(dtype), tree)


@pytest.mark.parametrize("what,expect", [
    ("bf16_parameters", "parameters are ['bfloat16'], stated float32"),
    ("bf16_activations", "matmul_operands are ['bfloat16', 'float32'], stated float32"),
    ("narrow_torso", "kernels [(27, 128), (128, 256)"),
    ("other_observation", "are not the stated [(27, 256)"),
    ("rollout_drift", "rollout_length resolved to 32, stated 16"),
])
def test_correct_holds_the_run_to_what_the_file_states(what, expect):
    """A later PR that casts to bfloat16, or whose overrides drift from the
    stated widths, must not report `correct: true`."""
    import ml_dtypes

    spec, actor, critic, a_vars, c_vars, make_obs, obs_dim = _program_networks("ppo_ant_mlp256")
    dtypes = {"outputs": ["float32"], "matmul_operands": ["float32"]}
    shapes = {"rollout_length": 16, "epochs": 4, "num_minibatches": 4}
    if what == "bf16_parameters":
        a_vars = _cast(a_vars, ml_dtypes.bfloat16)
    elif what == "bf16_activations":
        # A network that casts inside: float32 in, float32 out, bfloat16 products.
        def apply(variables, obs):
            import jax.numpy as jnp

            low = lambda x: jnp.asarray(x, jnp.bfloat16)
            h = low(obs.agent_view)
            for kernel, bias in reference.dense_stack(variables["params"]["torso"]):
                h = jax.nn.silu(h @ low(kernel) + low(bias))
            ((kernel, bias),) = reference.dense_stack(variables["params"]["critic_head"])
            return (h @ low(kernel) + low(bias))[..., 0].astype(jnp.float32)

        _, dtypes = reference.check_networks(
            actor.apply, apply, a_vars, c_vars, make_obs, obs_dim, spec["reference"], seed=3
        )
        assert dtypes["outputs"] == ["float32"]
    elif what == "narrow_torso":
        t = a_vars["params"]["torso"]
        t["Dense_0"]["kernel"] = t["Dense_0"]["kernel"][:, :128]
        t["Dense_1"]["kernel"] = t["Dense_1"]["kernel"][:128]
    elif what == "other_observation":
        spec = {**spec, "observation_dim": 28}
        expect = "are not the stated [(28, 256)"
    elif what == "rollout_drift":
        shapes["rollout_length"] = 32
    problems = reference.stated_mismatches(spec, a_vars, c_vars, dtypes, shapes)
    assert any(expect in p for p in problems), problems


def test_matmul_operand_dtypes_looks_inside_nested_calls():
    import jax.numpy as jnp

    @jax.jit
    def inner(x, w):
        return jax.lax.cond(x.sum() > 0, lambda: x.astype(jnp.bfloat16) @ w.astype(jnp.bfloat16),
                            lambda: (x @ w).astype(jnp.bfloat16))

    x = np.ones((4, 3), np.float32)
    assert compare.matmul_operand_dtypes(lambda a, b: inner(a, b) + 1, x, x.T) == ["bfloat16", "float32"]
    assert compare.matmul_operand_dtypes(lambda a: a + 1, x) == []


@pytest.mark.parametrize(
    "wrong", [{"activation": "relu"}, {"activation": "tanh"}, {"min_scale": 0.5}]
)
def test_reference_tolerance_catches_a_wrong_network(wrong):
    spec, actor, critic, a_vars, c_vars, make_obs, obs_dim = _program_networks("ppo_ant_mlp256")
    errors, _ = reference.check_networks(
        actor.apply, critic.apply, a_vars, c_vars, make_obs, obs_dim,
        {**spec["reference"], **wrong}, seed=3,
    )
    assert max(errors.values()) > spec["reference"]["mlp_tol"], errors


def test_reference_tolerance_catches_a_dropped_bias():
    spec, actor, critic, a_vars, c_vars, make_obs, obs_dim = _program_networks("ppo_ant_mlp256")
    broken = jax.tree.map(lambda x: x, a_vars)
    broken["params"]["torso"]["Dense_1"]["bias"] = np.zeros_like(
        broken["params"]["torso"]["Dense_1"]["bias"]
    )
    obs = compare.seeded_observations(3, obs_dim, batch=64)
    want = reference.mlp_reference(a_vars, obs, spec["reference"], "action_head")
    got = reference.mlp_reference(broken, obs, spec["reference"], "action_head")
    assert compare.max_scaled_error(got["loc"], want["loc"]) > 0  # the bias matters
    out = jax.jit(lambda v, o: reference.program_outputs(actor.apply(v, o), "tanh_normal"))(
        broken, make_obs(obs)
    )
    assert compare.max_scaled_error(out["loc"], got["loc"]) < 1e-5


GAE_TOL = loader._read_json(f"{loader.ROOT}/benchmarks/configs/ppo_ant_mlp256.json")["reference"]["gae_tol"]


@pytest.mark.parametrize("impl", ["scan", "assoc"])
def test_gae_loop_agrees_with_the_programs_multistep(impl):
    from stoix_tpu.ops import multistep

    errors = reference.check_gae(
        multistep.truncated_generalized_advantage_estimation, seed=5, impl=impl, shape=(16, 64)
    )
    assert max(errors.values()) < GAE_TOL, errors


def test_gae_tolerance_catches_a_wrong_lambda():
    from stoix_tpu.ops import multistep

    def wrong(r, d, lam, **kw):
        return multistep.truncated_generalized_advantage_estimation(r, d, lam * 0.99, **kw)

    errors = reference.check_gae(wrong, seed=5, impl="scan", shape=(16, 64))
    assert max(errors.values()) > GAE_TOL


def test_gae_reference_by_hand():
    # Two steps, one env: A_1 = delta_1; A_0 = delta_0 + g*l*A_1.
    adv, tgt = reference.gae_reference(
        np.array([[1.0], [2.0]]), np.array([[0.9], [0.9]]), 0.5,
        v_tm1=np.array([[0.5], [0.25]]), v_t=np.array([[0.25], [1.0]]),
        truncation_t=np.zeros((2, 1)),
    )
    d1 = 2.0 + 0.9 * 1.0 - 0.25
    d0 = 1.0 + 0.9 * 0.25 - 0.5
    assert adv[1, 0] == pytest.approx(d1) and adv[0, 0] == pytest.approx(d0 + 0.45 * d1)
    assert tgt[0, 0] == pytest.approx(0.5 + adv[0, 0])


def test_max_scaled_error_flags_shape_and_nan():
    assert compare.max_scaled_error(np.zeros(3), np.zeros(4)) == float("inf")
    assert compare.max_scaled_error(np.array([np.nan]), np.zeros(1)) == float("inf")
    assert compare.max_scaled_error(np.array([10.5]), np.array([10.0])) == pytest.approx(0.05)
