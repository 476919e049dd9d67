"""The OLMoE token policy (networks/olmoe.py, systems/ppo/anakin/ff_lm_ppo.py,
envs/token_task.py) against its plain reference (reference/olmoe.py), at a
tiny preset on the CPU: hidden 64, 4 heads x 16, 8 experts top-2 of width 32,
vocabulary 97, L = 16. Tolerance 1e-5 throughout: both sides are float32 on
the CPU (no bfloat16 pass), and differ only in summation order."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stoix_tpu import envs
from stoix_tpu.base_types import ActorCriticParams
from stoix_tpu.networks import olmoe
from stoix_tpu.observability import BLOCK_SCOPES, SCOPES
from stoix_tpu.reference import olmoe as reference
from stoix_tpu.systems.ppo.anakin import ff_lm_ppo
from stoix_tpu.utils import config as config_lib

TOL = 1e-5
VOCAB, LENGTH = 97, 16
TINY = [
    "network.actor_network.hidden_size=64", "network.actor_network.num_heads=4",
    "network.actor_network.head_dim=16", "network.actor_network.num_experts=8",
    "network.actor_network.experts_per_token=2", "network.actor_network.expert_width=32",
    f"env.kwargs.vocab_size={VOCAB}", f"env.kwargs.length={LENGTH}",
    f"system.rollout_length={LENGTH}", "arch.total_num_envs=32", "system.num_minibatches=4",
    "arch.num_eval_episodes=8", "arch.total_timesteps=~", "arch.num_updates=2",
    "arch.num_evaluation=1", "arch.absolute_metric=False", "logger.use_console=False",
    "logger.checkpointing.save_model=False",
]
HYPER = {"clip_eps": 0.2, "ent_coef": 0.01, "vf_coef": 0.5, "aux_coef": 0.01}


def _spec(layers):
    return {
        "hidden_size": 64, "num_attention_heads": 4, "num_experts": 8, "num_experts_per_tok": 2,
        "num_hidden_layers": layers, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
    }


def _model(layers):
    actor = olmoe.OlmoeLM(
        vocab_size=VOCAB, hidden_size=64, num_heads=4, head_dim=16, num_experts=8,
        experts_per_token=2, expert_width=32, num_layers=layers,
    )
    critic = olmoe.ValueHead()
    key = jax.random.PRNGKey(layers)
    actor_params = actor.init(key, jnp.zeros((1, 2), jnp.int32), method="forward")
    # normal(0.02) leaves every router near uniform; scale the weights up so
    # that routing, attention and the norms all matter to the outputs.
    actor_params = jax.tree.map(lambda w: w * 8.0 if w.ndim > 1 else w, actor_params)
    critic_params = critic.init(key, jnp.zeros((1, 2, 64)))
    critic_params = jax.tree.map(lambda w: w + 0.1, critic_params)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (4, LENGTH), 0, VOCAB)
    return ff_lm_ppo.network_functions(actor, critic, LENGTH), actor_params, critic_params, tokens


@pytest.fixture(scope="module", params=[1, 2], ids=["1layer", "2layers"])
def model(request):
    return (request.param,) + _model(request.param)


def _close(got, want):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=TOL, atol=TOL)


@pytest.mark.parametrize("output", ["logits", "values", "expert_index"])
def test_forward_matches_the_plain_reference(model, output):
    layers, nets, actor_params, critic_params, tokens = model
    want = reference.forward(actor_params, critic_params, tokens, _spec(layers))
    logits, hidden, stats = jax.jit(nets.forward)(actor_params, tokens)
    if output == "logits":
        _close(logits, want["logits"])
    elif output == "values":
        _close(nets.value(critic_params, hidden), want["values"])
    else:  # the chosen expert SETS are identical, layer by layer
        got = np.sort(np.asarray(stats["expert_index"]), axis=-1)
        assert (got == np.sort(np.asarray(want["expert_index"]), axis=-1)).all()
        assert int(stats["expert_count"].sum()) == layers * tokens.size * 2


@pytest.mark.parametrize("output", ["logits", "hidden"])
def test_cached_steps_reproduce_the_teacher_forced_forward(model, output):
    """Acting step by step through the cache gives the learner's logits: the
    RL form of 'prefill then decode equals the full forward'."""
    layers, nets, actor_params, _, tokens = model
    want_logits, want_hidden, _ = nets.forward(actor_params, tokens)
    step = jax.jit(nets.step)
    cache = nets.init_cache(tokens.shape[0])
    got = []
    for t in range(LENGTH):
        logits, hidden, cache, _ = step(actor_params, cache, tokens[:, t])
        got.append(logits if output == "logits" else hidden)
    _close(jnp.stack(got, axis=1), want_logits if output == "logits" else want_hidden)
    assert (np.asarray(cache.length) == LENGTH).all()


def test_cache_reset_on_done_starts_a_new_sequence(model):
    """After `reset_cache` a sequence's stale entries are never read: its
    next steps equal a fresh cache's, and its neighbours are untouched."""
    layers, nets, actor_params, _, tokens = model
    step = jax.jit(nets.step)
    # A position a sequence (`length [B]`): these two end apart.
    cache = olmoe.init_cache(layers, 2, LENGTH, 4, 16)
    for t in range(5):
        _, _, cache, _ = step(actor_params, cache, tokens[:2, t])
    cache = olmoe.reset_cache(cache, jnp.array([True, False]))
    assert cache.length.tolist() == [0, 5]
    fresh = olmoe.init_cache(layers, 1, LENGTH, 4, 16)
    for t in range(3):
        logits, _, cache, _ = step(actor_params, cache, tokens[2:4, t])
        want, _, fresh, _ = step(actor_params, fresh, tokens[2:3, t])
        _close(logits[0], want[0])
    whole = jnp.concatenate([tokens[1:2, :5], tokens[3:4, :3]], axis=1)
    continued, _, _ = nets.forward(actor_params, whole)
    _close(logits[1], continued[0, -1])


def test_no_token_is_dropped_when_the_router_sends_everything_to_one_expert():
    """A router forced to the same two experts for every token: both groups
    hold all N rows, the other six none, and the output still equals the
    reference's dense loop."""
    _, actor_params, _, _ = _model(1)
    layer = actor_params["params"]["layer_0"]
    # Every token carries 1.0 in feature 0, and only that feature is routed
    # on: expert 3, then 5, wins for every token.
    router = jnp.zeros((64, 8)).at[0, 3].set(50.0).at[0, 5].set(25.0)
    x = jax.random.normal(jax.random.PRNGKey(3), (40, 64)).at[:, 0].set(1.0)
    out, stats = olmoe.moe(x, router, layer["gate"], layer["up"], layer["down"], 2)
    assert stats["expert_count"].tolist() == [0, 0, 0, 40, 0, 40, 0, 0]
    want, _ = reference.moe({**layer, "router": router}, x, _spec(1))
    _close(out, want)


LEAVES = [
    "actor/embed", "actor/final_norm", "actor/lm_head", "critic/kernel", "critic/bias",
] + [
    f"actor/layer_0/{name}"
    for name in ("input_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "post_attn_norm",
                 "router", "gate", "up", "down")
]


@pytest.fixture(scope="module")
def loss_and_grads():
    nets, actor_params, critic_params, tokens = _model(1)
    rng = np.random.default_rng(0)
    shape = tokens.shape
    batch = {
        "token": tokens,
        "action": jnp.asarray(rng.integers(0, VOCAB, shape), jnp.int32),
        "log_prob": jnp.asarray(-4.5 + 0.3 * rng.normal(size=shape), jnp.float32),
        "value": jnp.asarray(rng.normal(size=shape), jnp.float32),
        "advantage": jnp.asarray(rng.normal(size=shape), jnp.float32),
        "target": jnp.asarray(rng.normal(size=shape), jnp.float32),
    }
    params = ActorCriticParams(actor_params, critic_params)
    hyper = {k: HYPER[k] for k in ("clip_eps", "ent_coef", "vf_coef", "aux_coef")}
    (total, info), grads = jax.jit(
        jax.value_and_grad(lambda p: ff_lm_ppo.lm_ppo_loss(nets, p, batch, **hyper), has_aux=True)
    )(params)
    ref_batch = {**batch, "tokens": batch["token"]}
    want_total, want_parts, want_grads = reference.ppo_loss_and_grads(
        (actor_params, critic_params), ref_batch, _spec(1), hyper
    )
    flat = lambda actor, critic: {
        **{"actor/" + "/".join(k.key for k in path[1:]): leaf
           for path, leaf in jax.tree_util.tree_leaves_with_path(actor)},
        **{"critic/" + path[-1].key: leaf
           for path, leaf in jax.tree_util.tree_leaves_with_path(critic)},
    }
    return (
        {"total_loss": total, **info}, {"total_loss": want_total, **want_parts},
        flat(grads.actor_params, grads.critic_params), flat(*want_grads),
    )


@pytest.mark.parametrize("part", ["total_loss", "actor_loss", "value_loss", "entropy", "aux_loss"])
def test_loss_matches_the_reference_loss(loss_and_grads, part):
    got, want, _, _ = loss_and_grads
    _close(got[part], want[part])


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_leaf_matches_jax_grad_of_the_reference_loss(loss_and_grads, leaf):
    _, _, got, want = loss_and_grads
    assert sorted(got) == sorted(LEAVES) == sorted(want)
    assert float(jnp.max(jnp.abs(want[leaf]))) > 0.0  # a gradient that is there to compare
    _close(got[leaf], want[leaf])


# --------------------------------------------------------------------------- #
# The token task
# --------------------------------------------------------------------------- #


def _episode(env, key, policy_key):
    """One episode of `env` under a uniform random policy: (rewards, tokens)."""
    state, ts = env.reset(key)
    rewards, tokens = [], [int(ts.observation.agent_view[0])]
    for t in range(env.length):
        action = jax.random.randint(jax.random.fold_in(policy_key, t), (), 0, env.vocab_size)
        assert int(ts.observation.agent_view[1]) == t
        state, ts = env.step(state, action)
        rewards.append(float(ts.reward))
        tokens.append(int(ts.observation.agent_view[0]))
    assert bool(ts.last()) and float(ts.discount) == 0.0
    return rewards, tokens


def test_token_task_is_determined_by_its_key_and_rewards_at_the_end_only():
    from stoix_tpu.envs.token_task import TokenTask

    env = TokenTask(vocab_size=VOCAB, length=LENGTH)
    first = _episode(env, jax.random.PRNGKey(5), jax.random.PRNGKey(6))
    assert first == _episode(env, jax.random.PRNGKey(5), jax.random.PRNGKey(6))
    assert first[1][0] != _episode(env, jax.random.PRNGKey(8), jax.random.PRNGKey(6))[1][0]
    rewards, tokens = first
    assert rewards[:-1] == [0.0] * (LENGTH - 1) and 0.0 <= rewards[-1] <= 1.0
    matches = sum(a % 2 == b % 2 for a, b in zip(tokens[1:], tokens[:-1]))
    assert rewards[-1] == pytest.approx(matches / LENGTH)


@pytest.mark.parametrize("policy,low,high", [("uniform", 0.45, 0.55), ("same_parity", 1.0, 1.0)])
def test_token_task_reward_range(policy, low, high):
    """A uniform policy scores about 0.5; one that keeps the parity of the
    token before scores 1: the task is learnable and verifiable."""
    from stoix_tpu.envs.token_task import TokenTask

    env = TokenTask(vocab_size=VOCAB, length=LENGTH)

    def episode(key):
        reset_key, act_key = jax.random.split(key)
        state, ts = env.reset(reset_key)

        def step(carry, t):
            state, ts = carry
            action = jax.random.randint(jax.random.fold_in(act_key, t), (), 0, VOCAB - 1)
            if policy == "same_parity":
                action = (action // 2) * 2 + ts.observation.agent_view[0] % 2
            state, ts = env.step(state, action)
            return (state, ts), ts.reward

        _, rewards = jax.lax.scan(step, (state, ts), jnp.arange(LENGTH))
        return rewards.sum()

    returns = jax.vmap(episode)(jax.random.split(jax.random.PRNGKey(0), 512))
    assert float(returns.min()) >= 0.0 and float(returns.max()) <= 1.0
    assert low <= float(returns.mean()) <= high


# --------------------------------------------------------------------------- #
# The system
# --------------------------------------------------------------------------- #


def _config(extra=()):
    return config_lib.compose(
        config_lib.default_config_dir(), "default/anakin/default_ff_lm_ppo.yaml", TINY + list(extra)
    )


@pytest.mark.parametrize("overrides,message", [
    (["system.rollout_length=8"], "episode length (16) to equal system.rollout_length (8)"),
    (["arch.update_batch_size=2", "arch.total_num_envs=64"], "arch.update_batch_size must be 1"),
    (["system.num_minibatches=5"], "do not divide into system.num_minibatches=5"),
])
def test_setup_refuses_what_v1_does_not_support(devices, overrides, message):
    from stoix_tpu.parallel import MeshRoles
    from stoix_tpu.utils.timestep_checker import check_total_timesteps

    config = _config(overrides)
    mesh = MeshRoles.from_config(config).learn_mesh()
    config = check_total_timesteps(config, int(mesh.shape["data"]))
    env, _ = envs.make(config)
    with pytest.raises(ValueError, match=re.escape(message)):
        ff_lm_ppo.learner_setup(env, config, mesh, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def learner_scopes(devices):
    """Path components of the tiny learner's compiled program, by phase."""
    from stoix_tpu.parallel import MeshRoles
    from stoix_tpu.utils.timestep_checker import check_total_timesteps

    config = _config()
    mesh = MeshRoles.from_config(config).learn_mesh()
    config = check_total_timesteps(config, int(mesh.shape["data"]))
    env, _ = envs.make(config)
    setup = ff_lm_ppo.learner_setup(env, config, mesh, jax.random.PRNGKey(0))
    hlo = setup.learn.lower(setup.learner_state).compile().as_text()
    strip = lambda part: re.sub(r"^(?:\w+\()+|\)+$", "", part)
    paths = [[strip(p) for p in path.split("/")] for path in re.findall(r'op_name="([^"]+)"', hlo)]
    scopes = {
        phase: {part for path in paths if SCOPES[phase] in path for part in path}
        for phase in ("rollout", "update_epoch")
    }
    scopes["all"] = {part for path in paths for part in path}
    return scopes


@pytest.mark.parametrize("phase", ["rollout", "update_epoch"])
@pytest.mark.parametrize("scope", BLOCK_SCOPES)
def test_the_block_scopes_are_in_both_phases_of_the_compiled_learner(learner_scopes, phase, scope):
    assert SCOPES[scope] in learner_scopes[phase]


@pytest.mark.parametrize("phase,scope", [
    ("rollout", "rollout_policy"), ("rollout", "rollout_env"),
    ("update_epoch", "update_minibatch"), ("update_epoch", "minibatch_shuffle"), ("all", "gae"),
])
def test_the_learner_keeps_the_ppo_scopes(learner_scopes, phase, scope):
    assert SCOPES[scope] in learner_scopes[phase]


def test_a_short_run_learns_the_token_task(devices):
    """Through `run_experiment`, the path `main()` takes: the greedy return
    of the trained policy is far above the untrained 0.5; every window logs
    top-2 routed pairs a token in the rollout and in the update (nothing
    dropped), and its rollout's record rides out with the episode metrics."""
    from stoix_tpu.utils.logger import LogEvent, StoixLogger

    logged = {LogEvent.TRAIN: [], LogEvent.ACT: []}
    original = StoixLogger.log

    def log(self, metrics, t, t_eval, event):
        if event in logged:
            logged[event].append(metrics)
        return original(self, metrics, t, t_eval, event)

    StoixLogger.log = log
    try:
        final = ff_lm_ppo.run_experiment(_config([
            "arch.num_updates=12", "arch.num_evaluation=2", "arch.total_num_envs=64",
            "system.actor_lr=3e-3", "system.critic_lr=3e-3", "arch.evaluation_greedy=True",
        ]))
    finally:
        StoixLogger.log = original
    assert final > 0.75, final
    assert len(logged[LogEvent.TRAIN]) == 2
    for train in logged[LogEvent.TRAIN]:
        assert float(train["routed_pairs_per_token"]) == 2.0
        assert float(train["rollout_routed_pairs_per_token"]) == 2.0
        assert float(train["expert_load_max_over_mean"]) >= 1.0
    for act in logged[LogEvent.ACT]:  # one value a finished episode: 6 updates x 64 sequences
        assert {"rollout_action", "rollout_log_prob", "rollout_value"} <= set(act)
        assert np.asarray(act["rollout_log_prob"]).shape == (6 * 64,)


def test_the_benchmark_keeps_a_copy_of_the_reference():
    """benchmarks/references/ppo_olmoe.py carries its own copy of the plain
    forward (it may import nothing of the program): the two agree exactly."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.harness import loader

    copy = loader.load_reference("ppo_olmoe")
    _, actor_params, critic_params, tokens = _model(2)
    want = reference.forward(actor_params, critic_params, tokens, _spec(2))
    got = copy.forward(actor_params, critic_params, tokens, _spec(2))
    for key in ("logits", "values", "expert_index"):
        np.testing.assert_array_equal(np.asarray(got[key]), np.asarray(want[key]))
