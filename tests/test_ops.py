"""Tests for distributions, losses, value transforms, running statistics."""

import re

import jax

import jax.numpy as jnp
import numpy as np
import pytest
import scipy.stats

from stoix_tpu.ops import distributions as dists
from stoix_tpu.ops import losses, running_statistics, value_transforms

KEY = jax.random.PRNGKey(0)


# ---- Distributions ----------------------------------------------------------


def test_categorical_log_prob_and_entropy():
    logits = jnp.array([[1.0, 2.0, 0.5], [0.0, 0.0, 0.0]])
    d = dists.Categorical(logits)
    sp = scipy.stats.rv_discrete
    probs = np.exp(logits - scipy.special.logsumexp(logits, axis=-1, keepdims=True))
    np.testing.assert_allclose(d.probs, probs, atol=1e-4)
    np.testing.assert_allclose(
        d.log_prob(jnp.array([1, 2])), np.log(probs[[0, 1], [1, 2]]), atol=1e-4
    )
    want_entropy = -np.sum(probs * np.log(probs), axis=-1)
    np.testing.assert_allclose(d.entropy(), want_entropy, atol=1e-4)
    # Uniform logits -> entropy log(3)
    np.testing.assert_allclose(d.entropy()[1], np.log(3), atol=1e-4)


def test_categorical_mask():
    logits = jnp.array([0.0, 10.0, 0.0])
    d = dists.Categorical(logits, mask=jnp.array([1.0, 0.0, 1.0]))
    samples = d.sample_n(200, seed=KEY)
    assert not np.any(np.asarray(samples) == 1)


def test_categorical_kl():
    l1, l2 = jnp.array([1.0, 0.0, -1.0]), jnp.array([0.0, 0.0, 0.0])
    d1, d2 = dists.Categorical(l1), dists.Categorical(l2)
    p = np.asarray(d1.probs)
    q = np.asarray(d2.probs)
    np.testing.assert_allclose(d1.kl_divergence(d2), np.sum(p * np.log(p / q)), atol=1e-4)
    np.testing.assert_allclose(d1.kl_divergence(d1), 0.0, atol=1e-5)


def test_normal_log_prob_matches_scipy():
    d = dists.Normal(jnp.array(1.5), jnp.array(0.7))
    x = 0.3
    np.testing.assert_allclose(
        d.log_prob(jnp.array(x)), scipy.stats.norm.logpdf(x, 1.5, 0.7), atol=1e-4
    )
    np.testing.assert_allclose(d.entropy(), scipy.stats.norm.entropy(1.5, 0.7), atol=1e-4)


def test_normal_kl_analytic():
    d1 = dists.Normal(jnp.array(0.0), jnp.array(1.0))
    d2 = dists.Normal(jnp.array(1.0), jnp.array(2.0))
    mu1, s1, mu2, s2 = 0.0, 1.0, 1.0, 2.0
    want = np.log(s2 / s1) + (s1**2 + (mu1 - mu2) ** 2) / (2 * s2**2) - 0.5
    np.testing.assert_allclose(d1.kl_divergence(d2), want, atol=1e-5)


def test_tanh_normal_log_prob_consistency():
    d = dists.TanhNormal(jnp.array([0.3]), jnp.array([0.5]), minimum=-2.0, maximum=2.0)
    x, lp = d.sample_and_log_prob(seed=KEY)
    assert np.all(np.abs(np.asarray(x)) <= 2.0)
    np.testing.assert_allclose(lp, d.log_prob(x), atol=1e-4)
    # Monte-Carlo check of normalization: integrate exp(log_prob) over support.
    grid = jnp.linspace(-1.999, 1.999, 20001)
    dens = jnp.exp(d.log_prob(grid[:, None]))[:, 0]
    integral = float(jnp.trapezoid(dens, grid))
    assert abs(integral - 1.0) < 1e-2


def test_beta_matches_scipy():
    d = dists.Beta(jnp.array(2.0), jnp.array(3.0))
    x = 0.4
    np.testing.assert_allclose(d.log_prob(jnp.array(x)), scipy.stats.beta.logpdf(x, 2, 3), atol=1e-4)
    np.testing.assert_allclose(d.entropy(), scipy.stats.beta.entropy(2, 3), atol=1e-4)
    np.testing.assert_allclose(d.mean(), 0.4, atol=1e-5)
    samples = d.sample_n(2000, seed=KEY)
    assert abs(float(jnp.mean(samples)) - 0.4) < 0.02


def test_epsilon_greedy():
    prefs = jnp.array([1.0, 5.0, 2.0])
    d = dists.EpsilonGreedy(prefs, epsilon=0.3)
    np.testing.assert_allclose(d.probs, [0.1, 0.8, 0.1], atol=1e-4)
    assert int(d.mode()) == 1
    d0 = dists.Greedy(prefs)
    assert int(d0.sample(seed=KEY)) == 1


def test_discrete_valued_distribution():
    values = jnp.linspace(-2.0, 2.0, 5)
    logits = jnp.array([0.0, 0.0, 10.0, 0.0, 0.0])  # mass at 0.0
    d = dists.DiscreteValued(logits, values)
    np.testing.assert_allclose(d.mean(), 0.0, atol=1e-3)
    np.testing.assert_allclose(d.variance(), 0.0, atol=1e-2)


def test_multi_discrete():
    flat_logits = jnp.array([0.0, 10.0, 10.0, 0.0, 0.0])  # dims (2, 3)
    d = dists.MultiDiscrete(flat_logits, (2, 3))
    mode = d.mode()
    np.testing.assert_array_equal(mode, [1, 0])
    lp = d.log_prob(mode)
    # log_prob sums across dims.
    assert lp.shape == ()
    s = d.sample(seed=KEY)
    assert s.shape == (2,)


def test_mvn_diag():
    d = dists.MultivariateNormalDiag(jnp.zeros(3), jnp.ones(3))
    x = jnp.array([0.1, -0.2, 0.3])
    want = scipy.stats.multivariate_normal.logpdf(np.asarray(x), np.zeros(3), np.eye(3))
    np.testing.assert_allclose(d.log_prob(x), want, atol=1e-4)


# ---- Losses -----------------------------------------------------------------


def test_categorical_l2_project_mass_and_identity():
    z = jnp.linspace(-1.0, 1.0, 11)
    probs = jax.nn.softmax(jnp.arange(11.0))[None]
    # Identity projection when source support == target support.
    out = losses.categorical_l2_project(z[None], probs, z)
    np.testing.assert_allclose(out, probs, atol=1e-6)
    # Mass is preserved and clipped when support is shifted out of range.
    out2 = losses.categorical_l2_project(z[None] + 10.0, probs, z)
    np.testing.assert_allclose(out2.sum(), 1.0, atol=1e-6)
    np.testing.assert_allclose(out2[0, -1], 1.0, atol=1e-6)  # all mass at top atom


def test_categorical_l2_project_split_mass():
    z_q = jnp.array([0.0, 1.0, 2.0])
    z_p = jnp.array([[0.5]])  # halfway between atoms 0 and 1
    probs = jnp.array([[1.0]])
    out = losses.categorical_l2_project(z_p, probs, z_q)
    np.testing.assert_allclose(out[0], [0.5, 0.5, 0.0], atol=1e-6)


def test_ppo_clip_loss_values():
    lp = jnp.log(jnp.array([1.2, 0.5]))
    old = jnp.log(jnp.array([1.0, 1.0]))
    adv = jnp.array([1.0, 1.0])
    # ratios 1.2, 0.5; eps=0.1 clips to 1.1, 0.9 — min(ratio*adv, clip*adv)
    got = losses.ppo_clip_loss(lp, old, adv, 0.1)
    np.testing.assert_allclose(got, -np.mean([1.1, 0.5]), atol=1e-6)


def test_q_learning_analytic():
    q_tm1 = jnp.array([[1.0, 2.0]])
    q_t = jnp.array([[3.0, 1.0]])
    got = losses.q_learning(q_tm1, jnp.array([0]), jnp.array([1.0]), jnp.array([0.5]), q_t)
    # target = 1 + 0.5*3 = 2.5; td = 2.5 - 1 = 1.5; loss = 0.5*1.5^2
    np.testing.assert_allclose(got, 0.5 * 1.5**2, atol=1e-6)


def test_double_q_learning_uses_selector():
    q_tm1 = jnp.array([[0.0, 0.0]])
    q_t_value = jnp.array([[1.0, 100.0]])
    q_t_selector = jnp.array([[10.0, 0.0]])  # selects action 0
    got = losses.double_q_learning(
        q_tm1, jnp.array([0]), jnp.array([0.0]), jnp.array([1.0]), q_t_value, q_t_selector
    )
    np.testing.assert_allclose(got, 0.5 * 1.0, atol=1e-6)  # target=1.0 not 100


def test_huber_matches_quadratic_inside_delta():
    np.testing.assert_allclose(losses.huber_loss(jnp.array(0.5)), 0.125, atol=1e-6)
    np.testing.assert_allclose(losses.huber_loss(jnp.array(2.0)), 0.5 + 1.0, atol=1e-6)


def test_quantile_q_learning_runs_and_zero_when_consistent():
    B, N, A = 2, 5, 3
    dist = jnp.zeros((B, N, A))
    tau = jnp.broadcast_to((jnp.arange(N) + 0.5) / N, (B, N))
    got = losses.quantile_q_learning(
        dist, tau, jnp.zeros(B, jnp.int32), jnp.zeros(B), jnp.zeros(B), dist, dist
    )
    np.testing.assert_allclose(got, 0.0, atol=1e-6)


def test_munchausen_reduces_to_soft_q():
    # With coefficient 0, check loss is finite and uses the soft backup.
    q = jnp.array([[1.0, 2.0]])
    got = losses.munchausen_q_learning(
        q, jnp.array([0]), jnp.array([0.0]), jnp.array([1.0]), q, q, 0.03, 0.0
    )
    assert np.isfinite(float(got))


# ---- Value transforms -------------------------------------------------------


def test_signed_hyperbolic_roundtrip():
    x = jnp.linspace(-100.0, 100.0, 41)
    pair = value_transforms.SIGNED_HYPERBOLIC_PAIR
    np.testing.assert_allclose(pair.apply_inv(pair.apply(x)), x, atol=5e-3)


# ---- Running statistics -----------------------------------------------------


def test_running_statistics_matches_numpy():
    template = jnp.zeros((3,))
    state = running_statistics.init_state(template)
    rng = np.random.default_rng(0)
    all_data = []
    for _ in range(4):
        batch = rng.normal(1.5, 2.5, size=(16, 3)).astype(np.float32)
        all_data.append(batch)
        state = running_statistics.update(state, jnp.asarray(batch))
    data = np.concatenate(all_data)
    np.testing.assert_allclose(state.mean, data.mean(0), atol=1e-4)
    np.testing.assert_allclose(state.std, data.std(0), atol=1e-4)
    normed = running_statistics.normalize(jnp.asarray(data), state)
    np.testing.assert_allclose(np.asarray(normed).mean(0), 0.0, atol=1e-4)
    round_trip = running_statistics.denormalize(normed, state)
    np.testing.assert_allclose(round_trip, data, atol=1e-4)


def test_running_statistics_psum_over_mesh(devices):
    # Statistics computed shard-wise with psum must equal the global batch stats.
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(devices), ("data",))
    template = jnp.zeros((2,))
    rng = np.random.default_rng(1)
    batch = rng.normal(0.5, 1.5, size=(64, 2)).astype(np.float32)

    def shard_update(state, batch):
        return running_statistics.update(state, batch, axis_names=("data",))

    state = running_statistics.init_state(template)
    sharded = jax.shard_map(
        shard_update,
        mesh=mesh,
        in_specs=(P(), P("data")),
        out_specs=P(),
    )(state, jnp.asarray(batch))
    np.testing.assert_allclose(sharded.mean, batch.mean(0), atol=1e-4)
    np.testing.assert_allclose(sharded.std, batch.std(0), atol=1e-4)
    np.testing.assert_allclose(sharded.count, 64.0, atol=1e-6)


def test_epsilon_greedy_respects_mask():
    # Greedy mass must land on the best LEGAL action; mode must be legal.
    d = dists.EpsilonGreedy(jnp.array([5.0, 1.0, 2.0]), 0.1, mask=jnp.array([0.0, 1.0, 1.0]))
    assert int(d.mode()) == 2
    np.testing.assert_allclose(d.probs, [0.0, 0.05, 0.95], atol=1e-3)
    g = dists.Greedy(jnp.array([5.0, 1.0, 2.0]), mask=jnp.array([0.0, 1.0, 1.0]))
    assert int(g.mode()) == 2


def test_c51_loss_accepts_head_shaped_atoms():
    B, A, M = 3, 2, 11
    atoms = jnp.linspace(-1.0, 1.0, M)  # [M], as the heads return
    logits = jnp.zeros((B, A, M))
    loss = losses.categorical_double_q_learning(
        logits, atoms, jnp.zeros(B, jnp.int32), jnp.zeros(B), jnp.ones(B) * 0.9,
        logits, atoms, jnp.zeros((B, A)),
    )
    assert np.isfinite(float(loss))


# ---- pick_along_last: x[..., index] by select where the last axis is narrow --


def _gather_pick(x, index):
    """What `Categorical.log_prob` and the Q losses did before the helper."""
    return jnp.take_along_axis(x, index[..., None], axis=-1)[..., 0]


def _bits(x):
    return np.asarray(x).view(np.uint32)


def _pick_inputs(width, lead, masked, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed + width), 4)
    raw = 3.0 * jax.random.normal(ks[0], lead + (width,))
    if masked:  # at least one legal action a row; the index is drawn among them
        mask = jax.random.bernoulli(ks[1], 0.5, lead + (width,)).at[..., 0].set(True)
        index = jax.random.categorical(ks[2], jnp.where(mask, 0.0, -jnp.inf), axis=-1)
    else:
        mask = None
        index = jax.random.randint(ks[2], lead, 0, width)
    return raw, mask, index.astype(jnp.int32), jax.random.normal(ks[3], lead)


def _weighted_pick(pick, raw, mask, index, weight):
    """The pick as the losses meet it: from normalised, possibly masked logits,
    weighted, so the gradient reaches the raw logits through the pick."""
    if mask is not None:
        raw = jnp.where(mask, raw, jnp.finfo(raw.dtype).min)
    logits = raw - jax.nn.logsumexp(raw, axis=-1, keepdims=True)
    picked = pick(logits, index)
    return jnp.sum(weight * picked), picked


@pytest.mark.parametrize("transform", ["jit", "vmap"])
@pytest.mark.parametrize("masked", [False, True], ids=["unmasked", "masked"])
@pytest.mark.parametrize("lead", [(7,), (3, 5)], ids=["B", "TB"])
@pytest.mark.parametrize("width", [2, 3, 18, 128, 129, 512, 513, 1000])
def test_pick_along_last_is_the_gather_bit_for_bit(width, lead, masked, transform):
    """Value and gradient, either side of `PICK_SELECT_MAX_WIDTH`."""
    if transform == "vmap":
        lead = (4,) + lead
    raw, mask, index, weight = _pick_inputs(width, lead, masked)
    assert (width <= dists.PICK_SELECT_MAX_WIDTH) == (width <= 512)

    def run(pick):
        fn = jax.value_and_grad(
            lambda r, m, i, w: _weighted_pick(pick, r, m, i, w), has_aux=True
        )
        if transform == "vmap":
            fn = jax.vmap(fn, in_axes=(0, None if mask is None else 0, 0, 0))
        return jax.jit(fn)(raw, mask, index, weight)

    ((_, got), got_grad), ((_, want), want_grad) = run(dists.pick_along_last), run(_gather_pick)
    assert got.shape == lead and got_grad.shape == lead + (width,)
    np.testing.assert_array_equal(_bits(got), _bits(want))
    np.testing.assert_array_equal(_bits(got_grad), _bits(want_grad))
    if masked:  # no illegal action's finfo.min reached a picked value
        assert np.all(np.asarray(got) > -1e4)


def test_pick_along_last_ignores_what_it_does_not_pick_and_checks_the_rank():
    x = jnp.array([[jnp.nan, 1.5, -jnp.inf], [jnp.inf, jnp.nan, -0.0]])
    np.testing.assert_array_equal(dists.pick_along_last(x, jnp.array([1, 2])), [1.5, 0.0])
    with pytest.raises(ValueError, match="picks from no x"):
        dists.pick_along_last(jnp.zeros((4, 3, 2)), jnp.zeros((3,), jnp.int32))


def _log_prob_case(name):
    key = jax.random.PRNGKey(5)
    if name == "multi_discrete":
        dist = dists.MultiDiscrete(jax.random.normal(key, (6, 5)), (2, 3))
        value = dist.sample(seed=key)
        want = sum(_gather_pick(d.logits, value[..., i]) for i, d in enumerate(dist.dists))
        return dist, value, want
    width = 600 if name == "categorical_wide" else 6
    prefs = jax.random.normal(key, (4, 6, width))
    mask = jax.random.bernoulli(key, 0.6, prefs.shape).at[..., 1].set(True)
    dist = {
        "categorical": lambda: dists.Categorical(prefs),
        "categorical_wide": lambda: dists.Categorical(prefs),
        "categorical_masked": lambda: dists.Categorical(prefs, mask=mask),
        "epsilon_greedy": lambda: dists.EpsilonGreedy(prefs, 0.1, mask=mask),
        "greedy": lambda: dists.Greedy(prefs),
    }[name]()
    value = dist.sample(seed=key)
    return dist, value, _gather_pick(dist.logits, value)


@pytest.mark.parametrize("name", [
    "categorical", "categorical_wide", "categorical_masked", "multi_discrete",
    "epsilon_greedy", "greedy",
])
def test_log_prob_is_the_old_expression(name):
    dist, value, want = _log_prob_case(name)
    got = dist.log_prob(value)
    assert got.shape == want.shape
    np.testing.assert_array_equal(_bits(got), _bits(want))


def _ops_under(hlo_text, opcode, scope):
    return [
        line for line in hlo_text.splitlines()
        if re.search(rf"= [^=]*\b{opcode}\(", line)
        and re.search(rf'op_name="[^"]*\b{scope}\b', line)
    ]


@pytest.fixture(scope="module")
def discrete_programs(devices):
    """Compiled text of the Sebulba PPO learn step and `act_fn` on CartPole
    (two actions), and of the token policy's loss either side of the threshold."""
    import optax

    from stoix_tpu.base_types import (
        ActorCriticOptStates,
        ActorCriticParams,
        PPOTransition,
    )
    from stoix_tpu.envs.factory import make_factory
    from stoix_tpu.networks import olmoe
    from stoix_tpu.parallel import MeshRoles
    from stoix_tpu.systems.ppo.anakin import ff_lm_ppo
    from stoix_tpu.systems.ppo.sebulba import ff_ppo
    from stoix_tpu.utils import config as config_lib

    config = config_lib.compose(
        config_lib.default_config_dir(), "default/sebulba/default_ff_ppo.yaml",
        ["env=cartpole", "env.backend=cvec", "arch.total_num_envs=16",
         "arch.actor.device_ids=[0]", "arch.learner.device_ids=[0]",
         "arch.evaluator_device_id=0", "arch.total_timesteps=~", "arch.num_updates=2",
         "arch.num_evaluation=1", "system.rollout_length=8", "system.epochs=2",
         "system.num_minibatches=2"],
    )
    mesh = MeshRoles.from_config(config).learn_mesh()
    pool = make_factory(config)(1)
    assert pool.num_actions == 2
    config.system.action_dim = pool.num_actions
    actor, critic = ff_ppo._build_networks(config, pool.num_actions, None, env=pool)
    obs0 = jax.tree.map(jnp.asarray, pool.reset(seed=0).observation)
    params = ActorCriticParams(actor.init(KEY, obs0), critic.init(KEY, obs0))
    optim = optax.adam(1e-3)
    state = ff_ppo.CoreLearnerState(
        params,
        ActorCriticOptStates(optim.init(params.actor_params), optim.init(params.critic_params)),
        KEY,
        running_statistics.init_state(obs0.agent_view[0]),
    )
    obs = jax.tree.map(lambda x: jnp.zeros((8, 16) + x.shape[1:], x.dtype), obs0)
    zeros = jnp.zeros((8, 16))
    traj = PPOTransition(
        done=zeros.astype(bool), truncated=zeros.astype(bool), action=zeros.astype(jnp.int32),
        value=zeros, reward=zeros, log_prob=zeros, obs=obs, next_obs=obs, info={},
    )
    learn = ff_ppo.get_learn_step(
        actor.apply, critic.apply, (optim.update, optim.update), config, mesh
    )
    programs = {"learn": learn.lower(state, traj).compile().as_text()}
    act_fn = ff_ppo.get_act_fn(actor.apply, critic.apply, False)
    programs["act_fn"] = act_fn.lower((params, state.obs_stats), obs0, KEY).compile().as_text()

    for vocab in (97, 600):
        lm = olmoe.OlmoeLM(
            vocab_size=vocab, hidden_size=32, num_heads=2, head_dim=16, num_experts=4,
            experts_per_token=2, expert_width=16, num_layers=1,
        )
        head = olmoe.ValueHead()
        tokens = jnp.zeros((2, 8), jnp.int32)
        lm_params = ActorCriticParams(
            lm.init(KEY, tokens, method="forward"), head.init(KEY, jnp.zeros((2, 8, 32)))
        )
        nets = ff_lm_ppo.network_functions(lm, head, 8)
        batch = {
            "token": tokens, "action": tokens,
            **{k: jnp.zeros(tokens.shape) for k in ("log_prob", "value", "advantage", "target")},
        }
        loss = lambda p: ff_lm_ppo.lm_ppo_loss(
            nets, p, batch, clip_eps=0.2, ent_coef=0.01, vf_coef=0.5, aux_coef=0.01
        )
        lowered = jax.jit(jax.value_and_grad(loss, has_aux=True)).lower(lm_params)
        programs[f"lm_loss_{vocab}"] = lowered.compile().as_text()
    return programs


@pytest.mark.parametrize("program,scope", [
    ("learn", "ppo_minibatch"), ("act_fn", "rollout_policy"), ("lm_loss_97", "lm_head"),
])
def test_narrow_action_axis_is_picked_without_a_gather(discrete_programs, program, scope):
    hlo = discrete_programs[program]
    assert not _ops_under(hlo, "gather", scope), _ops_under(hlo, "gather", scope)
    assert not _ops_under(hlo, "scatter", scope)
    assert re.search(rf'op_name="[^"]*\b{scope}\b[^"]*\bpick_select\b', hlo)


def test_wide_vocabulary_keeps_its_one_gather(discrete_programs):
    hlo = discrete_programs["lm_loss_600"]
    gathers = _ops_under(hlo, "gather", "lm_head")
    assert len(gathers) == 1 and "jit(take_along_axis)/gather" in gathers[0], gathers
    assert "pick_select" not in hlo
