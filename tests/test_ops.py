"""Tests for distributions, losses, value transforms, running statistics."""

import jax

import jax.numpy as jnp
import numpy as np
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
