"""`ops/minibatch.shuffled_minibatch_epoch` against the formulation it
replaced (docs/DESIGN.md §2.7a): from the same keys the SGD step is handed
the same minibatches, bit for bit — alone, under `jit`, under
`vmap(axis_name="batch")` with one lane (squeezed) and two (mapped, each its
own permutation), inside the epoch scan — and each converted learner reaches
the parameters of that formulation after one update. The plain reference
(`_parent_epoch`: permute, `take` every leaf, reshape, scan) lives here."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from stoix_tpu.base_types import (
    ActorCriticOptStates,
    ActorCriticParams,
    PPOTransition,
)
from stoix_tpu.envs.types import Observation
from stoix_tpu.observability import SCOPES, get_registry
from stoix_tpu.ops import minibatch
from stoix_tpu.utils import config as config_lib

T, E, M = 4, 8, 4


def _parent_epoch(step, carry, data, num_minibatches):
    """The parent commit's epoch, with `shuffled_minibatch_epoch`'s signature."""
    del carry

    def epoch(carry, shuffle_key):
        n = jax.tree.leaves(data)[0].shape[0] * jax.tree.leaves(data)[0].shape[1]
        permutation = jax.random.permutation(shuffle_key, n)
        flat = jax.tree.map(lambda x: x.reshape((n,) + x.shape[2:]), data)
        shuffled = jax.tree.map(lambda x: jnp.take(x, permutation, axis=0), flat)
        minibatches = jax.tree.map(
            lambda x: x.reshape((int(num_minibatches), -1) + x.shape[1:]), shuffled
        )
        return jax.lax.scan(step, carry, minibatches)

    return epoch


def _ppo_tree(key, obs_dim, num_actions, discrete, lead=()):
    """(traj_batch, advantages, targets) with the leaves of a cell's learner."""
    ks = jax.random.split(key, 8)
    shape = lead + (T, E)
    normal = lambda k, *tail: jax.random.normal(k, shape + tail, jnp.float32)
    obs = Observation(
        agent_view=normal(ks[0], obs_dim),
        action_mask=jnp.ones(shape + (num_actions,), jnp.float32),
        step_count=jax.random.randint(ks[1], shape, 0, 1000, jnp.int32),
    )
    action = (
        jax.random.randint(ks[2], shape, 0, num_actions, jnp.int32)
        if discrete else normal(ks[2], num_actions)
    )
    traj = PPOTransition(
        done=jax.random.bernoulli(ks[3], 0.1, shape), truncated=jnp.zeros(shape, bool),
        action=action, value=normal(ks[4]), reward=normal(ks[5]), log_prob=normal(ks[6]),
        obs=obs, next_obs=obs, info={"episode_return": normal(ks[7])},
    )
    return traj, normal(ks[5]), normal(ks[6])


def _mixed_tree(key, lead=()):
    ks = jax.random.split(key, 5)
    shape = lead + (T, E)
    odd = jnp.asarray([0.0, -0.0, jnp.nan, jnp.inf, 1e-42], jnp.float32)
    return {
        "f32": jax.random.normal(ks[0], shape + (5,)) + jnp.resize(odd, shape + (5,)),
        "i32": jax.random.randint(ks[1], shape, -(2**31), 2**31 - 1, jnp.int32),
        "u32_2d": jax.random.bits(ks[2], shape + (2, 3), jnp.uint32),
        "bool": jax.random.bernoulli(ks[3], 0.5, shape),
        "bf16": jax.random.normal(ks[4], shape + (3,), jnp.bfloat16),
        "wide": jax.random.normal(ks[4], shape + (8, 16), jnp.float32),
    }


TREES = {
    "mixed": _mixed_tree,
    "ant": lambda key, lead=(): _ppo_tree(key, 27, 8, False, lead),
    "cartpole": lambda key, lead=(): _ppo_tree(key, 4, 2, True, lead),
}


def _identity_step(carry, minibatch_):
    return carry, minibatch_


def _minibatches(make_epoch, data, key, epochs):
    """[epochs, M, B, ...]: what the SGD step is handed over `epochs` epochs,
    through the learners' own loop (split the key, one epoch, carry on)."""
    epoch = make_epoch(_identity_step, jnp.int32(0), data, M)

    def body(key, _):
        key, shuffle_key = jax.random.split(key)
        return key, epoch(jnp.int32(0), shuffle_key)[1]

    return jax.lax.scan(body, key, None, epochs)[1]


def _bits(x):
    x = np.asarray(x)
    return x.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


def _assert_same_bits(got, want):
    got_leaves, got_def = jax.tree.flatten(got)
    want_leaves, want_def = jax.tree.flatten(want)
    assert got_def == want_def
    for g, w in zip(got_leaves, want_leaves):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(_bits(g), _bits(w))


@pytest.mark.parametrize("lanes,epochs", [(0, 1), (0, 3), (1, 1), (1, 3), (2, 1), (2, 3)],
                         ids=["jit", "jit_scan", "vmap1", "vmap1_scan", "vmap2", "vmap2_scan"])
@pytest.mark.parametrize("tree", sorted(TREES))
def test_minibatches_are_the_parents_bit_for_bit(tree, lanes, epochs):
    key = jax.random.PRNGKey(24)
    lead = (lanes,) if lanes else ()
    data = TREES[tree](key, lead)
    keys = jax.random.split(key, lanes) if lanes else key

    def run(make_epoch):
        fn = lambda d, k: _minibatches(make_epoch, d, k, epochs)
        if lanes:
            fn = jax.vmap(fn, axis_name="batch")
        return jax.jit(fn)(data, keys)

    got, want = run(minibatch.shuffled_minibatch_epoch), run(_parent_epoch)
    _assert_same_bits(got, want)
    first = jax.tree.leaves(got)[0]
    assert first.shape[len(lead):len(lead) + 3] == (epochs, M, T * E // M)
    if lanes == 2:  # each lane its own permutation
        assert not np.array_equal(_bits(first[0]), _bits(first[1]))
    if epochs > 1:  # drawn anew every epoch
        moved = first[0] if lanes else first
        assert not np.array_equal(_bits(moved[0]), _bits(moved[1]))


@pytest.mark.parametrize(
    "make_epoch", [minibatch.shuffled_minibatch_epoch, _parent_epoch], ids=["change", "parent"]
)
def test_a_batch_that_does_not_divide_is_refused_as_by_the_parent(make_epoch):
    data = _mixed_tree(jax.random.PRNGKey(0))
    with pytest.raises(TypeError, match="reshape"):
        make_epoch(_identity_step, jnp.int32(0), data, 3)(jnp.int32(0), jax.random.PRNGKey(1))


def _ppo_step(weights, batch):
    """A PPO-shaped SGD step: reads obs.agent_view, action, log_prob, value,
    advantages, targets (and the action mask for a discrete action), syncs
    over the "batch" axis, and nothing else of the transition."""
    traj, advantages, targets = batch

    def loss(w):
        hidden = jnp.tanh(traj.obs.agent_view @ w)
        if jnp.issubdtype(traj.action.dtype, jnp.integer):
            logits = jnp.where(traj.obs.action_mask > 0, hidden[:, : traj.obs.action_mask.shape[-1]], -1e9)
            log_prob = jnp.take_along_axis(
                jax.nn.log_softmax(logits), traj.action[:, None], axis=-1
            )[:, 0]
        else:
            log_prob = -jnp.sum((hidden[:, : traj.action.shape[-1]] - traj.action) ** 2, -1)
        ratio = jnp.exp(log_prob - traj.log_prob)
        value = hidden.sum(-1)
        return jnp.mean(ratio * advantages) + jnp.mean((value - targets) ** 2 + traj.value)

    grads = jax.lax.pmean(jax.grad(loss)(weights), axis_name="batch")
    return weights - 1e-3 * grads, jnp.sum(grads)


def _gauge():
    series = get_registry().snapshot()["stoix_tpu_minibatch_shuffle"]["series"]
    return {s["labels"]["field"]: s["value"] for s in series}


# (tree, lanes) -> packed leaves, their words a sample, leaves read but gathered alone
EXPECTED_GAUGE = {
    ("ant", 1): (6, 27 + 8 + 1 + 1 + 1 + 1, 0),
    ("ant", 2): (6, 39, 0),
    ("cartpole", 1): (7, 4 + 2 + 1 + 1 + 1 + 1 + 1, 0),
}


@pytest.fixture(scope="module")
def ppo_shaped():
    """{(tree, lanes): (compiled HLO text, gauge, weights new, weights parent)}
    of an epochs x minibatches PPO-shaped update under vmap(axis_name="batch")."""
    out = {}
    for (tree, lanes) in EXPECTED_GAUGE:
        key = jax.random.PRNGKey(7)
        data = TREES[tree](key, (lanes,))
        weights = jnp.broadcast_to(
            0.1 * jax.random.normal(key, (data[0].obs.agent_view.shape[-1], 16)),
            (lanes, data[0].obs.agent_view.shape[-1], 16),
        )

        def update(make_epoch, weights, data, key):
            epoch = make_epoch(_ppo_step, weights, data, M)

            def body(carry, _):
                weights, key = carry
                key, shuffle_key = jax.random.split(key)
                weights, _ = epoch(weights, shuffle_key)
                return (weights, key), None

            return jax.lax.scan(body, (weights, key), None, 2)[0][0]

        keys = jax.random.split(key, lanes)
        fns = {
            name: jax.jit(jax.vmap(lambda w, d, k, f=f: update(f, w, d, k), axis_name="batch"))
            for name, f in (("new", minibatch.shuffled_minibatch_epoch), ("parent", _parent_epoch))
        }
        compiled = fns["new"].lower(weights, data, keys).compile()
        gauge = _gauge()
        out[(tree, lanes)] = (
            compiled.as_text(), gauge, compiled(weights, data, keys),
            fns["parent"](weights, data, keys),
        )
    return out


def _ops_under(hlo_text, opcode, scope):
    return [
        line for line in hlo_text.splitlines()
        if re.search(rf"= [^=]*\b{opcode}\(", line)
        and re.search(rf'op_name="[^"]*\b{scope}\b', line)
    ]


@pytest.mark.parametrize("tree,lanes", sorted(EXPECTED_GAUGE))
def test_ppo_shaped_program_one_gather_a_minibatch(ppo_shaped, tree, lanes):
    hlo, _, new, parent = ppo_shaped[(tree, lanes)]
    # The minibatch scan's body appears once in the text and runs M times an
    # epoch: one gather there is one gather a minibatch step. The dead
    # leaves' gathers (reward, done, next_obs, info, step_count) are gone.
    gathers = _ops_under(hlo, "gather", SCOPES["minibatch_shuffle"])
    assert len(gathers) == 1, gathers
    _assert_same_bits(new, parent)


@pytest.mark.parametrize("tree,lanes", sorted(EXPECTED_GAUGE))
def test_permutation_sorts_the_unbatched_operand_for_one_lane(ppo_shaped, tree, lanes):
    hlo = ppo_shaped[(tree, lanes)][0]
    sorts = _ops_under(hlo, "sort", SCOPES["minibatch_shuffle"])
    assert sorts
    want = rf"u32\[{T * E}\]" if lanes == 1 else rf"u32\[{lanes},{T * E}\]"
    for line in sorts:
        assert re.search(rf"sort\({want}", line) or re.search(rf"= \({want}", line), line


@pytest.mark.parametrize("tree,lanes", sorted(EXPECTED_GAUGE))
def test_gauge_reads_what_the_shuffle_was_made_of(ppo_shaped, tree, lanes):
    gauge = ppo_shaped[(tree, lanes)][1]
    packed, words, alone = EXPECTED_GAUGE[(tree, lanes)]
    assert gauge == {
        "packed_leaves": packed, "packed_words": words, "slab_words": 128,
        "alone_leaves": alone, "unbatched": 1.0 if lanes == 1 else 0.0,
    }


def test_wide_and_narrow_dtype_leaves_are_gathered_alone():
    data = _mixed_tree(jax.random.PRNGKey(3))
    jax.jit(lambda d, k: _minibatches(minibatch.shuffled_minibatch_epoch, d, k, 1))(
        data, jax.random.PRNGKey(4)
    )
    # f32 [5] + i32 [] + u32 [2, 3] packed; bool, bf16 and the 128-word leaf alone.
    assert _gauge() == {
        "packed_leaves": 3, "packed_words": 5 + 1 + 6, "slab_words": 128,
        "alone_leaves": 3, "unbatched": 0.0,
    }


# --- the converted learners against the parent's epoch, one update each ----

ANAKIN_TINY = [
    "arch.total_num_envs=32", "arch.num_updates=1", "arch.total_timesteps=~",
    "arch.num_evaluation=1", "system.rollout_length=4", "system.epochs=2",
    "system.num_minibatches=2",
]
ANAKIN_CASES = {
    "discrete": ("default/anakin/default_ff_ppo.yaml", ["env=identity_game"]),
    "discrete_two_lanes": (
        "default/anakin/default_ff_ppo.yaml",
        ["env=identity_game", "arch.update_batch_size=2"],
    ),
    "continuous": ("default/anakin/default_ff_ppo_continuous.yaml", []),
}


def _anakin_params_after_one_update(case):
    from stoix_tpu import envs
    from stoix_tpu.parallel import MeshRoles
    from stoix_tpu.systems.ppo.anakin import ff_ppo
    from stoix_tpu.utils.timestep_checker import check_total_timesteps

    root, extra = ANAKIN_CASES[case]
    config = config_lib.compose(config_lib.default_config_dir(), root, ANAKIN_TINY + extra)
    mesh = MeshRoles.from_config(config).learn_mesh()
    config = check_total_timesteps(config, int(mesh.shape["data"]))
    env, _ = envs.make(config)
    setup = ff_ppo.learner_setup(env, config, mesh, jax.random.PRNGKey(0))
    return jax.device_get(setup.learn(setup.learner_state).learner_state.params)


@pytest.mark.parametrize("case", sorted(ANAKIN_CASES))
def test_anakin_learner_reaches_the_parents_parameters(devices, monkeypatch, case):
    from stoix_tpu.systems.ppo.anakin import ff_ppo

    new = _anakin_params_after_one_update(case)
    monkeypatch.setattr(ff_ppo, "shuffled_minibatch_epoch", _parent_epoch)
    parent = _anakin_params_after_one_update(case)
    # The minibatches are the same bits (tests above), and so are the two-lane
    # learner's parameters. With one lane XLA:CPU compiles the loss's small
    # matmuls and sums over columns SLICED from the gathered [B, W] slab with
    # another vectorisation than over separately gathered [1, B, ...] leaves,
    # so float32 sums associate differently: measured 1.5e-8 on parameters of
    # magnitude <= 0.43 (half an ulp) after the four Adam steps, in both
    # one-lane cases. The bound is two ulps of the largest parameter.
    for n, p in zip(jax.tree.leaves(new), jax.tree.leaves(parent)):
        np.testing.assert_allclose(n, p, rtol=0, atol=1.2e-7)
    if case == "discrete_two_lanes":
        _assert_same_bits(new, parent)
    assert _gauge()["packed_leaves"] >= 6  # and the change's trace did pack


def _sebulba_params_after_one_update(impact):
    from stoix_tpu.envs.factory import make_factory
    from stoix_tpu.ops import running_statistics
    from stoix_tpu.parallel import MeshRoles
    from stoix_tpu.systems.ppo.sebulba import ff_ppo

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
    config.system.action_dim = pool.num_actions
    actor, critic = ff_ppo._build_networks(config, pool.num_actions, None, env=pool)
    obs0 = jax.tree.map(jnp.asarray, pool.reset(seed=0).observation)
    key = jax.random.PRNGKey(0)
    params = ActorCriticParams(actor.init(key, obs0), critic.init(key, obs0))
    optim = optax.adam(1e-2)
    state = ff_ppo.CoreLearnerState(
        params,
        ActorCriticOptStates(optim.init(params.actor_params), optim.init(params.critic_params)),
        jax.random.PRNGKey(1),
        running_statistics.init_state(obs0.agent_view[0]),
    )
    steps, n = 8, 16
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    normal = lambda k: jax.random.normal(k, (steps, n), jnp.float32)
    obs = Observation(
        agent_view=0.1 * jax.random.normal(ks[0], (steps, n) + obs0.agent_view.shape[1:]),
        action_mask=jnp.ones((steps, n, pool.num_actions), jnp.float32),
        step_count=jnp.zeros((steps, n), jnp.int32),
    )
    traj = PPOTransition(
        done=jax.random.bernoulli(ks[1], 0.1, (steps, n)), truncated=jnp.zeros((steps, n), bool),
        action=jax.random.randint(ks[2], (steps, n), 0, pool.num_actions, jnp.int32),
        value=normal(ks[3]), reward=normal(ks[4]), log_prob=-jnp.abs(normal(ks[5])),
        obs=obs, next_obs=obs, info={},
    )
    update_fns = (optim.update, optim.update)
    if impact:
        learn = ff_ppo.get_impact_learn_step(
            actor.apply, critic.apply, update_fns, config, mesh, rho_clip=2.0
        )
        new_state, _ = learn(state, params, traj)
    else:
        learn = ff_ppo.get_learn_step(actor.apply, critic.apply, update_fns, config, mesh)
        new_state, _ = learn(state, traj)
    return jax.device_get(new_state.params)


@pytest.mark.parametrize("impact", [False, True], ids=["learn_step", "impact_learn_step"])
def test_sebulba_learner_reaches_the_parents_parameters(devices, monkeypatch, impact):
    from stoix_tpu.systems.ppo.sebulba import ff_ppo

    new = _sebulba_params_after_one_update(impact)
    gauge = _gauge()
    monkeypatch.setattr(ff_ppo, "shuffled_minibatch_epoch", _parent_epoch)
    parent = _sebulba_params_after_one_update(impact)
    _assert_same_bits(new, parent)
    assert gauge["packed_leaves"] >= 6 and gauge["unbatched"] == 0.0
