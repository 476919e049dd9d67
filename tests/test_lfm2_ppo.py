"""The LFM2 hybrid token policy (networks/lfm2.py, the router arguments of
networks/olmoe.py, systems/ppo/anakin/ff_lm_ppo.py with `network=lfm2_moe`)
against its plain reference (reference/lfm2.py), at a tiny preset on the CPU:
hidden 64, the published first six layers (conv, conv, full_attention, conv,
conv, conv; two dense feed-forwards of width 96, then four routed ones), 4
query heads and 2 key/value heads of 16, 32 experts top-4 of width 32 of
which a rank holds 8 (4 ranks), vocabulary 64, L = 16. Tolerance 1e-5
throughout: both sides are float32 on the CPU and differ only in summation
order."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stoix_tpu import envs
from stoix_tpu.base_types import ActorCriticParams
from stoix_tpu.networks import lfm2, olmoe
from stoix_tpu.observability import BLOCK_SCOPES, HYBRID_SCOPES, SCOPES, get_registry
from stoix_tpu.reference import lfm2 as reference
from stoix_tpu.systems.ppo.anakin import ff_lm_ppo
from stoix_tpu.utils import config as config_lib

TOL = 1e-5
VOCAB, LENGTH = 64, 16
LAYER_TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv"]
EXPERTS, HELD, TOP_K = 32, 8, 4
TINY = [
    "network=lfm2_moe",
    "network.actor_network.hidden_size=64", "network.actor_network.dense_width=96",
    "network.actor_network.num_heads=4", "network.actor_network.num_kv_heads=2",
    "network.actor_network.head_dim=16", "network.actor_network.expert_width=32",
    f"env.kwargs.vocab_size={VOCAB}", f"env.kwargs.length={LENGTH}",
    f"system.rollout_length={LENGTH}", "system.router_aux_loss_coef=0.0",
    "arch.total_num_envs=32", "system.num_minibatches=4",
    "arch.num_eval_episodes=8", "arch.total_timesteps=~", "arch.num_updates=2",
    "arch.num_evaluation=1", "arch.absolute_metric=False", "logger.use_console=False",
    "logger.checkpointing.save_model=False",
]
HYPER = {"clip_eps": 0.2, "ent_coef": 0.01, "vf_coef": 0.5, "aux_coef": 0.01}


def _spec(held=HELD, offset=0, layers=6, **extra):
    return {
        "hidden_size": 64, "layer_types": LAYER_TYPES, "num_hidden_layers": layers,
        "num_dense_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 16, "num_experts": held, "expert_offset": offset,
        "num_experts_per_tok": TOP_K, "norm_eps": 1e-5, "rope_theta": 1000000.0,
        "routed_scaling_factor": 1.0, **extra,
    }


def _actor(held=HELD, offset=0, vocab=VOCAB, **extra):
    return lfm2.Lfm2LM(
        vocab_size=vocab, hidden_size=64, layer_types=LAYER_TYPES, num_dense_layers=2,
        dense_width=96, num_heads=4, num_kv_heads=2, head_dim=16, num_experts=EXPERTS,
        experts_held=held, expert_offset=offset, experts_per_token=TOP_K, expert_width=32,
        expert_bias_scale=0.05, **extra,
    )


def _model(held=HELD, offset=0, **extra):
    actor, critic = _actor(held, offset, **extra), olmoe.ValueHead()
    key = jax.random.PRNGKey(6)
    actor_params = actor.init(key, jnp.zeros((1, 2), jnp.int32), method="forward")
    # normal(0.02) leaves every router near uniform; scale the weights up so
    # that routing, the convolutions, attention and the norms all matter.
    scale = lambda path, w: w * 8.0 if w.ndim > 1 and path[-1].key != "conv" else w
    actor_params = jax.tree_util.tree_map_with_path(scale, actor_params)
    critic_params = jax.tree.map(lambda w: w + 0.1, critic.init(key, jnp.zeros((1, 2, 64))))
    tokens = jax.random.randint(jax.random.PRNGKey(7), (4, LENGTH), 0, VOCAB)
    return ff_lm_ppo.network_functions(actor, critic, LENGTH), actor_params, critic_params, tokens


@pytest.fixture(scope="module")
def model():
    return _model()


def _close(got, want):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=TOL, atol=TOL)


def _sets(index):
    return np.sort(np.asarray(index), axis=-1)


@pytest.mark.parametrize("output", ["logits", "values", "expert_index", "bias_changed"])
def test_forward_matches_the_plain_reference(model, output):
    nets, actor_params, critic_params, tokens = model
    want = reference.forward(actor_params, critic_params, tokens, _spec())
    logits, hidden, stats = jax.jit(nets.forward)(actor_params, tokens)
    if output == "logits":
        _close(logits, want["logits"])
    elif output == "values":
        _close(nets.value(critic_params, hidden), want["values"])
    elif output == "expert_index":  # the chosen expert SETS are identical, layer by layer
        assert stats["expert_index"].shape == (4, tokens.size, TOP_K)  # the four routed layers
        assert (_sets(stats["expert_index"]) == _sets(want["expert_index"])).all()
        assert int(stats["expert_count"].sum()) == 4 * tokens.size * TOP_K
    else:  # what the selection bias re-routed, counted alike, and not nothing
        changed = np.any(_sets(want["expert_index"]) != _sets(want["plain_index"]), axis=-1)
        assert stats["bias_changed_sum"].tolist() == changed.sum(axis=-1).tolist()
        assert 0 < changed.sum() < changed.size


@pytest.mark.parametrize("prefix", [1, 2, 3, 7, LENGTH])
def test_decoding_through_the_hybrid_carry_is_the_reference_forward_of_every_prefix(model, prefix):
    """`prefix` steps from an empty carry — conv tails and a KV cache side by
    side — give, at the last of them, what the reference's whole forward of
    the first `prefix` tokens gives at its last position."""
    nets, actor_params, critic_params, tokens = model
    step = jax.jit(nets.step)
    carry = nets.init_cache(tokens.shape[0])
    for t in range(prefix):
        logits, hidden, carry, _ = step(actor_params, carry, tokens[:, t])
    want = reference.forward(actor_params, critic_params, tokens[:, :prefix], _spec())
    _close(logits, want["logits"][:, -1])
    _close(nets.value(critic_params, hidden), want["values"][:, -1])
    assert (np.asarray(carry.length) == prefix).all()


def test_the_carry_holds_two_kinds_of_state_and_the_gauge_says_how_much(model):
    nets, _, _, _ = model
    carry = nets.init_cache(3)
    kinds = [type(state).__name__ for state in carry.layers]
    assert kinds == ["ConvTail", "ConvTail", "KV", "ConvTail", "ConvTail", "ConvTail"]
    assert carry.layers[0].z.shape == (3, 2, 64)  # the last two rows of z a sequence
    assert carry.layers[2].k.shape == (LENGTH, 3, 2, 16)
    sizes = _actor().carry_bytes(3, LENGTH)
    assert sizes == {"conv_tail": 5 * 3 * 2 * 64 * 4, "kv": 2 * LENGTH * 3 * 2 * 16 * 4}


def test_a_reset_on_done_starts_a_new_sequence(model):
    """After `reset_carry` a sequence's conv tails are zeros and its stale
    cache entries are never read: its next steps equal a fresh carry's, and
    its neighbour goes on as if nothing had happened."""
    nets, actor_params, critic_params, tokens = model
    step = jax.jit(nets.step)
    # A position a sequence (`length [B]`): these two end apart.
    carry = _actor().init_carry(2, LENGTH)
    for t in range(5):
        _, _, carry, _ = step(actor_params, carry, tokens[:2, t])
    assert float(jnp.abs(carry.layers[0].z[0]).max()) > 0.0
    carry = nets.reset_cache(carry, jnp.array([True, False]))
    assert carry.length.tolist() == [0, 5]
    assert float(jnp.abs(carry.layers[0].z[0]).max()) == 0.0
    assert float(jnp.abs(carry.layers[0].z[1]).max()) > 0.0
    fresh = _actor().init_carry(1, LENGTH)
    for t in range(3):
        logits, _, carry, _ = step(actor_params, carry, tokens[2:4, t])
        want, _, fresh, _ = step(actor_params, fresh, tokens[2:3, t])
        _close(logits[0], want[0])
    whole = jnp.concatenate([tokens[1:2, :5], tokens[3:4, :3]], axis=1)
    continued = reference.forward(actor_params, critic_params, whole, _spec())
    _close(logits[1], continued["logits"][0, -1])


def test_the_bias_chooses_and_the_scores_weigh():
    """A bias that lifts expert 5 into every token's chosen set where the
    scores alone would not: the set changes, and the weights are still the
    scores at the chosen experts over their sum (+ 1e-6), not score + bias."""
    x = jax.random.normal(jax.random.PRNGKey(3), (40, 64))
    router = 0.3 * jax.random.normal(jax.random.PRNGKey(4), (64, EXPERTS))
    bias = jnp.zeros((EXPERTS,)).at[5].set(10.0)
    scores, weights, index = olmoe.route(
        x, router, TOP_K, True, score="sigmoid", bias=bias, epsilon=1e-6, scale=2.0
    )
    _, _, plain = olmoe.route(x, router, TOP_K, True, score="sigmoid")
    _close(scores, jax.nn.sigmoid(x @ router))
    assert (index == 5).any(axis=-1).all() and not (plain == 5).any(axis=-1).all()
    chosen = jnp.take_along_axis(scores, index, axis=-1)
    _close(weights, 2.0 * chosen / (chosen.sum(axis=-1, keepdims=True) + 1e-6))
    assert float(weights.max()) < 2.0  # a weight from score + bias would be near 2 * 10 / 12


def test_the_router_arguments_default_to_the_softmax_router():
    x = jax.random.normal(jax.random.PRNGKey(3), (40, 64))
    router = 0.3 * jax.random.normal(jax.random.PRNGKey(4), (64, EXPERTS))
    probs, weights, index = olmoe.route(x, router, TOP_K)
    want = jax.lax.top_k(jax.nn.softmax(x @ router, axis=-1), TOP_K)
    _close(weights, want[0])
    assert (index == want[1]).all() and float(jnp.abs(probs.sum(-1) - 1).max()) < 1e-5


def test_no_token_is_dropped_when_the_router_sends_everything_to_one_expert():
    """A router forced to the same four experts for every token, three of
    them held here: their groups hold all N rows each and the output still
    equals the reference's dense loop."""
    _, actor_params, _, _ = _model()
    ffn = actor_params["params"]["layer_2"]["ffn"]
    # Every token carries 1.0 in feature 0, and only that feature is routed on.
    router = jnp.zeros((64, EXPERTS))
    for expert, logit in ((3, 8.0), (5, 6.0), (7, 4.0), (20, 2.0)):
        router = router.at[0, expert].set(logit)
    x = jax.random.normal(jax.random.PRNGKey(3), (40, 64)).at[:, 0].set(1.0)
    bias = jnp.zeros((EXPERTS,))
    out, stats = olmoe.moe(
        x, router, ffn["gate"], ffn["up"], ffn["down"], TOP_K, held=(0, HELD), renormalise=True,
        score="sigmoid", bias=bias, epsilon=1e-6,
    )
    counts = stats["expert_count"].tolist()
    assert [counts[e] for e in (3, 5, 7, 20)] == [40] * 4 and sum(counts) == 40 * TOP_K
    assert int(stats["bias_changed_sum"]) == 0
    want, _ = reference.moe({**ffn, "router": router, "expert_bias": bias}, x, _spec())
    _close(out, want)


ACTOR_LEAVES = ["embed", "final_norm"] + [
    f"layer_{i}/{name}"
    for i, kind in enumerate(LAYER_TYPES)
    for name in ["operator_norm", "ffn_norm"]
    + (["mixer/in_proj", "mixer/conv", "mixer/out_proj"] if kind == "conv" else
       ["mixer/wq", "mixer/wk", "mixer/wv", "mixer/wo", "mixer/q_norm", "mixer/k_norm"])
    + (["ffn/w1", "ffn/w3", "ffn/w2"] if i < 2 else
       ["ffn/router", "ffn/expert_bias", "ffn/gate", "ffn/up", "ffn/down"])
]
LEAVES = ["actor/" + name for name in ACTOR_LEAVES] + ["critic/kernel", "critic/bias"]


@pytest.fixture(scope="module")
def loss_and_grads():
    nets, actor_params, critic_params, tokens = _model()
    rng = np.random.default_rng(0)
    shape = tokens.shape
    batch = {
        "token": tokens,
        "action": jnp.asarray(rng.integers(0, VOCAB, shape), jnp.int32),
        "log_prob": jnp.asarray(-4.0 + 0.3 * rng.normal(size=shape), jnp.float32),
        "value": jnp.asarray(rng.normal(size=shape), jnp.float32),
        "advantage": jnp.asarray(rng.normal(size=shape), jnp.float32),
        "target": jnp.asarray(rng.normal(size=shape), jnp.float32),
    }
    params = ActorCriticParams(actor_params, critic_params)
    (total, info), grads = jax.jit(
        jax.value_and_grad(lambda p: ff_lm_ppo.lm_ppo_loss(nets, p, batch, **HYPER), has_aux=True)
    )(params)
    ref_batch = {**batch, "tokens": batch["token"]}
    want_total, want_parts, want_grads = reference.ppo_loss_and_grads(
        (actor_params, critic_params), ref_batch, _spec(), HYPER
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


@pytest.mark.parametrize("part", [
    "total_loss", "actor_loss", "value_loss", "entropy", "aux_loss", "expert_load_max_over_mean",
    "routed_pairs_per_token", "held_pairs_per_token", "router_bias_changed_share",
])
def test_loss_matches_the_reference_loss(loss_and_grads, part):
    got, want, _, _ = loss_and_grads
    _close(got[part], want[part])


def test_the_loss_counts_no_dropped_pair(loss_and_grads):
    got, _, _, _ = loss_and_grads
    assert float(got["dropped_pairs"]) == 0.0 and float(got["routed_pairs_per_token"]) == TOP_K


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_leaf_matches_jax_grad_of_the_reference_loss(loss_and_grads, leaf):
    _, _, got, want = loss_and_grads
    assert sorted(got) == sorted(LEAVES) == sorted(want)
    if leaf.endswith("expert_bias"):  # only the choice reads it: no gradient on either side
        assert float(jnp.abs(got[leaf]).max()) == 0.0 == float(jnp.abs(want[leaf]).max())
        return
    assert float(jnp.max(jnp.abs(want[leaf]))) > 0.0  # a gradient that is there to compare
    _close(got[leaf], want[leaf])


# --------------------------------------------------------------------------- #
# One rank's share against the uncut layer and head
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def uncut():
    """The uncut model at the tiny size: all 32 experts, all 64 rows."""
    _, actor_params, critic_params, tokens = _model(held=EXPERTS)
    return actor_params, critic_params, tokens


def _rank_params(actor_params, rank=None, vocab=None):
    """Of the uncut tree: rank `rank` of 4's 8 experts a routed layer (with
    `rank`) and the first `vocab` rows of the embedding (with `vocab`)."""
    cut = lambda path, w: (
        w[rank * HELD:(rank + 1) * HELD]
        if path[-1].key in ("gate", "up", "down") and rank is not None else
        w[:vocab] if path[-1].key == "embed" and vocab else w
    )
    return jax.tree_util.tree_map_with_path(cut, actor_params)


def test_the_four_ranks_shares_add_up_to_the_uncut_layer(uncut):
    """The routed layer's outputs for `held` = (0, 8), (8, 8), (16, 8),
    (24, 8) — the program's held-experts path on each rank's own weights —
    sum to the uncut reference's layer."""
    actor_params, _, _ = uncut
    ffn = actor_params["params"]["layer_3"]["ffn"]
    x = jax.random.normal(jax.random.PRNGKey(5), (48, 64))
    want, _ = reference.moe(ffn, x, _spec(held=EXPERTS))
    total = jnp.zeros_like(x)
    for rank in range(4):
        mine = _rank_params(actor_params, rank)["params"]["layer_3"]["ffn"]
        assert mine["gate"].shape[0] == HELD
        part, stats = olmoe.moe(
            x, mine["router"], mine["gate"], mine["up"], mine["down"], TOP_K,
            held=(rank * HELD, HELD), renormalise=True, score="sigmoid",
            bias=mine["expert_bias"], epsilon=1e-6,
        )
        # ... and equal the reference's own share, given the uncut weights
        share, _ = reference.moe(ffn, x, _spec(held=HELD, offset=rank * HELD))
        _close(part, share)
        assert float(jnp.abs(part).max()) > 0.0
        total = total + part
    _close(total, want)


def test_the_sliced_heads_logits_are_the_uncut_heads_rows(uncut):
    """Rank 0's rows of the tied embedding, with tokens drawn from the slice:
    the program's logits over the slice are the uncut model's first rows."""
    actor_params, critic_params, tokens = uncut
    rows = VOCAB // 4
    tokens = tokens % rows
    want = reference.forward(actor_params, critic_params, tokens, _spec(held=EXPERTS))
    actor = _actor(held=EXPERTS, vocab=rows)
    logits, _, _ = actor.apply(_rank_params(actor_params, vocab=rows), tokens, method="forward")
    assert logits.shape[-1] == rows
    _close(logits, want["logits"][..., :rows])
    sliced = reference.forward(
        actor_params, critic_params, tokens, _spec(held=EXPERTS, vocab_slice=(0, rows))
    )
    _close(sliced["logits"], want["logits"][..., :rows])


# --------------------------------------------------------------------------- #
# The system
# --------------------------------------------------------------------------- #


def _config(extra=()):
    return config_lib.compose(
        config_lib.default_config_dir(), "default/anakin/default_ff_lm_ppo.yaml", TINY + list(extra)
    )


def _paths(hlo):
    strip = lambda part: re.sub(r"^(?:\w+\()+|\)+$", "", part)
    return [[strip(p) for p in path.split("/")] for path in re.findall(r'op_name="([^"]+)"', hlo)]


@pytest.fixture(scope="module")
def program_scopes(devices):
    """Path components of the tiny learner's compiled program, by phase, and
    of the evaluator's."""
    from stoix_tpu.evaluator import carry_evaluator_setup
    from stoix_tpu.parallel import MeshRoles
    from stoix_tpu.utils.timestep_checker import check_total_timesteps

    config = _config()
    mesh = MeshRoles.from_config(config).learn_mesh()
    config = check_total_timesteps(config, int(mesh.shape["data"]))
    env, eval_env = envs.make(config)
    setup = ff_lm_ppo.learner_setup(env, config, mesh, jax.random.PRNGKey(0))
    paths = _paths(setup.learn.lower(setup.learner_state).compile().as_text())
    scopes = {
        phase: {part for path in paths if SCOPES[phase] in path for part in path}
        for phase in ("rollout", "update_epoch")
    }
    evaluator, _ = carry_evaluator_setup()(eval_env, setup.eval_act_fn, config, mesh)
    lowered = jax.jit(evaluator).lower(
        setup.eval_params_fn(setup.learner_state), jax.random.PRNGKey(1)
    )
    scopes["evaluator"] = {part for path in _paths(lowered.compile().as_text()) for part in path}
    return scopes


@pytest.mark.parametrize("phase", ["rollout", "update_epoch", "evaluator"])
@pytest.mark.parametrize("scope", HYBRID_SCOPES + BLOCK_SCOPES)
def test_the_scopes_are_in_both_phases_of_the_learner_and_in_the_evaluator(
    program_scopes, phase, scope
):
    assert SCOPES[scope] in program_scopes[phase]


def test_learner_setup_publishes_the_carry_by_kind(program_scopes):
    series = get_registry().gauge("stoix_tpu_lm_carry_bytes").labels_and_values()
    gauge = {dict(labels)["kind"]: value for labels, value in series}
    per_shard = 32 // 8  # sequences a shard of the 8 virtual devices
    assert gauge == {
        "conv_tail": 5 * per_shard * 2 * 64 * 4, "kv": 2 * LENGTH * per_shard * 2 * 16 * 4
    }


def test_a_short_run_learns_the_token_task(devices):
    """Through `run_experiment`, the path `main()` takes: the greedy return
    of the trained policy is far above the untrained 0.5; every window logs
    top-4 routed pairs a token in the rollout and in the update (nothing
    dropped), the pairs held here, and what the selection bias re-routed."""
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
        assert float(train["routed_pairs_per_token"]) == TOP_K
        assert float(train["rollout_routed_pairs_per_token"]) == TOP_K
        assert float(train["dropped_pairs"]) == 0.0
        assert 0.0 < float(train["held_pairs_per_token"]) < TOP_K
        assert 0.0 < float(train["rollout_held_pairs_per_token"]) < TOP_K
        assert float(train["expert_load_max_over_mean"]) >= 1.0
        assert 0.0 < float(train["router_bias_changed_share"]) < 1.0
    for act in logged[LogEvent.ACT]:  # one value a finished episode: 6 updates x 64 sequences
        assert {"rollout_action", "rollout_log_prob", "rollout_value"} <= set(act)
        assert np.asarray(act["rollout_log_prob"]).shape == (6 * 64,)


def test_the_benchmark_keeps_a_copy_of_the_reference(model):
    """benchmarks/references/ppo_lfm2.py carries its own copy of the plain
    forward and loss (it may import nothing of the program): they agree
    exactly."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.harness import loader

    copy = loader.load_reference("ppo_lfm2")
    _, actor_params, critic_params, tokens = model
    want = reference.forward(actor_params, critic_params, tokens, _spec())
    got = copy.forward(actor_params, critic_params, tokens, _spec())
    for key in ("logits", "values", "expert_index", "plain_index"):
        np.testing.assert_array_equal(np.asarray(got[key]), np.asarray(want[key]))
