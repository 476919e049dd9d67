"""The Kanana-2 latent-attention token policy (networks/mla.py's mixer and the
shared expert on networks/lfm2.py's stack, ops/pallas_attention.py with values
of a head size of their own, systems/ppo/anakin/ff_lm_ppo.py with
`network=kanana2_moe`) against its plain reference (reference/kanana2.py), at
a tiny preset on the CPU: hidden 64, five latent-attention layers (one dense
feed-forward of width 96, then four routed ones), 4 heads of 16 + 8 rotated
query/key and 12 value dimensions over a latent of 24, 32 experts top-3 of
width 32 of which a rank holds 4 (8 ranks) beside 2 shared experts,
vocabulary 64, L = 16. Tolerance 1e-5 throughout: both sides are float32 on
the CPU and differ only in summation order."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stoix_tpu import envs
from stoix_tpu.base_types import ActorCriticParams
from stoix_tpu.networks import lfm2, mla, olmoe
from stoix_tpu.observability import BLOCK_SCOPES, LATENT_SCOPES, SCOPES, get_registry
from stoix_tpu.ops.pallas_attention import flash_attention
from stoix_tpu.ops.ring_attention import full_attention
from stoix_tpu.reference import kanana2 as reference
from stoix_tpu.systems.ppo.anakin import ff_lm_ppo
from stoix_tpu.utils import config as config_lib

TOL = 1e-5
VOCAB, LENGTH, LAYERS = 64, 16, 5
EXPERTS, HELD, TOP_K, RANKS = 32, 4, 3, 8
HEADS, RANK, NOPE, ROPE, V_DIM = 4, 24, 16, 8, 12
SCALING = 2.448
TINY = [
    "network=kanana2_moe",
    "network.actor_network.hidden_size=64", "network.actor_network.dense_width=96",
    f"network.actor_network.num_heads={HEADS}", f"network.actor_network.num_kv_heads={HEADS}",
    f"network.actor_network.head_dim={ROPE}", f"network.actor_network.kv_lora_rank={RANK}",
    f"network.actor_network.qk_nope_head_dim={NOPE}", f"network.actor_network.qk_rope_head_dim={ROPE}",
    f"network.actor_network.v_head_dim={V_DIM}", f"network.actor_network.num_experts={EXPERTS}",
    f"network.actor_network.experts_held={HELD}", f"network.actor_network.experts_per_token={TOP_K}",
    "network.actor_network.expert_width=32",
    f"env.kwargs.vocab_size={VOCAB}", f"env.kwargs.length={LENGTH}",
    f"system.rollout_length={LENGTH}", "system.router_aux_loss_coef=0.0",
    "arch.total_num_envs=32", "system.num_minibatches=4",
    "arch.num_eval_episodes=8", "arch.total_timesteps=~", "arch.num_updates=2",
    "arch.num_evaluation=1", "arch.absolute_metric=False", "logger.use_console=False",
    "logger.checkpointing.save_model=False",
]
HYPER = {"clip_eps": 0.2, "ent_coef": 0.01, "vf_coef": 0.5, "aux_coef": 0.01}


def _spec(held=HELD, offset=0, **extra):
    return {
        "hidden_size": 64, "num_hidden_layers": LAYERS, "first_k_dense_replace": 1,
        "num_attention_heads": HEADS, "kv_lora_rank": RANK, "qk_nope_head_dim": NOPE,
        "qk_rope_head_dim": ROPE, "v_head_dim": V_DIM, "n_routed_experts": held,
        "expert_offset": offset, "num_experts_per_tok": TOP_K, "rms_norm_eps": 1e-6,
        "rope_theta": 1000000.0, "routed_scaling_factor": SCALING, **extra,
    }


def _actor(held=HELD, offset=0, vocab=VOCAB, **extra):
    return lfm2.Lfm2LM(
        vocab_size=vocab, hidden_size=64, layer_types=["latent_attention"] * LAYERS,
        num_dense_layers=1, dense_width=96, num_heads=HEADS, num_kv_heads=HEADS, head_dim=ROPE,
        kv_lora_rank=RANK, qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPE, v_head_dim=V_DIM,
        num_experts=EXPERTS, experts_held=held, expert_offset=offset, experts_per_token=TOP_K,
        expert_width=32, n_shared_experts=2, routed_scaling_factor=SCALING,
        router_epsilon=1e-20, expert_bias_scale=0.05, tie_word_embeddings=False, rms_eps=1e-6,
        **extra,
    )


def _model(held=HELD, offset=0, **extra):
    actor, critic = _actor(held, offset, **extra), olmoe.ValueHead()
    key = jax.random.PRNGKey(6)
    actor_params = actor.init(key, jnp.zeros((1, 2), jnp.int32), method="forward")
    # normal(0.02) leaves every router near uniform; scale the weights up so
    # that routing, the rotation, the latent's norm and attention all matter.
    actor_params = jax.tree.map(lambda w: w * 8.0 if w.ndim > 1 else w, actor_params)
    critic_params = jax.tree.map(lambda w: w + 0.1, critic.init(key, jnp.zeros((1, 2, 64))))
    tokens = jax.random.randint(jax.random.PRNGKey(7), (4, LENGTH), 0, VOCAB)
    return ff_lm_ppo.network_functions(actor, critic, LENGTH), actor_params, critic_params, tokens


@pytest.fixture(scope="module")
def model():
    return _model()


def _close(got, want, tol=TOL):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol)


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
        assert stats["expert_index"].shape == (LAYERS - 1, tokens.size, TOP_K)
        assert (_sets(stats["expert_index"]) == _sets(want["expert_index"])).all()
        assert int(stats["expert_count"].sum()) == (LAYERS - 1) * tokens.size * TOP_K
    else:  # what the selection bias re-routed, counted alike, and not nothing
        changed = np.any(_sets(want["expert_index"]) != _sets(want["plain_index"]), axis=-1)
        assert stats["bias_changed_sum"].tolist() == changed.sum(axis=-1).tolist()
        assert 0 < changed.sum() < changed.size


@pytest.mark.parametrize("prefix", [1, 2, 3, 7, LENGTH])
def test_decoding_through_the_latent_cache_is_the_reference_forward_of_every_prefix(model, prefix):
    """`prefix` absorbed steps from an empty latent cache give, at the last of
    them, what the reference's whole forward of the first `prefix` tokens —
    keys and values expanded a head, no cache — gives at its last position."""
    nets, actor_params, critic_params, tokens = model
    step = jax.jit(nets.step)
    carry = nets.init_cache(tokens.shape[0])
    for t in range(prefix):
        logits, hidden, carry, _ = step(actor_params, carry, tokens[:, t])
    want = reference.forward(actor_params, critic_params, tokens[:, :prefix], _spec())
    _close(logits, want["logits"][:, -1])
    _close(nets.value(critic_params, hidden), want["values"][:, -1])
    assert (np.asarray(carry.length) == prefix).all()


def _one_mixer():
    mixer = mla.LatentAttention(64, HEADS, RANK, NOPE, ROPE, V_DIM, 1000000.0, 1e-6)
    u = jax.random.normal(jax.random.PRNGKey(2), (3, LENGTH, 64))
    params = mixer.init(jax.random.PRNGKey(3), u, method="forward")
    return mixer, jax.tree.map(lambda w: w * 8.0 if w.ndim > 1 else w, params), u


@pytest.mark.parametrize("together", [True, False], ids=["one_position", "a_position_a_sequence"])
def test_absorbed_and_expanded_attention_are_equal_on_one_layer(together):
    """One layer, one set of weights: `step` (W_uk absorbed into the query,
    W_uv into the output, attention over the 32-wide latent rows) position by
    position against `forward` (keys and values expanded a head), and against
    the reference's layer."""
    mixer, params, u = _one_mixer()
    expanded = mixer.apply(params, u, method="forward")
    rows = jnp.zeros((3, LENGTH, RANK + ROPE))
    step = jax.jit(lambda p, x, state, at: mixer.apply(p, x, state, at, method="step"))
    for t in range(LENGTH):
        at = jnp.int32(t) if together else jnp.full((3,), t, jnp.int32)
        absorbed, state = step(params, u[:, t], mla.Latent(rows), at)
        rows = state.rows
        _close(absorbed, expanded[:, t])
    assert rows.shape == (3, LENGTH, RANK + ROPE)  # one row a position for all four heads
    _close(expanded, reference.latent_attention(params["params"], u, _spec()))
    gauge = get_registry().gauge("stoix_tpu_mla_decode")
    assert gauge.value({"form": "absorbed"}) == 1.0 and gauge.value({"form": "expanded"}) == 0.0


def test_the_scale_is_of_the_whole_query_width():
    """1 / sqrt(n + r) = 1 / sqrt(24) here, not 1 / sqrt(n): a reference
    layer scaled by the other differs from the program by far more than
    rounding."""
    mixer, params, u = _one_mixer()
    assert mixer.scale == pytest.approx((NOPE + ROPE) ** -0.5)
    got = mixer.apply(params, u, method="forward")
    _close(got, reference.latent_attention(params["params"], u, _spec()))
    # the reference's layer with queries pre-scaled as if the scale were 1 / sqrt(n)
    wrong = {**params["params"], "wq": params["params"]["wq"] * ((NOPE + ROPE) / NOPE) ** 0.5}
    assert float(jnp.abs(got - reference.latent_attention(wrong, u, _spec())).max()) > 1e-2


@pytest.mark.parametrize("shape", [(5, 7, 8), (2, 3, 4, 64)])
def test_the_interleaved_rotation_is_a_complex_multiplication_of_neighbouring_pairs(shape):
    """(x_2i + i x_2i+1) e^{i p theta^(-2i/dim)}, written with complex
    numbers: the program's lane-roll form and the reference's pair form."""
    theta = 1000000.0
    x = jax.random.normal(jax.random.PRNGKey(1), shape)
    positions = jnp.arange(shape[-2])
    z = np.asarray(x, np.float64)[..., 0::2] + 1j * np.asarray(x, np.float64)[..., 1::2]
    dim = shape[-1]
    angle = np.arange(shape[-2])[:, None] * theta ** (-np.arange(0, dim, 2) / dim)
    turned = z * np.exp(1j * angle)
    want = np.stack([turned.real, turned.imag], axis=-1).reshape(shape)
    _close(mla.rope_interleaved(x, positions, theta), want)
    _close(reference.rope_pairs(x, theta), want)
    assert float(np.abs(want - np.asarray(x)).max()) > 0.1  # (a rotation that turns something)


def test_only_the_rotated_part_of_query_and_key_is_rotated():
    """Position 0 is turned by nothing; at a later position the latent half
    of the cache row is the un-rotated normalised latent."""
    mixer, params, u = _one_mixer()
    _, state = mixer.apply(
        params, u[:, 5], mla.Latent(jnp.zeros((3, LENGTH, RANK + ROPE))), jnp.int32(5), method="step"
    )
    down = u[:, 5] @ params["params"]["wkv_a"]
    _close(state.rows[:, 5, :RANK], reference.rms_norm(down[:, :RANK], params["params"]["kv_norm"], 1e-6))
    assert float(jnp.abs(state.rows[:, 5, RANK:] - down[:, RANK:]).max()) > 1e-3
    assert float(jnp.abs(state.rows[:, :5]).max()) == 0.0 == float(jnp.abs(state.rows[:, 6:]).max())


@pytest.mark.parametrize("lengths", ["together", "apart", "first_position"])
def test_the_decode_kernel_is_the_plain_products_and_reads_no_unwritten_row(lengths):
    """`latent_decode_attention` (through the Pallas interpreter; blocks of
    16 positions, 8 sequences a grid step) against `attend_latent`'s plain
    branch, and both with NaNs in every row past a sequence's length: a
    program that drops its final carry finds there whatever the memory held
    (XLA:TPU allocates such a cache without filling it), and a weight of 0
    times a NaN would be a NaN."""
    from stoix_tpu.ops.pallas_attention import latent_decode_attention

    batch, length = 16, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (batch, HEADS, RANK + ROPE))
    rows = jax.random.normal(jax.random.PRNGKey(1), (batch, length, RANK + ROPE))
    last = {"together": jnp.full((batch,), 37), "apart": jnp.arange(batch) * 4,
            "first_position": jnp.zeros((batch,), jnp.int32)}[lengths]
    unwritten = jnp.arange(length)[None, :, None] > last[:, None, None]
    poisoned = jnp.where(unwritten, jnp.nan, rows)
    scale = (NOPE + ROPE) ** -0.5
    want = mla.attend_latent(q, jnp.where(unwritten, 0.0, rows), last, RANK, scale)
    assert want.shape == (batch, HEADS, RANK)
    _close(mla.attend_latent(q, poisoned, last, RANK, scale), want)
    kernel = lambda cache: latent_decode_attention(
        q, cache, last, rank=RANK, scale=scale, block=16, interpret=True
    )
    _close(kernel(poisoned), want)
    np.testing.assert_array_equal(np.asarray(kernel(poisoned)), np.asarray(kernel(rows)))


def test_the_carry_holds_latent_rows_and_the_gauge_says_how_much(model):
    nets, _, _, _ = model
    carry = nets.init_cache(3)
    assert [type(state).__name__ for state in carry.layers] == ["Latent"] * LAYERS
    assert carry.layers[0].rows.shape == (3, LENGTH, RANK + ROPE) and carry.length.shape == ()
    assert _actor().carry_bytes(3, LENGTH) == {"latent": LAYERS * 3 * LENGTH * (RANK + ROPE) * 4}
    # ... a twentieth of what the expanded keys and values of 4 heads would take
    assert HEADS * (NOPE + ROPE + V_DIM) / (RANK + ROPE) > 4


def test_a_reset_on_done_starts_a_new_sequence(model):
    """After `reset_carry` a sequence's stale rows are never read: its next
    steps equal a fresh carry's, and its neighbour goes on as if nothing had
    happened."""
    nets, actor_params, critic_params, tokens = model
    step = jax.jit(nets.step)
    carry = _actor().init_carry(2, LENGTH)  # a position a sequence: these two end apart
    for t in range(5):
        _, _, carry, _ = step(actor_params, carry, tokens[:2, t])
    carry = nets.reset_cache(carry, jnp.array([True, False]))
    assert carry.length.tolist() == [0, 5]
    assert float(jnp.abs(carry.layers[0].rows[0]).max()) > 0.0  # stale rows stay, unread
    fresh = _actor().init_carry(1, LENGTH)
    for t in range(3):
        logits, _, carry, _ = step(actor_params, carry, tokens[2:4, t])
        want, _, fresh, _ = step(actor_params, fresh, tokens[2:3, t])
        _close(logits[0], want[0])
    whole = jnp.concatenate([tokens[1:2, :5], tokens[3:4, :3]], axis=1)
    continued = reference.forward(actor_params, critic_params, whole, _spec())
    _close(logits[1], continued["logits"][0, -1])


def test_the_bias_chooses_and_the_scores_weigh_times_the_scaling_factor():
    """The family's router as a call of `olmoe.route`: sigmoid scores, the
    top-6 of score + bias, weights the scores at the chosen experts over
    (their sum + 1e-20), times 2.448 — never score + bias."""
    x = jax.random.normal(jax.random.PRNGKey(3), (40, 64))
    router = 0.3 * jax.random.normal(jax.random.PRNGKey(4), (64, EXPERTS))
    bias = jnp.zeros((EXPERTS,)).at[5].set(10.0)
    scores, weights, index = olmoe.route(
        x, router, 6, True, score="sigmoid", bias=bias, epsilon=1e-20, scale=SCALING
    )
    _, _, plain = olmoe.route(x, router, 6, True, score="sigmoid")
    _close(scores, jax.nn.sigmoid(x @ router))
    assert (index == 5).any(axis=-1).all() and not (plain == 5).any(axis=-1).all()
    chosen = jnp.take_along_axis(scores, index, axis=-1)
    _close(weights, SCALING * chosen / chosen.sum(axis=-1, keepdims=True))
    _close(weights.sum(axis=-1), jnp.full((40,), SCALING))
    assert float(weights.max()) < SCALING * 0.8  # a weight from score + bias would be near 2.448 * 10 / 13


def test_no_pair_is_dropped_when_every_token_chooses_the_same_experts():
    """A router forced to the same three experts for every token, two of them
    held here: their groups hold all N rows each and the output still equals
    the reference's dense loop, shared expert included."""
    actor_params = _model()[1]
    ffn = actor_params["params"]["layer_2"]["ffn"]
    router = jnp.zeros((64, EXPERTS))
    for expert, logit in ((1, 8.0), (3, 6.0), (20, 4.0)):
        router = router.at[0, expert].set(logit)
    x = jax.random.normal(jax.random.PRNGKey(3), (40, 64)).at[:, 0].set(1.0)
    bias = jnp.zeros((EXPERTS,))
    out, stats = olmoe.moe(
        x, router, ffn["gate"], ffn["up"], ffn["down"], TOP_K, held=(0, HELD), renormalise=True,
        score="sigmoid", bias=bias, epsilon=1e-20, scale=SCALING,
    )
    counts = stats["expert_count"].tolist()
    assert [counts[e] for e in (1, 3, 20)] == [40] * 3 and sum(counts) == 40 * TOP_K
    want, _ = reference.moe(
        {**ffn, "router": router, "expert_bias": bias}, x, _spec(shared_expert=False)
    )
    _close(out, want)


MIXER = ["mixer/wq", "mixer/wkv_a", "mixer/kv_norm", "mixer/wkv_b", "mixer/wo"]
ACTOR_LEAVES = ["embed", "final_norm", "lm_head"] + [
    f"layer_{i}/{name}"
    for i in range(LAYERS)
    for name in ["operator_norm", "ffn_norm"] + MIXER
    + (["ffn/w1", "ffn/w3", "ffn/w2"] if i < 1 else
       ["ffn/router", "ffn/expert_bias", "ffn/gate", "ffn/up", "ffn/down",
        "ffn/shared/w1", "ffn/shared/w3", "ffn/shared/w2"])
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
    _close(got[leaf], want[leaf], tol=2e-5)


# --------------------------------------------------------------------------- #
# One rank's share against the uncut layer and head
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def uncut():
    """The uncut model at the tiny size: all 32 experts, all 64 rows."""
    _, actor_params, critic_params, tokens = _model(held=EXPERTS)
    return actor_params, critic_params, tokens


def _rank_params(actor_params, rank=None, vocab=None):
    """Of the uncut tree: rank `rank` of 8's 4 experts a routed layer (with
    `rank`), and the first `vocab` rows of the embedding and columns of the
    head (with `vocab`)."""
    def cut(path, w):
        name = path[-1].key
        if name in ("gate", "up", "down") and rank is not None:
            return w[rank * HELD:(rank + 1) * HELD]
        if vocab and name == "embed":
            return w[:vocab]
        return w[:, :vocab] if vocab and name == "lm_head" else w

    return jax.tree_util.tree_map_with_path(cut, actor_params)


def test_the_eight_ranks_parts_add_up_to_the_uncut_layer_with_the_shared_expert_once(uncut):
    """The routed layer on each rank's own weights, through the program's
    module: its held experts' part and the shared expert, which every rank
    computes alike. The eight parts, with the shared expert counted ONCE, sum
    to the uncut reference's layer."""
    actor_params, _, _ = uncut
    ffn = actor_params["params"]["layer_3"]["ffn"]
    x = jax.random.normal(jax.random.PRNGKey(5), (48, 64))
    want, _ = reference.moe(ffn, x, _spec(held=EXPERTS))
    shared = reference.dense_mlp(ffn["shared"], x)
    total = jnp.zeros_like(x)
    for rank in range(RANKS):
        mine = _rank_params(actor_params, rank)["params"]["layer_3"]["ffn"]
        assert mine["gate"].shape[0] == HELD
        layer = lfm2.RoutedMLP(
            64, EXPERTS, HELD, rank * HELD, TOP_K, 32, SCALING, 0.05, 1e-20, 2 * 32
        )
        part, _ = layer.apply({"params": mine}, x)
        # ... equal to the reference's own share, given the uncut weights
        share, _ = reference.moe(ffn, x, _spec(held=HELD, offset=rank * HELD))
        _close(part, share)
        routed = part - shared  # what this rank alone adds
        assert float(jnp.abs(routed).max()) > 1e-3
        total = total + routed
    assert float(jnp.abs(shared).max()) > 1e-3
    _close(total + shared, want)
    # counted on every rank, the shared expert would be there eight times
    assert float(jnp.abs(total + RANKS * shared - want).max()) > 1e-2


def test_the_sliced_heads_logits_are_the_uncut_heads_rows(uncut):
    """Rank 0's rows of the embedding and columns of the untied head, with
    tokens drawn from the slice: the program's logits over the slice are the
    uncut model's first columns."""
    actor_params, critic_params, tokens = uncut
    rows = VOCAB // RANKS
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
# Attention kernels with values of a head size of their own
# --------------------------------------------------------------------------- #


def _plain_softmax_attention(q, k, v, causal):
    scores = np.einsum("bqhd,bkhd->bhqk", np.asarray(q, np.float64), np.asarray(k, np.float64))
    scores = scores / np.sqrt(q.shape[-1])
    if causal:
        scores = np.where(np.tril(np.ones(scores.shape[-2:], bool)), scores, -np.inf)
    weights = np.exp(scores - scores.max(axis=-1, keepdims=True))
    weights = weights / weights.sum(axis=-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", weights, np.asarray(v, np.float64))


def _qkv(d, d_v, length=40):
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    shape = lambda width: (2, length, 3, width)
    return tuple(jax.random.normal(k, shape(w)) for k, w in zip(keys, (d, d, d_v)))


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("kernel", ["flash_attention", "full_attention"])
def test_attention_takes_values_of_a_head_size_of_their_own(kernel, causal):
    """Queries and keys 24 wide, values 16: the scale is 1 / sqrt(24), the
    output 16 wide; against a plain float64 softmax."""
    q, k, v = _qkv(24, 16)
    attend = (
        (lambda *a: flash_attention(*a, causal=causal, block_q=16, block_k=16, interpret=True))
        if kernel == "flash_attention" else (lambda *a: full_attention(*a, causal=causal))
    )
    got = attend(q, k, v)
    assert got.shape == (2, 40, 3, 16)
    _close(got, _plain_softmax_attention(q, k, v, causal))


def test_the_flash_kernels_gradient_with_narrower_values_is_the_plain_paths():
    q, k, v = _qkv(24, 16, length=32)
    loss = lambda attend: lambda *a: jnp.sum(attend(*a) ** 2)
    flash = lambda *a: flash_attention(*a, causal=True, block_q=16, block_k=16, interpret=True)
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda *a: full_attention(*a, causal=True)), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        _close(g, w)


@pytest.mark.parametrize("kernel", ["flash_attention", "full_attention"])
def test_attention_at_equal_head_sizes_is_what_it_was(kernel):
    """The path every earlier caller takes: padding values up to the queries'
    width and cutting the result back is the same attention."""
    q, k, v = _qkv(16, 16)
    attend = (
        (lambda *a: flash_attention(*a, causal=True, block_q=16, block_k=16, interpret=True))
        if kernel == "flash_attention" else (lambda *a: full_attention(*a, causal=True))
    )
    _close(attend(q, k, v), _plain_softmax_attention(q, k, v, True))
    narrow = attend(q, k, v[..., :8])
    np.testing.assert_array_equal(np.asarray(narrow), np.asarray(attend(q, k, v)[..., :8]))


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
@pytest.mark.parametrize("scope", LATENT_SCOPES + ("dense_mlp",) + BLOCK_SCOPES)
def test_the_scopes_are_in_both_phases_of_the_learner_and_in_the_evaluator(
    program_scopes, phase, scope
):
    assert SCOPES[scope] in program_scopes[phase]


def test_learner_setup_publishes_the_carry_the_write_and_the_decode_form(program_scopes):
    by = lambda gauge, label: {
        dict(labels)[label]: value for labels, value in gauge.labels_and_values()
    }
    registry = get_registry()
    per_shard = 32 // 8  # sequences a shard of the 8 virtual devices
    assert by(registry.gauge("stoix_tpu_lm_carry_bytes"), "kind") == {
        "latent": LAYERS * per_shard * LENGTH * (RANK + ROPE) * 4
    }
    assert by(registry.gauge("stoix_tpu_lm_cache_write"), "form") == {"slice": 1.0, "scatter": 0.0}
    assert by(registry.gauge("stoix_tpu_mla_decode"), "form") == {"absorbed": 1.0, "expanded": 0.0}


def _logged_run(extra):
    """`run_experiment` (the path `main()` takes) -> (final return, what it
    logged as TRAIN and ACT events)."""
    from stoix_tpu.utils.logger import LogEvent, StoixLogger

    logged = {LogEvent.TRAIN: [], LogEvent.ACT: []}
    original = StoixLogger.log

    def log(self, metrics, t, t_eval, event):
        if event in logged:
            logged[event].append(metrics)
        return original(self, metrics, t, t_eval, event)

    StoixLogger.log = log
    try:
        final = ff_lm_ppo.run_experiment(_config(extra))
    finally:
        StoixLogger.log = original
    return final, logged[LogEvent.TRAIN], logged[LogEvent.ACT]


def test_a_short_run_learns_the_token_task(devices):
    """The greedy return of the trained policy is far above the untrained
    0.5; every window logs top-3 routed pairs a token in the rollout and in
    the update: nothing dropped."""
    final, trains, acts = _logged_run([
        "arch.num_updates=12", "arch.num_evaluation=2", "arch.total_num_envs=64",
        "system.actor_lr=3e-3", "system.critic_lr=3e-3", "arch.evaluation_greedy=True",
    ])
    assert final > 0.75, final
    assert len(trains) == 2
    for train in trains:
        assert float(train["routed_pairs_per_token"]) == TOP_K
        assert float(train["rollout_routed_pairs_per_token"]) == TOP_K
        assert float(train["dropped_pairs"]) == 0.0
    for act in acts:  # one value a finished episode: 6 updates x 64 sequences
        assert {"rollout_action", "rollout_log_prob", "rollout_value"} <= set(act)
        assert np.asarray(act["rollout_log_prob"]).shape == (6 * 64,)


def test_a_run_logs_the_held_shares_counters(devices):
    """At the configuration's own learning rate (a router trained at 3e-3 on
    32-token minibatches sends a whole minibatch past the 4 held experts now
    and then): the pairs held here in rollout and update, the held experts'
    load, and what the selection bias re-routed."""
    _, trains, _ = _logged_run(["arch.num_updates=2", "arch.num_evaluation=1"])
    (train,) = trains
    uniform = TOP_K * HELD / EXPERTS
    assert 0.5 * uniform < float(train["held_pairs_per_token"]) < 2 * uniform
    assert 0.5 * uniform < float(train["rollout_held_pairs_per_token"]) < 2 * uniform
    assert float(train["expert_load_max_over_mean"]) >= 1.0
    assert 0.0 < float(train["router_bias_changed_share"]) < 1.0
    assert float(train["dropped_pairs"]) == 0.0


def test_the_benchmark_keeps_a_copy_of_the_reference(model):
    """benchmarks/references/ppo_kanana2.py carries its own copy of the plain
    forward and loss (it may import nothing of the program): they agree
    exactly."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.harness import loader

    copy = loader.load_reference("ppo_kanana2")
    _, actor_params, critic_params, tokens = model
    want = reference.forward(actor_params, critic_params, tokens, _spec())
    got = copy.forward(actor_params, critic_params, tokens, _spec())
    for key in ("logits", "values", "expert_index", "plain_index"):
        np.testing.assert_array_equal(np.asarray(got[key]), np.asarray(want[key]))
