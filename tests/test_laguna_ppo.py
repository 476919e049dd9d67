"""The Laguna-XS.2 window-and-full attention token policy (networks/lfm2.py's
`GroupedQueryAttention` in two kinds — `window=`, a head count a layer, a
rotation a layer kind, a gate a head — over ops/pallas_attention.py's banded
kernel pair, the ring `WindowKV` beside the growing `KV` in one carry,
systems/ppo/anakin/ff_lm_ppo.py with `network=laguna_xs2_moe`) against its
plain reference (reference/laguna.py), at a tiny preset on the CPU: hidden 64,
[full + dense, window + routed x 3, full + routed], 6 | 8 query heads on 2
key/value heads of 16, a window of 6 in sequences of 20 (three windows and a
remainder: the ring wraps three times), the full layers rotated over half a
head with YaRN, 32 experts top-3 of width 32 of which a rank holds 4 (8 ranks)
beside one shared expert, vocabulary 64. Tolerance 1e-5 throughout: both sides
are float32 on the CPU and differ only in summation order."""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stoix_tpu import envs
from stoix_tpu.base_types import ActorCriticParams
from stoix_tpu.networks import lfm2, olmoe
from stoix_tpu.observability import BLOCK_SCOPES, SCOPES, WINDOW_SCOPES, get_registry
from stoix_tpu.ops import pallas_attention
from stoix_tpu.ops.ring_attention import full_attention
from stoix_tpu.reference import laguna as reference
from stoix_tpu.systems.ppo.anakin import ff_lm_ppo
from stoix_tpu.utils import config as config_lib

TOL = 1e-5
VOCAB, LENGTH, WINDOW = 64, 20, 6
KINDS = ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
FEED_FORWARDS = ["dense"] + ["sparse"] * 4
HEADS, KV_HEADS, HEAD_DIM = [6, 8, 8, 8, 6], 2, 16
EXPERTS, HELD, TOP_K, RANKS, SCALING = 32, 4, 3, 8, 2.5
ATTENTION_FACTOR = 1.4158883083359672
ROPE = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 16, "beta_slow": 1, "beta_fast": 4,
        "attention_factor": ATTENTION_FACTOR, "partial_rotary_factor": 0.5,
    },
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
}
TINY = [
    "network=laguna_xs2_moe",
    "network.actor_network.hidden_size=64", "network.actor_network.dense_width=96",
    "network.actor_network.num_heads=6", f"network.actor_network.num_heads_per_layer={HEADS}",
    f"network.actor_network.num_kv_heads={KV_HEADS}", f"network.actor_network.head_dim={HEAD_DIM}",
    f"network.actor_network.sliding_window={WINDOW}",
    f"network.actor_network.num_experts={EXPERTS}", f"network.actor_network.experts_held={HELD}",
    f"network.actor_network.experts_per_token={TOP_K}", "network.actor_network.expert_width=32",
    "network.actor_network.rope_parameters.full_attention.original_max_position_embeddings=16",
    "network.actor_network.rope_parameters.full_attention.beta_fast=4",
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
        "hidden_size": 64, "num_hidden_layers": len(KINDS), "layer_types": KINDS,
        "mlp_layer_types": FEED_FORWARDS, "num_attention_heads_per_layer": HEADS,
        "num_key_value_heads": KV_HEADS, "head_dim": HEAD_DIM, "sliding_window": WINDOW,
        "rope_parameters": ROPE, "rms_norm_eps": 1e-6, "num_experts": held, "expert_offset": offset,
        "num_experts_per_tok": TOP_K, "moe_routed_scaling_factor": SCALING, **extra,
    }


def _actor(held=HELD, offset=0, vocab=VOCAB, **extra):
    keys = dict(
        vocab_size=vocab, hidden_size=64, layer_types=KINDS, num_dense_layers=1, dense_width=96,
        num_heads=6, num_heads_per_layer=HEADS, num_kv_heads=KV_HEADS, head_dim=HEAD_DIM,
        sliding_window=WINDOW, attention_gate=True, rope_parameters=ROPE, num_experts=EXPERTS,
        experts_held=held, expert_offset=offset, experts_per_token=TOP_K, expert_width=32,
        n_shared_experts=1, routed_scaling_factor=SCALING, router_epsilon=1e-20,
        expert_bias_scale=0.0, tie_word_embeddings=False, rms_eps=1e-6,
    )
    return lfm2.Lfm2LM(**{**keys, **extra})


def _model(held=HELD, offset=0, **extra):
    actor, critic = _actor(held, offset, **extra), olmoe.ValueHead()
    key = jax.random.PRNGKey(6)
    actor_params = actor.init(key, jnp.zeros((1, 2), jnp.int32), method="forward")
    # normal(0.02) leaves every router near uniform, every softmax flat and
    # every gate at a half; scale the weights up so that routing, the band,
    # the rotations and the gates all matter.
    actor_params = jax.tree.map(lambda w: w * 8.0 if w.ndim > 1 else w, actor_params)
    critic_params = jax.tree.map(lambda w: w + 0.1, critic.init(key, jnp.zeros((1, 2, 64))))
    tokens = jax.random.randint(jax.random.PRNGKey(7), (4, LENGTH), 0, VOCAB)
    return ff_lm_ppo.network_functions(actor, critic, LENGTH), actor_params, critic_params, tokens


@pytest.fixture(scope="module")
def model():
    return _model()


def _reference_forward(actor_params, critic_params, tokens, spec):
    return jax.jit(lambda a, c, t: reference.forward(a, c, t, spec))(actor_params, critic_params, tokens)


def _close(got, want, tol=TOL):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol)


def _sets(index):
    return np.sort(np.asarray(index), axis=-1)


@pytest.mark.parametrize("output", ["logits", "values", "expert_index"])
def test_forward_matches_the_plain_reference(model, output):
    """Both layer kinds at different head counts, under the causal and the
    banded mask, against the reference's explicit [T, T] softmax."""
    nets, actor_params, critic_params, tokens = model
    want = _reference_forward(actor_params, critic_params, tokens, _spec())
    logits, hidden, stats = jax.jit(nets.forward)(actor_params, tokens)
    if output == "logits":
        _close(logits, want["logits"])
    elif output == "values":
        _close(nets.value(critic_params, hidden), want["values"])
    else:  # the chosen expert SETS are identical, layer by layer
        assert stats["expert_index"].shape == (len(KINDS) - 1, tokens.size, TOP_K)
        assert (_sets(stats["expert_index"]) == _sets(want["expert_index"])).all()
        assert int(stats["expert_count"].sum()) == (len(KINDS) - 1) * tokens.size * TOP_K


def test_the_window_matters_at_this_size(model):
    """The reference read causally everywhere is another result: what the
    band changes is far above the tolerance the tests hold the program to."""
    _, actor_params, critic_params, tokens = model
    want = _reference_forward(actor_params, critic_params, tokens, _spec())
    causal = _reference_forward(actor_params, critic_params, tokens, _spec(sliding_window=None))
    _close(causal["logits"][:, :WINDOW], want["logits"][:, :WINDOW])  # (a prefix inside one window)
    assert float(jnp.abs(causal["logits"] - want["logits"]).max()) > 0.1


@pytest.fixture(scope="module", params=[True, False], ids=["one_position", "a_position_a_sequence"])
def decoded(request, model):
    """LENGTH steps from empty rings and caches, with `length` together or
    apart: what every step gave."""
    nets, actor_params, critic_params, tokens = model
    carry = _actor().init_carry(tokens.shape[0], LENGTH, together=request.param)

    def one(carry, token):
        logits, hidden, carry, _ = nets.step(actor_params, carry, token)
        return carry, (logits, nets.value(critic_params, hidden), carry.length)

    _, (logits, values, lengths) = jax.jit(lambda c: jax.lax.scan(one, c, tokens.T))(carry)
    return logits, values, lengths, request.param


@pytest.mark.parametrize(
    "prefix", [1, 2, WINDOW - 1, WINDOW, WINDOW + 1, 2 * WINDOW - 1, 2 * WINDOW + 1, LENGTH]
)
def test_decoding_through_the_ring_and_the_cache_is_the_reference_forward_of_every_prefix(
    model, decoded, prefix
):
    """`prefix` steps give, at the last of them, what the reference's whole
    forward of the first `prefix` tokens — no cache, no ring — gives at its
    last position: before the ring is full, as it fills, and after it has
    wrapped once, twice and three times."""
    _, actor_params, critic_params, tokens = model
    logits, values, lengths, together = decoded
    want = _reference_forward(actor_params, critic_params, tokens[:, :prefix], _spec())
    _close(logits[prefix - 1], want["logits"][:, -1])
    _close(values[prefix - 1], want["values"][:, -1])
    assert lengths[prefix - 1].shape == (() if together else (tokens.shape[0],))
    assert (np.asarray(lengths[prefix - 1]) == prefix).all()


# --------------------------------------------------------------------------- #
# The banded mask in the plain attention and in the flash kernel pair
# --------------------------------------------------------------------------- #

_TILE, _SEQUENCE = 16, 50  # (a length that is no whole number of tiles)


def _banded_softmax(q, k, v, window):
    length = q.shape[1]
    at = jnp.arange(length)
    seen = (at[:, None] >= at[None, :]) & (at[:, None] - at[None, :] < window)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def _qkv(length=_SEQUENCE, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(key, (2, length, 4, 32)) for key in keys)


def _kernel(q, k, v, window):
    return pallas_attention.flash_attention(
        q, k, v, causal=True, block_q=_TILE, block_k=_TILE, interpret=True, window=window
    )


@pytest.mark.parametrize(
    "window", [5, _TILE, 23, 2 * _TILE], ids=["narrower", "a_tile", "wider", "two_tiles"]
)
@pytest.mark.parametrize("what", ["plain", "kernel", "gradient"])
def test_the_banded_attention_is_an_explicit_banded_softmax(what, window):
    """`full_attention(window=)`, the flash kernel (Pallas interpreter) and
    the kernel pair's gradient against an explicit banded softmax, with the
    window narrower than, equal to and wider than a tile. At a window of
    whole tiles the first tile a query tile visits lies wholly before its
    LAST query's band (query 47 of tile 32..47 at W = 32 sees keys 16..47,
    the walk starts at tile 0): that query's sums are garbage until its first
    real key scales them by exp(-huge) = 0."""
    q, k, v = _qkv()
    want = _banded_softmax(q, k, v, window)
    if what == "plain":
        _close(full_attention(q, k, v, causal=True, window=window), want)
    elif what == "kernel":
        _close(_kernel(q, k, v, window), want)
    else:
        loss = lambda fn: jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2))(q, k, v)
        for got, wanted in zip(loss(lambda *a: _kernel(*a, window)), loss(lambda *a: _banded_softmax(*a, window))):
            _close(got, wanted, tol=2e-5)


@pytest.mark.parametrize("window", [None, _SEQUENCE, 3 * _SEQUENCE], ids=["none", "the_length", "longer"])
@pytest.mark.parametrize("what", ["plain", "kernel"])
def test_no_window_and_a_window_that_holds_the_sequence_are_the_causal_result_to_the_bit(what, window):
    q, k, v = _qkv()
    if what == "plain":
        got, want = full_attention(q, k, v, causal=True, window=window), full_attention(q, k, v, causal=True)
    else:
        got = _kernel(q, k, v, window)
        want = pallas_attention.flash_attention(
            q, k, v, causal=True, block_q=_TILE, block_k=_TILE, interpret=True
        )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_a_window_needs_the_causal_mask():
    q, k, v = _qkv(8)
    with pytest.raises(ValueError, match="causal"):
        pallas_attention.flash_attention(q, k, v, window=4, interpret=True)
    with pytest.raises(ValueError, match="causal"):
        full_attention(q, k, v, window=4)


@pytest.mark.parametrize("tile", [128, 256, 512])
def test_the_banded_walk_visits_the_bands_tiles_alone(tile):
    """Key tiles a banded walk over 1,024 positions visits at a window of
    512, by the tile: `_walk`'s own bounds, counted. A tile as wide as the
    band visits what the causal walk visits; the walk takes it all the same,
    as the causal walk does: at two windows' length big tiles win by more
    than the band saves (PERF.md section 6, PR 44)."""
    length, window = 1024, 512
    visited = causal = 0
    for first in range(0, length, tile):
        start = max(first - window + 1, 0) // tile
        last = min((first + tile + tile - 1) // tile, length // tile)
        visited, causal = visited + (last - start), causal + last
    assert causal == {128: 36, 256: 10, 512: 3}[tile]
    assert visited == {128: 30, 256: 9, 512: 3}[tile]
    assert pallas_attention._tile(128, length) == 512  # both walks' tile


# --------------------------------------------------------------------------- #
# The two rotations
# --------------------------------------------------------------------------- #

PUBLISHED = olmoe.Yarn(64.0, 4096, 64.0, 1.0, ATTENTION_FACTOR)


def test_the_yarn_frequencies_are_the_closed_form():
    """The published full layers: r = 64, theta 500,000, factor 64 over 4,096
    positions, beta 64 | 1: low 5, high 16; frequencies below `low` are the
    plain ones, from `high` on the plain ones over 64, blended between."""
    low, high, ramp = olmoe.yarn_ramp(64, 500000.0, PUBLISHED)
    turns = lambda beta: 64 * math.log(4096 / (2 * math.pi * beta)) / (2 * math.log(500000.0))
    assert (low, high) == (5, 16) == (math.floor(turns(64)), math.ceil(turns(1)))
    assert ramp.shape == (32,) and (ramp[:6] == 0.0).all() and (ramp[16:] == 1.0).all()
    np.testing.assert_allclose(ramp[6:16], (np.arange(6, 16) - 5) / 11.0, rtol=1e-6)
    positions = jnp.arange(7)
    angles = olmoe.rope_angles(positions, 64, 500000.0, PUBLISHED)
    plain = 500000.0 ** (-np.arange(0, 64, 2) / 64.0)
    want = plain / 64.0 * ramp + plain * (1.0 - ramp)
    np.testing.assert_allclose(np.asarray(angles[1, :32]), want, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(angles[1, 32:]), want, rtol=1e-6)  # the half's twice
    np.testing.assert_allclose(np.asarray(angles[1, :5]), plain[:5], rtol=1e-6)  # both ends
    np.testing.assert_allclose(np.asarray(angles[1, 16:32]), plain[16:] / 64.0, rtol=1e-6)
    # ... the same numbers the reference computes from the configuration's block
    inv_freq, factor = reference.inverse_frequencies(64, {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1, "beta_fast": 64,
        "attention_factor": ATTENTION_FACTOR,
    })
    np.testing.assert_allclose(np.asarray(inv_freq), want, rtol=1e-6)
    assert factor == ATTENTION_FACTOR == pytest.approx(0.1 * math.log(64) + 1)


def test_the_partial_rotation_turns_the_first_half_scaled_and_leaves_the_rest():
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 5, 4, 128))
    positions = jnp.broadcast_to(jnp.arange(5), (3, 5))
    got = olmoe.rope(x, positions, 500000.0, 64, PUBLISHED)
    np.testing.assert_array_equal(np.asarray(got[..., 64:]), np.asarray(x[..., 64:]))
    # position 0: no turn, but cos is times the attention factor
    _close(got[:, 0, :, :64], x[:, 0, :, :64] * ATTENTION_FACTOR)
    # a turn keeps a pair's length, up to that factor: dims (i, i + 32) of the rotated part
    norm = lambda t: jnp.sqrt(t[..., :32] ** 2 + t[..., 32:64] ** 2)
    _close(norm(got), norm(x) * ATTENTION_FACTOR, tol=1e-4)
    # against the reference's rotation, laid [N, H, T, d]
    stated = {**ROPE["full_attention"], "original_max_position_embeddings": 4096, "beta_fast": 64}
    want = reference.rotate(jnp.swapaxes(x, 1, 2), 128, stated)
    _close(got, jnp.swapaxes(want, 1, 2))


def test_the_default_rotation_is_the_one_it_was():
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 3, 16))
    positions = jnp.broadcast_to(jnp.arange(5), (2, 5))
    half = 8
    inv_freq = 1.0 / (10000.0 ** (jnp.arange(0, 16, 2, dtype=jnp.float32) / 16))
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    emb = jnp.concatenate([angles, angles], axis=-1)[..., None, :]
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    want = x * jnp.cos(emb) + rotated * jnp.sin(emb)
    np.testing.assert_array_equal(np.asarray(olmoe.rope(x, positions, 10000.0)), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(olmoe.rope(x, positions, 10000.0, rotary_dim=16)), np.asarray(want)
    )


# --------------------------------------------------------------------------- #
# The gate, and the ring as a carry
# --------------------------------------------------------------------------- #


def _one_mixer(gate, window=None):
    mixer = lfm2.GroupedQueryAttention(64, 8, 2, 16, 10000.0, 1e-6, window=window, gate=gate)
    u = jax.random.normal(jax.random.PRNGKey(3), (2, 9, 64))
    params = mixer.init(jax.random.PRNGKey(4), u, method="forward")
    return mixer, jax.tree.map(lambda w: w * 8.0 if w.ndim > 1 else w, params), u


def test_the_gate_off_is_grouped_query_attention_as_it_was():
    mixer, params, u = _one_mixer(gate=False)
    assert sorted(params["params"]) == ["k_norm", "q_norm", "wk", "wo", "wq", "wv"]
    assert mixer.trace_scope == "attention" and mixer.attend_scope == "attention_scores"
    tree = params["params"]
    positions = jnp.broadcast_to(jnp.arange(9), (2, 9))
    heads = lambda t, n: t.reshape(2, 9, n, 16)
    q = olmoe.rope(olmoe.rms_norm(heads(u @ tree["wq"], 8), tree["q_norm"], 1e-6), positions, 10000.0)
    k = olmoe.rope(olmoe.rms_norm(heads(u @ tree["wk"], 2), tree["k_norm"], 1e-6), positions, 10000.0)
    v = heads(u @ tree["wv"], 2)
    attended = full_attention(q, jnp.repeat(k, 4, axis=2), jnp.repeat(v, 4, axis=2), causal=True)
    want = attended.reshape(2, 9, -1) @ tree["wo"]
    np.testing.assert_array_equal(np.asarray(mixer.apply(params, u, method="forward")), np.asarray(want))


@pytest.mark.parametrize("window", [None, 4], ids=["full", "window"])
def test_the_gate_multiplies_each_heads_result_in_both_entry_points(window):
    mixer, params, u = _one_mixer(gate=True, window=window)
    assert params["params"]["wg"].shape == (64, 8)
    assert mixer.trace_scope == ("window_mixer" if window else "attention")
    plain = lfm2.GroupedQueryAttention(64, 8, 2, 16, 10000.0, 1e-6, window=window)
    ungated = {"params": {k: v for k, v in params["params"].items() if k != "wg"}}
    tree = params["params"]
    # what the gate does, from outside: each head's own part of the ungated result (W_o's
    # other rows at zero), times that head's gate
    gates = jax.nn.sigmoid(u @ tree["wg"])  # [2, 9, 8]
    rows_of = lambda h: (jnp.arange(128) // 16 == h)[:, None]
    part = lambda h: plain.apply(
        {"params": {**ungated["params"], "wo": jnp.where(rows_of(h), tree["wo"], 0.0)}}, u,
        method="forward",
    )
    want = sum(gates[..., h:h + 1] * part(h) for h in range(8))
    got = mixer.apply(params, u, method="forward")
    _close(got, want)
    assert float(jnp.abs(got - plain.apply(ungated, u, method="forward")).max()) > 1e-2
    # step by step through the cache (or the ring) it is the same layer
    rows = window or 9
    state = (lfm2.WindowKV if window else lfm2.KV)(*(jnp.zeros((rows, 2, 2, 16)),) * 2)
    for t in range(9):
        out, state = mixer.apply(params, u[:, t], state, jnp.int32(t), method="step")
        _close(out, got[:, t])


def _caches(key, rows, batch, kv_heads):
    return tuple(jax.random.normal(k, (rows, batch, kv_heads, 128)) for k in jax.random.split(key))


# (rows, sequences, key/value heads, group): two heads at eight and at one sequence a grid step, and
# the cell's layout — eight heads, a sublane tile, at both its groups — with two grid rows turning.
@pytest.mark.parametrize("rows,batch,kv_heads,group", [(256, 8, 2, 6), (128, 3, 2, 8), (256, 16, 8, 6), (256, 16, 8, 8)])
@pytest.mark.parametrize("last", ["apart", "full", "one_row"])
def test_the_decode_kernel_is_the_plain_attend(rows, batch, kv_heads, group, last):
    """`gqa_decode_attention` (Pallas interpreter) against `_attend_cache`:
    sequences at different live rows, every row live (a wrapped ring), one row
    live; rows past the live ones may hold anything."""
    keys = jax.random.split(jax.random.PRNGKey(8))
    q = jax.random.normal(keys[0], (batch, kv_heads, group, 128))
    cache_k, cache_v = _caches(keys[1], rows, batch, kv_heads)
    lasts = {
        "apart": jnp.arange(batch) * 37 % rows, "full": jnp.full((batch,), rows - 1),
        "one_row": jnp.zeros((batch,), jnp.int32),
    }[last]
    want = olmoe._attend_cache(q, cache_k, cache_v, lasts)
    dead = jnp.arange(rows)[:, None, None, None] > lasts[None, :, None, None]
    poisoned = lambda cache: jnp.where(dead, jnp.nan, cache)
    got = pallas_attention.gqa_decode_attention(
        q, poisoned(cache_k), poisoned(cache_v), lasts, interpret=True
    )
    _close(got, want)


@pytest.mark.parametrize("others", ["huge", "other_rows"])
@pytest.mark.parametrize("group", [6, 8])
def test_a_heads_result_is_of_its_own_key_value_head_alone(group, others):
    """The kernel multiplies a sequence's queries with the rows of ALL its
    key/value heads at once and masks what is not a head's own: whatever the
    other heads' LIVE rows hold — 1e30, which a weight of exactly 0 must meet,
    or other rows — a head's result is bit for bit what it was."""
    rows, batch, kv_heads, head = 256, 8, 8, 3
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(keys[0], (batch, kv_heads, group, 128))
    cache_k, cache_v = _caches(keys[1], rows, batch, kv_heads)
    lasts = jnp.arange(batch) * 37 % rows
    attend = lambda k, v: pallas_attention.gqa_decode_attention(q, k, v, lasts, interpret=True)
    before = attend(cache_k, cache_v)
    other_k, other_v = _caches(keys[2], rows, batch, kv_heads) if others == "other_rows" else (1e30, -1e30)
    own = (jnp.arange(kv_heads) == head)[None, None, :, None]
    after = attend(jnp.where(own, cache_k, other_k), jnp.where(own, cache_v, other_v))
    np.testing.assert_array_equal(np.asarray(after[:, head]), np.asarray(before[:, head]))
    _close(before, olmoe._attend_cache(q, cache_k, cache_v, lasts))
    assert bool(jnp.isfinite(after).all())


def test_the_decode_takes_the_kernel_on_the_chip_for_heads_of_whole_lanes(monkeypatch):
    """`attend_rows`: the Pallas kernel on a TPU for heads of 128 and rows in
    whole blocks; `_attend_cache` off the chip, at the LFM2 cell's heads of
    64, and for rows that are no whole blocks."""
    taken = []
    monkeypatch.setattr(lfm2, "gqa_decode_attention", lambda q, k, v, last: taken.append("kernel") or q)
    monkeypatch.setattr(lfm2, "_attend_cache", lambda q, k, v, last: taken.append("plain") or q)
    call = lambda group, head_dim, rows: lfm2.attend_rows(
        jnp.zeros((2, 8, group, head_dim)), jnp.zeros((rows, 2, 8, head_dim)),
        jnp.zeros((rows, 2, 8, head_dim)), jnp.int32(3),
    )
    call(8, 128, 512)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    call(8, 128, 512), call(6, 128, 1024), call(4, 64, 512), call(8, 128, 20)
    assert taken == ["plain", "kernel", "kernel", "plain", "plain"]


def test_the_carry_holds_rings_beside_caches_and_says_how_much(model):
    nets, _, _, _ = model
    carry = nets.init_cache(3)
    assert [type(state).__name__ for state in carry.layers] == ["KV"] + ["WindowKV"] * 3 + ["KV"]
    assert carry.layers[0].k.shape == (LENGTH, 3, KV_HEADS, HEAD_DIM)
    assert carry.layers[1].k.shape == (WINDOW, 3, KV_HEADS, HEAD_DIM) and carry.length.shape == ()
    row = 3 * KV_HEADS * HEAD_DIM * 4 * 2  # keys and values
    assert _actor().carry_bytes(3, LENGTH) == {"kv": 2 * LENGTH * row, "window_kv": 3 * WINDOW * row}
    # ... and a ring does not grow with the sequence
    assert _actor().carry_bytes(3, 8 * LENGTH)["window_kv"] == 3 * WINDOW * row
    # the published widths at the cell's sizes: 512 + 384 MiB, not 1,280
    published = _actor(
        hidden_size=2048, num_heads=48, num_heads_per_layer=[48, 64, 64, 64, 48], num_kv_heads=8,
        head_dim=128, sliding_window=512,
    ).carry_bytes(32, 1024)
    assert published == {"kv": 512 * 2**20, "window_kv": 384 * 2**20}


def test_a_reset_leaves_a_ring_alone_and_a_new_episode_reads_none_of_the_old_rows(model):
    """`reset_carry` moves `length` back and touches no ring: the rows a new
    episode's first steps attend are the rows it has itself written, so a
    ring full of a predecessor's rows (or of anything) changes nothing."""
    nets, actor_params, critic_params, tokens = model
    step = jax.jit(nets.step)
    carry = _actor().init_carry(2, LENGTH)  # a position a sequence: these two end apart
    for t in range(WINDOW + 2):  # past the wrap
        _, _, carry, _ = step(actor_params, carry, tokens[:2, t])
    before = carry
    carry = nets.reset_cache(carry, jnp.array([True, False]))
    assert carry.length.tolist() == [0, WINDOW + 2]
    for was, now in zip(before.layers, carry.layers):
        np.testing.assert_array_equal(np.asarray(now.k), np.asarray(was.k))
        np.testing.assert_array_equal(np.asarray(now.v), np.asarray(was.v))
    assert float(jnp.abs(carry.layers[1].k[:, 0]).min()) > 0.0  # the old rows are all still there
    # ... and poisoned besides: whatever a ring holds beyond the live rows is not read
    poisoned = carry._replace(layers=tuple(
        type(state)(state.k.at[:, 0].set(1e9), state.v.at[:, 0].set(-1e9)) for state in carry.layers
    ))
    fresh = _actor().init_carry(1, LENGTH)
    for t in range(WINDOW + 2):
        logits, _, poisoned, _ = step(actor_params, poisoned, tokens[2:4, t])
        want, _, fresh, _ = step(actor_params, fresh, tokens[2:3, t])
        _close(logits[0], want[0])
    whole = jnp.concatenate([tokens[1:2, :WINDOW + 2], tokens[3:4, :WINDOW + 2]], axis=1)
    continued = _reference_forward(actor_params, critic_params, whole, _spec())
    _close(logits[1], continued["logits"][0, -1])  # its neighbour went on as if nothing had happened


def test_a_ring_written_at_the_position_is_another_result(model):
    """What `length % W` is for: written at `length`, the row of a position
    beyond the ring is dropped (an update out of bounds is clipped to the
    last row), and the steps past the first window read other keys."""
    nets, actor_params, _, tokens = model
    mixer = lfm2.GroupedQueryAttention
    real = mixer.step

    def unwrapped(self, u, state, length):
        if not self.window:
            return real(self, u, state, length)
        out, state = real(self, u, state, jnp.minimum(length, self.window - 1))
        return out, state

    def run(step_fn):
        mixer.step = step_fn
        try:
            carry = _actor().init_carry(1, LENGTH, together=True)
            outs = []
            for t in range(WINDOW + 3):
                logits, _, carry, _ = nets.step(actor_params, carry, tokens[:1, t])
                outs.append(logits)
            return jnp.stack(outs)
        finally:
            mixer.step = real

    want, got = run(real), run(unwrapped)
    _close(got[:WINDOW], want[:WINDOW])
    assert float(jnp.abs(got[WINDOW:] - want[WINDOW:]).max()) > 1e-2


# --------------------------------------------------------------------------- #
# Loss and gradients
# --------------------------------------------------------------------------- #

_MIXER = ["wq", "wk", "wv", "wo", "wg", "q_norm", "k_norm"]
_ROUTED = ["router", "expert_bias", "gate", "up", "down", "shared/w1", "shared/w3", "shared/w2"]
ACTOR_LEAVES = ["embed", "final_norm", "lm_head"] + [
    f"layer_{i}/{name}"
    for i in range(len(KINDS))
    for name in ["operator_norm", "ffn_norm"] + [f"mixer/{m}" for m in _MIXER]
    + [f"ffn/{f}" for f in (["w1", "w3", "w2"] if i == 0 else _ROUTED)]
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
    want_total, want_parts, want_grads = jax.jit(
        lambda params, batch: reference.ppo_loss_and_grads(params, batch, _spec(), HYPER)
    )((actor_params, critic_params), ref_batch)
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


def test_the_loss_counts_no_dropped_pair_and_no_bias(loss_and_grads):
    got, _, _, _ = loss_and_grads
    assert float(got["dropped_pairs"]) == 0.0 and float(got["routed_pairs_per_token"]) == TOP_K
    assert float(got["router_bias_changed_share"]) == 0.0  # no selection bias is published


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_leaf_matches_jax_grad_of_the_reference_loss(loss_and_grads, leaf):
    """The banded and the causal attention's backward pass through five
    layers, leaf by leaf, against `jax.grad` of the reference's explicit
    masked softmax."""
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


def test_the_ranks_parts_add_up_to_the_uncut_layer_with_the_shared_expert_once(uncut):
    """The routed layer on each rank's own weights, through the program's
    module: its held experts' part and the shared expert, which every rank
    computes alike. The eight parts (32 in the deployment, each of 8 experts
    of 256), with the shared expert counted ONCE, sum to the uncut
    reference's layer."""
    actor_params, _, _ = uncut
    ffn = actor_params["params"]["layer_3"]["ffn"]
    x = jax.random.normal(jax.random.PRNGKey(5), (48, 64))
    want, _ = reference.moe(ffn, x, _spec(held=EXPERTS))
    shared = reference.dense_mlp(ffn["shared"], x)
    total = jnp.zeros_like(x)
    for rank in range(RANKS):
        mine = _rank_params(actor_params, rank)["params"]["layer_3"]["ffn"]
        assert mine["gate"].shape[0] == HELD
        layer = lfm2.RoutedMLP(64, EXPERTS, HELD, rank * HELD, TOP_K, 32, SCALING, 0.0, 1e-20, 32)
        part, _ = layer.apply({"params": mine}, x)
        # ... equal to the reference's own share, given the uncut weights
        share, _ = reference.moe(ffn, x, _spec(held=HELD, offset=rank * HELD))
        _close(part, share)
        total = total + (part - shared)  # what this rank alone adds
    assert float(jnp.abs(shared).max()) > 1e-3 and float(jnp.abs(total).max()) > 1e-3
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
    want = _reference_forward(actor_params, critic_params, tokens, _spec(held=EXPERTS))
    actor = _actor(held=EXPERTS, vocab=rows)
    logits, _, _ = jax.jit(lambda p, t: actor.apply(p, t, method="forward"))(
        _rank_params(actor_params, vocab=rows), tokens
    )
    assert logits.shape[-1] == rows
    _close(logits, want["logits"][..., :rows])
    sliced = _reference_forward(
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


def test_the_yaml_is_the_published_layer():
    """configs/network/laguna_xs2_moe.yaml at its defaults: the published
    widths, the two rotation blocks to the digit, depth 5 and the share."""
    config = config_lib.compose(
        config_lib.default_config_dir(), "default/anakin/default_ff_lm_ppo.yaml",
        ["env=token_task", "network=laguna_xs2_moe"],
    )
    net = config.network.actor_network
    assert (net.hidden_size, net.num_kv_heads, net.head_dim, net.sliding_window) == (2048, 8, 128, 512)
    assert list(net.layer_types) == KINDS and list(net.num_heads_per_layer) == [48, 64, 64, 64, 48]
    assert (net.dense_width, net.expert_width, net.num_experts, net.experts_per_token) == (8192, 512, 256, 8)
    assert (net.experts_held, net.n_shared_experts, net.routed_scaling_factor) == (8, 1, 2.5)
    assert net.attention_gate and not net.tie_word_embeddings
    full = {k: net.rope_parameters.full_attention[k] for k in net.rope_parameters.full_attention}
    assert full == {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1, "beta_fast": 64,
        "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5,
    }
    window = {k: net.rope_parameters.sliding_attention[k] for k in net.rope_parameters.sliding_attention}
    assert window == {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1}
    actor = config_lib.instantiate(net, vocab_size=12544)
    shapes = jax.eval_shape(
        lambda key: actor.init(key, jnp.zeros((1, 2), jnp.int32), method="forward"), jax.random.PRNGKey(0)
    )
    assert sum(x.size for x in jax.tree.leaves(shapes)) + 2049 == 389_638_401
    theta, rotary_dim, yarn = actor._rotation("full_attention")
    assert (theta, rotary_dim, yarn) == (500000.0, 64, PUBLISHED)
    assert actor._rotation("sliding_attention") == (10000.0, None, None)
    assert config.network.get("learner_compiler_options") is None


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
    # the attend's own scope lies inside its mixer's, and a window layer's ops carry no
    # scope of a full layer's (no reader can take one for the other)
    scopes["nested"] = all(
        SCOPES["window_mixer"] in path and SCOPES["attention"] not in path
        and SCOPES["attention_scores"] not in path
        for path in paths if SCOPES["window_attend"] in path
    ) and all(SCOPES["attention"] in path for path in paths if SCOPES["attention_scores"] in path)
    evaluator, _ = carry_evaluator_setup()(eval_env, setup.eval_act_fn, config, mesh)
    lowered = jax.jit(evaluator).lower(
        setup.eval_params_fn(setup.learner_state), jax.random.PRNGKey(1)
    )
    scopes["evaluator"] = {part for path in _paths(lowered.compile().as_text()) for part in path}
    return scopes


@pytest.mark.parametrize("phase", ["rollout", "update_epoch", "evaluator"])
@pytest.mark.parametrize(
    "scope", WINDOW_SCOPES + ("attention_scores", "dense_mlp", "shared_expert") + BLOCK_SCOPES
)
def test_the_scopes_are_in_both_phases_of_the_learner_and_in_the_evaluator(
    program_scopes, phase, scope
):
    assert SCOPES[scope] in program_scopes[phase]


def test_a_window_layers_scopes_are_its_own(program_scopes):
    assert program_scopes["nested"]
    assert len(set(SCOPES.values())) == len(SCOPES)
    for name in WINDOW_SCOPES:  # whole path components: no scope's name is part of another's
        assert not any(name in other.split("_mixer")[0] for other in SCOPES.values() if other != name)


def test_learner_setup_publishes_the_carry_kinds_and_the_updates_form(program_scopes):
    by = lambda gauge, label: {
        dict(labels)[label]: value for labels, value in gauge.labels_and_values()
    }
    registry = get_registry()
    per_shard = 32 // 8  # sequences a shard of the 8 virtual devices
    row = per_shard * KV_HEADS * HEAD_DIM * 4 * 2
    assert by(registry.gauge("stoix_tpu_lm_carry_bytes"), "kind") == {
        "kv": 2 * LENGTH * row, "window_kv": 3 * WINDOW * row,
    }
    assert by(registry.gauge("stoix_tpu_lm_cache_write"), "form") == {"slice": 1.0, "scatter": 0.0}
    # off the chip the update's window layers multiply every causal pair and mask
    assert by(registry.gauge("stoix_tpu_window_attend_update"), "form") == {"banded": 0.0, "masked": 1.0}


def test_the_updates_form_is_banded_where_the_kernel_pair_runs(monkeypatch):
    """On a TPU a window shorter than the sequence goes through the flash
    kernel pair, whose walk starts at the band; a window that holds the whole
    sequence is the causal walk."""
    mixer, params, u = _one_mixer(gate=False, window=4)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    seen = {}
    monkeypatch.setattr(
        lfm2, "best_attention", lambda q, k, v, causal, window: seen.update(window=window) or q
    )
    gauge = lambda: {
        dict(labels)["form"]: value
        for labels, value in get_registry().gauge("stoix_tpu_window_attend_update").labels_and_values()
    }
    mixer.apply(params, u, method="forward")
    assert gauge() == {"banded": 1.0, "masked": 0.0} and seen == {"window": 4}
    lfm2.GroupedQueryAttention(64, 8, 2, 16, 10000.0, 1e-6, window=9).apply(params, u, method="forward")
    assert gauge() == {"banded": 0.0, "masked": 1.0}


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
    """At the configuration's own learning rate: the pairs held here in
    rollout and update, the held experts' load, nothing re-routed by a bias
    that is not there, nothing dropped."""
    _, trains, _ = _logged_run(["arch.num_updates=2", "arch.num_evaluation=1"])
    (train,) = trains
    uniform = TOP_K * HELD / EXPERTS
    assert 0.3 * uniform < float(train["held_pairs_per_token"]) < 3 * uniform
    assert 0.3 * uniform < float(train["rollout_held_pairs_per_token"]) < 3 * uniform
    assert float(train["expert_load_max_over_mean"]) >= 1.0
    assert float(train["router_bias_changed_share"]) == 0.0
    assert float(train["dropped_pairs"]) == 0.0


def test_the_benchmark_keeps_a_copy_of_the_reference(model):
    """benchmarks/references/ppo_laguna.py carries its own copy of the plain
    forward and loss (it may import nothing of the program): they agree
    exactly, with the window and with the window ignored."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.harness import loader

    copy = loader.load_reference("ppo_laguna")
    _, actor_params, critic_params, tokens = model
    for spec in (_spec(), _spec(sliding_window=None)):
        want = _reference_forward(actor_params, critic_params, tokens, spec)
        got = jax.jit(lambda a, c, t: copy.forward(a, c, t, spec))(actor_params, critic_params, tokens)
        for key in ("logits", "values", "expert_index", "plain_index"):
            np.testing.assert_array_equal(np.asarray(got[key]), np.asarray(want[key]))
