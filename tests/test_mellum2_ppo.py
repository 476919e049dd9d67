"""The Mellum2-12B-A2.5B token policy that generates FROM A PROMPT
(networks/lfm2.py's `prefill` beside `forward` and `step`, an all-routed stack
behind a softmax router with no selection bias, envs/token_task.py's
`prompt_length`, systems/ppo/anakin/ff_lm_ppo.py with `network=mellum2_moe`
and the evaluator's start carry) against its plain reference
(reference/mellum2.py), at a tiny preset on the CPU: hidden 64, [window x 3,
full], 16 query heads on 2 key/value heads of 16 (eight queries a key/value
head, as published), a window of 6, the full layer rotated under YaRN, 16
experts top-3 of width 32 of which a rank holds 2 (8 ranks), vocabulary 64;
prompts of 4, 6, 12 and 15 tokens (under, equal to, a multiple of and no
multiple of the window) before 9 generated ones (a further wrap). Tolerance
1e-5 throughout: both sides are float32 on the CPU and differ only in
summation order."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stoix_tpu import envs
from stoix_tpu.base_types import ActorCriticParams
from stoix_tpu.envs.token_task import TokenTask
from stoix_tpu.envs.wrappers import apply_core_wrappers, unwrapped_state
from stoix_tpu.networks import lfm2, olmoe
from stoix_tpu.observability import BLOCK_SCOPES, PROMPT_SCOPES, SCOPES, WINDOW_SCOPES, get_registry
from stoix_tpu.ops import pallas_attention
from stoix_tpu.reference import mellum2 as reference
from stoix_tpu.systems.ppo.anakin import ff_lm_ppo
from stoix_tpu.utils import config as config_lib

TOL = 1e-5
VOCAB, WINDOW, RESPONSE = 64, 6, 9
PROMPTS = {"under": 4, "the_window": WINDOW, "two_windows": 2 * WINDOW, "no_multiple": 15}
KINDS = ["sliding_attention"] * 3 + ["full_attention"]
HEADS, KV_HEADS, HEAD_DIM = 16, 2, 16
EXPERTS, HELD, TOP_K, RANKS = 16, 2, 3, 8
ATTENTION_FACTOR = 1.2772588722239782
ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 16, "beta_fast": 4, "beta_slow": 1,
        "attention_factor": ATTENTION_FACTOR,
    },
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
}
TINY_NETWORK = [
    "network=mellum2_moe",
    "network.actor_network.hidden_size=64", f"network.actor_network.num_heads={HEADS}",
    f"network.actor_network.num_kv_heads={KV_HEADS}", f"network.actor_network.head_dim={HEAD_DIM}",
    f"network.actor_network.sliding_window={WINDOW}",
    f"network.actor_network.num_experts={EXPERTS}", f"network.actor_network.experts_held={HELD}",
    f"network.actor_network.experts_per_token={TOP_K}", "network.actor_network.expert_width=32",
    "network.actor_network.rope_parameters.full_attention.original_max_position_embeddings=16",
    "network.actor_network.rope_parameters.full_attention.beta_fast=4",
]
PROMPT = PROMPTS["no_multiple"]
TINY = TINY_NETWORK + [
    f"env.kwargs.vocab_size={VOCAB}", f"env.kwargs.length={RESPONSE}",
    f"env.kwargs.prompt_length={PROMPT}", f"system.rollout_length={RESPONSE}",
    "system.router_aux_loss_coef=0.0", "arch.total_num_envs=32", "system.num_minibatches=4",
    "arch.num_eval_episodes=8", "arch.total_timesteps=~", "arch.num_updates=2",
    "arch.num_evaluation=1", "arch.absolute_metric=False", "logger.use_console=False",
    "logger.checkpointing.save_model=False",
]
HYPER = {"clip_eps": 0.2, "ent_coef": 0.01, "vf_coef": 0.5, "aux_coef": 0.01}


def _spec(held=HELD, offset=0, **extra):
    return {
        "hidden_size": 64, "num_hidden_layers": len(KINDS), "layer_types": KINDS,
        "num_attention_heads": HEADS, "num_key_value_heads": KV_HEADS, "head_dim": HEAD_DIM,
        "sliding_window": WINDOW, "rope_parameters": ROPE, "rms_norm_eps": 1e-6,
        "num_experts": held, "expert_offset": offset, "num_experts_per_tok": TOP_K,
        "attention_query_block": 8, **extra,
    }


def _actor(held=HELD, offset=0, vocab=VOCAB, **extra):
    keys = dict(
        vocab_size=vocab, hidden_size=64, layer_types=KINDS, num_dense_layers=0, dense_width=96,
        num_heads=HEADS, num_kv_heads=KV_HEADS, head_dim=HEAD_DIM, sliding_window=WINDOW,
        rope_parameters=ROPE, num_experts=EXPERTS, experts_held=held, expert_offset=offset,
        experts_per_token=TOP_K, expert_width=32, routed_scaling_factor=1.0, router_epsilon=0.0,
        router_scoring="softmax", router_selection_bias=False, tie_word_embeddings=False,
        rms_eps=1e-6,
    )
    return lfm2.Lfm2LM(**{**keys, **extra})


def _model(held=HELD, offset=0, length=PROMPT + RESPONSE, **extra):
    actor, critic = _actor(held, offset, **extra), olmoe.ValueHead()
    key = jax.random.PRNGKey(6)
    actor_params = actor.init(key, jnp.zeros((1, 2), jnp.int32), method="forward")
    # normal(0.02) leaves every router near uniform and every softmax flat;
    # scale the weights up so that routing, the band and the rotations matter.
    actor_params = jax.tree.map(lambda w: w * 8.0 if w.ndim > 1 else w, actor_params)
    critic_params = jax.tree.map(lambda w: w + 0.1, critic.init(key, jnp.zeros((1, 2, 64))))
    tokens = jax.random.randint(jax.random.PRNGKey(7), (4, length), 0, VOCAB)
    return ff_lm_ppo.network_functions(actor, critic, length), actor_params, critic_params, tokens


@pytest.fixture(scope="module")
def model():
    return _model()


def _reference_forward(actor_params, critic_params, tokens, spec, response=None):
    return jax.jit(lambda a, c, t: reference.forward(a, c, t, spec, response=response))(
        actor_params, critic_params, tokens
    )


def _close(got, want, tol=TOL):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol)


def _apart(got, want):
    return float(jnp.max(jnp.abs(jnp.asarray(got) - jnp.asarray(want))))


def _sets(index):
    return np.sort(np.asarray(index), axis=-1)


# --------------------------------------------------------------------------- #
# The teacher-forced pass
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("output", ["logits", "values", "expert_index"])
def test_forward_matches_the_plain_reference(model, output):
    """Three window layers and a full one at eight queries a key/value head,
    every feed-forward routed behind the softmax router, against the
    reference's explicit masked softmax (made a block of 8 queries at a
    time: 24 positions are three blocks)."""
    nets, actor_params, critic_params, tokens = model
    want = _reference_forward(actor_params, critic_params, tokens, _spec())
    logits, hidden, stats = jax.jit(nets.forward)(actor_params, tokens)
    if output == "logits":
        _close(logits, want["logits"])
    elif output == "values":
        _close(nets.value(critic_params, hidden), want["values"])
    else:  # every layer is routed; the chosen expert SETS are identical, layer by layer
        assert stats["expert_index"].shape == (len(KINDS), tokens.size, TOP_K)
        assert (_sets(stats["expert_index"]) == _sets(want["expert_index"])).all()
        assert int(stats["expert_count"].sum()) == len(KINDS) * tokens.size * TOP_K


def test_the_head_on_the_last_positions_is_the_whole_heads_last_rows(model):
    """`forward(tokens, n)`: logits and hidden of the last n positions alone,
    bit for bit the whole pass's; the router's stats stay every position's."""
    nets, actor_params, critic_params, tokens = model
    logits, hidden, stats = jax.jit(nets.forward)(actor_params, tokens)
    last, last_hidden, last_stats = jax.jit(lambda p, t: nets.forward(p, t, RESPONSE))(actor_params, tokens)
    assert last.shape == (tokens.shape[0], RESPONSE, VOCAB)
    np.testing.assert_array_equal(np.asarray(last), np.asarray(logits[:, -RESPONSE:]))
    np.testing.assert_array_equal(np.asarray(last_hidden), np.asarray(hidden[:, -RESPONSE:]))
    np.testing.assert_array_equal(np.asarray(last_stats["expert_count"]), np.asarray(stats["expert_count"]))
    want = _reference_forward(actor_params, critic_params, tokens, _spec(), response=RESPONSE)
    assert want["logits"].shape == last.shape and want["expert_index"].shape[1] == tokens.size
    _close(last, want["logits"])


def test_the_reference_in_query_blocks_is_the_reference_in_one(model):
    _, actor_params, critic_params, tokens = model
    blocks = _reference_forward(actor_params, critic_params, tokens, _spec())
    whole = _reference_forward(actor_params, critic_params, tokens, _spec(attention_query_block=512))
    _close(blocks["logits"], whole["logits"])


def test_the_window_and_the_scoring_matter_at_this_size(model):
    """Two of the readings that have to come out as NOT equal: a window layer
    that attends causally, and the softmax router scored as a sigmoid (the
    same experts chosen, other weights on them)."""
    nets, actor_params, critic_params, tokens = model
    want = _reference_forward(actor_params, critic_params, tokens, _spec())
    causal = _reference_forward(actor_params, critic_params, tokens, _spec(sliding_window=None))
    _close(causal["logits"][:, :WINDOW], want["logits"][:, :WINDOW])  # (a prefix inside one window)
    assert _apart(causal["logits"], want["logits"]) > 0.1
    sigmoid = _actor(router_scoring="sigmoid")
    logits, _, stats = jax.jit(lambda p, t: sigmoid.apply(p, t, method="forward"))(actor_params, tokens)
    assert (_sets(stats["expert_index"][0]) == _sets(want["expert_index"][0])).all()
    assert _apart(logits, want["logits"]) > 0.1


# --------------------------------------------------------------------------- #
# Prefill, then decode
# --------------------------------------------------------------------------- #


def _decode(nets, actor_params, critic_params, carry, tokens):
    """`tokens` [B, G] one step each from `carry`: every step's logits and
    values [B, G, ...] and the carry afterwards."""
    def one(carry, token):
        logits, hidden, carry, _ = nets.step(actor_params, carry, token)
        return carry, (logits, nets.value(critic_params, hidden))

    carry, (logits, values) = jax.lax.scan(one, carry, tokens.T)
    return jnp.swapaxes(logits, 0, 1), jnp.swapaxes(values, 0, 1), carry


@pytest.fixture(scope="module", params=sorted(PROMPTS), ids=sorted(PROMPTS))
def prefilled(request):
    """A prompt of P tokens prefilled into three rings and a cache, then
    RESPONSE decode steps through them: (what every step gave, the reference's
    full forward over [prompt ; response], the carry after the prefill and
    after the decode, P)."""
    prompt = PROMPTS[request.param]
    nets, actor_params, critic_params, tokens = _model(length=prompt + RESPONSE)

    def run(actor_params, tokens):
        carry, stats = nets.prefill(actor_params, nets.init_cache(tokens.shape[0]), tokens[:, :prompt])
        logits, values, after = _decode(nets, actor_params, critic_params, carry, tokens[:, prompt:])
        return logits, values, carry, after, stats

    logits, values, carry, after, stats = jax.jit(run)(actor_params, tokens)
    want = _reference_forward(actor_params, critic_params, tokens, _spec(), response=RESPONSE)
    return (logits, values), want, (carry, after, stats), (nets, actor_params, critic_params, tokens), prompt


@pytest.mark.parametrize("output", ["logits", "values"])
def test_prefill_then_decode_is_the_reference_forward_at_every_response_position(prefilled, output):
    """The reference has no cache, no ring and no prefill: its ONE forward
    over [prompt ; response] gives at each response position what the decode
    through the prefilled rings and cache gives there — for a prompt under,
    equal to, a multiple of and no multiple of the window, the generation
    wrapping every ring once more."""
    (logits, values), want, _, _, _ = prefilled
    _close(logits if output == "logits" else values, want[output])


def test_the_prefill_leaves_what_as_many_steps_leave(prefilled):
    """The carry after `prefill` is the carry after P `step`s from empty: the
    same length, and the same live rows at the same places of every ring and
    of the cache (a ring's live rows are all of it once P >= W)."""
    _, _, (carry, after, stats), (nets, actor_params, critic_params, tokens), prompt = prefilled
    stepped = jax.jit(
        lambda p, t: _decode(nets, p, critic_params, nets.init_cache(t.shape[0]), t)[2]
    )(actor_params, tokens[:, :prompt])
    assert carry.length.shape == () and int(carry.length) == prompt == int(stepped.length)
    assert int(after.length) == prompt + RESPONSE
    for kind, got, want in zip(KINDS, carry.layers, stepped.layers):
        assert type(got) is type(want) is (lfm2.WindowKV if kind == "sliding_attention" else lfm2.KV)
        rows = min(prompt, WINDOW) if kind == "sliding_attention" else prompt
        assert got.k.shape[0] == (WINDOW if kind == "sliding_attention" else prompt + RESPONSE)
        _close(got.k[:rows], want.k[:rows])
        _close(got.v[:rows], want.v[:rows])
    # the prefill's router saw every prefix token in every layer, and dropped nothing
    assert int(stats["expert_count"].sum()) == len(KINDS) * tokens.shape[0] * prompt * TOP_K


def _splice(carry, rings):
    """`carry` with its window layers' states replaced by `rings`'s."""
    layers = tuple(
        other if isinstance(state, lfm2.WindowKV) else state
        for state, other in zip(carry.layers, rings.layers)
    )
    return carry._replace(layers=layers)


@pytest.mark.parametrize("fault", ["first_rows", "unrotated_keys", "from_empty"])
def test_a_wrong_prefill_is_another_result(fault):
    """What the comparison above has to refuse: a ring filled with the FIRST
    W prefix rows (position t at t, not the newest W at t % W), a prefill
    that kept its keys unrotated, and a decode that starts at the right
    position from EMPTY rings and cache (the prefix dropped)."""
    prompt = PROMPTS["no_multiple"]
    nets, actor_params, critic_params, tokens = _model(length=prompt + RESPONSE)
    want = _reference_forward(actor_params, critic_params, tokens, _spec(), response=RESPONSE)
    empty = nets.init_cache(tokens.shape[0])
    carry, _ = nets.prefill(actor_params, empty, tokens[:, :prompt])
    if fault == "first_rows":
        first, _ = nets.prefill(actor_params, empty, tokens[:, :WINDOW])
        carry = _splice(carry, first)
    elif fault == "unrotated_keys":
        still = {kind: {"rope_type": "default", "rope_theta": 1e30} for kind in ROPE}
        unrotated = ff_lm_ppo.network_functions(_actor(rope_parameters=still), olmoe.ValueHead(), prompt + RESPONSE)
        carry, _ = unrotated.prefill(actor_params, empty, tokens[:, :prompt])
    else:
        carry = empty._replace(length=empty.length + prompt)
    logits, _, _ = jax.jit(lambda c: _decode(nets, actor_params, critic_params, c, tokens[:, prompt:]))(carry)
    assert _apart(logits, want["logits"]) > 0.05


def test_a_mixer_with_no_prefill_says_so_by_name():
    actor = _actor(layer_types=["conv", "full_attention"])
    params = actor.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32), method="forward")
    with pytest.raises(NotImplementedError, match="ShortConv mixer has no prefill"):
        actor.apply(params, actor.init_carry(2, 8, together=True), jnp.zeros((2, 4), jnp.int32), method="prefill")
    one = _actor(layer_types=["full_attention"])
    params = one.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32), method="forward")
    with pytest.raises(ValueError, match="does not fit a cache of 8 rows"):
        one.apply(params, one.init_carry(2, 8, together=True), jnp.zeros((2, 9), jnp.int32), method="prefill")


# --------------------------------------------------------------------------- #
# The decode kernel at four key/value heads
# --------------------------------------------------------------------------- #


def _caches(key, rows, batch, kv_heads):
    return tuple(jax.random.normal(k, (rows, batch, kv_heads, 128)) for k in jax.random.split(key))


# (rows, sequences): the cell's layout — four key/value heads of eight queries, so an (8, 128)
# tile of a block is TWO rows' heads — at eight sequences a grid step with two grid rows turning,
# and at one.
@pytest.mark.parametrize("rows,batch", [(256, 16), (128, 3)])
@pytest.mark.parametrize("last", ["apart", "full", "one_row"])
def test_the_decode_kernel_at_four_key_value_heads_is_the_plain_attend(rows, batch, last):
    """`gqa_decode_attention` (Pallas interpreter) at 4 key/value heads x 8
    queries against `_attend_cache`: sequences at different live rows, every
    row live (a wrapped ring), one row live; rows past the live ones may hold
    anything."""
    keys = jax.random.split(jax.random.PRNGKey(8))
    q = jax.random.normal(keys[0], (batch, 4, 8, 128))
    cache_k, cache_v = _caches(keys[1], rows, batch, 4)
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


def test_at_four_heads_a_heads_result_is_of_its_own_key_value_head_alone():
    rows, batch, head = 256, 8, 2
    keys = jax.random.split(jax.random.PRNGKey(9), 2)
    q = jax.random.normal(keys[0], (batch, 4, 8, 128))
    cache_k, cache_v = _caches(keys[1], rows, batch, 4)
    lasts = jnp.arange(batch) * 37 % rows
    attend = lambda k, v: pallas_attention.gqa_decode_attention(q, k, v, lasts, interpret=True)
    before = attend(cache_k, cache_v)
    own = (jnp.arange(4) == head)[None, None, :, None]
    after = attend(jnp.where(own, cache_k, 1e30), jnp.where(own, cache_v, -1e30))
    np.testing.assert_array_equal(np.asarray(after[:, head]), np.asarray(before[:, head]))
    assert bool(jnp.isfinite(after).all())


# --------------------------------------------------------------------------- #
# Loss and gradients on [prefix ; response]
# --------------------------------------------------------------------------- #

_MIXER = ["wq", "wk", "wv", "wo", "q_norm", "k_norm"]
_ROUTED = ["router", "gate", "up", "down"]  # (no expert_bias: no selection bias is published)
ACTOR_LEAVES = ["embed", "final_norm", "lm_head"] + [
    f"layer_{i}/{name}"
    for i in range(len(KINDS))
    for name in ["operator_norm", "ffn_norm"] + [f"mixer/{m}" for m in _MIXER] + [f"ffn/{f}" for f in _ROUTED]
]
LEAVES = ["actor/" + name for name in ACTOR_LEAVES] + ["critic/kernel", "critic/bias"]


def _batch(tokens):
    rng = np.random.default_rng(0)
    shape = (tokens.shape[0], RESPONSE)
    return {
        "prefix": tokens[:, :PROMPT], "token": tokens[:, PROMPT:],
        "action": jnp.asarray(rng.integers(0, VOCAB, shape), jnp.int32),
        "log_prob": jnp.asarray(-4.0 + 0.3 * rng.normal(size=shape), jnp.float32),
        "value": jnp.asarray(rng.normal(size=shape), jnp.float32),
        "advantage": jnp.asarray(rng.normal(size=shape), jnp.float32),
        "target": jnp.asarray(rng.normal(size=shape), jnp.float32),
    }


@pytest.fixture(scope="module")
def loss_and_grads():
    nets, actor_params, critic_params, tokens = _model()
    batch = _batch(tokens)
    params = ActorCriticParams(actor_params, critic_params)
    loss = lambda p, b: ff_lm_ppo.lm_ppo_loss(nets, p, b, **HYPER)
    (total, info), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(params, batch)
    ref_batch = {k: v for k, v in batch.items() if k not in ("prefix", "token")}
    ref_batch["tokens"] = tokens
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
        flat(grads.actor_params, grads.critic_params), flat(*want_grads), (jax.jit(loss), params, batch),
    )


@pytest.mark.parametrize("part", [
    "total_loss", "actor_loss", "value_loss", "entropy", "aux_loss", "expert_load_max_over_mean",
    "routed_pairs_per_token", "held_pairs_per_token",
])
def test_loss_matches_the_reference_loss(loss_and_grads, part):
    """PPO's clip, value loss and entropy over the 9 response positions of a
    24-token sequence, the router's statistics over all 24."""
    got, want, _, _, _ = loss_and_grads
    _close(got[part], want[part])


def test_the_loss_counts_no_dropped_pair_and_no_bias(loss_and_grads):
    got, _, _, _, _ = loss_and_grads
    assert float(got["dropped_pairs"]) == 0.0 and float(got["routed_pairs_per_token"]) == TOP_K
    assert "router_bias_changed_share" not in got  # there is no selection bias to re-route by


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_leaf_matches_jax_grad_of_the_reference_loss(loss_and_grads, leaf):
    _, _, got, want, _ = loss_and_grads
    assert sorted(got) == sorted(LEAVES) == sorted(want)
    assert float(jnp.max(jnp.abs(want[leaf]))) > 0.0  # a gradient that is there to compare
    _close(got[leaf], want[leaf], tol=2e-5)


def test_the_loss_is_the_responses_alone_and_reads_the_prefix_as_context(loss_and_grads):
    """No prefix position has a target: the loss's clip, value and entropy
    parts are a function of the LAST 9 positions' logits and values alone
    (recomputed here from the whole pass's, by hand), so nothing the head
    would say on a prefix position can move it; a changed prefix TOKEN does
    move it, through what the response positions attend to."""
    got, _, _, _, (loss, params, batch) = loss_and_grads
    nets, _, _, tokens = _model()
    logits, hidden, _ = jax.jit(nets.forward)(params.actor_params, tokens)
    log_probs = jax.nn.log_softmax(logits[:, PROMPT:], axis=-1)
    entropy = -jnp.sum(jnp.exp(log_probs) * log_probs, axis=-1).mean()
    _close(got["entropy"], entropy)
    log_prob = jnp.take_along_axis(log_probs, batch["action"][..., None], axis=-1)[..., 0]
    ratio = jnp.exp(log_prob - batch["log_prob"])
    surrogate = jnp.minimum(ratio * batch["advantage"], jnp.clip(ratio, 0.8, 1.2) * batch["advantage"])
    _close(got["actor_loss"], -surrogate.mean())
    other = batch["prefix"].at[:, 1].set((batch["prefix"][:, 1] + 1) % VOCAB)
    moved, _ = loss(params, {**batch, "prefix": other})
    assert abs(float(moved) - float(got["total_loss"])) > 1e-4


# --------------------------------------------------------------------------- #
# One rank's share against the uncut layer and head
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def uncut():
    """The uncut model at the tiny size: all 16 experts, all 64 rows."""
    _, actor_params, critic_params, tokens = _model(held=EXPERTS)
    return actor_params, critic_params, tokens


def test_the_eight_ranks_parts_add_up_to_the_uncut_layer(uncut):
    """The routed layer on each rank's own weights, through the program's
    module; there is no shared expert to count once: the eight parts sum to
    the uncut reference's layer."""
    actor_params, _, _ = uncut
    ffn = actor_params["params"]["layer_3"]["ffn"]
    assert sorted(ffn) == ["down", "gate", "router", "up"]
    x = jax.random.normal(jax.random.PRNGKey(5), (48, 64))
    want, _ = reference.moe(ffn, x, _spec(held=EXPERTS))
    total = jnp.zeros_like(x)
    for rank in range(RANKS):
        mine = {**ffn, **{name: ffn[name][rank * HELD:(rank + 1) * HELD] for name in ("gate", "up", "down")}}
        layer = lfm2.RoutedMLP(
            64, EXPERTS, HELD, rank * HELD, TOP_K, 32, 1.0, 0.0, 0.0, score="softmax", selection_bias=False
        )
        part, _ = layer.apply({"params": mine}, x)
        share, _ = reference.moe(ffn, x, _spec(held=HELD, offset=rank * HELD))
        _close(part, share)
        total = total + part
    assert float(jnp.abs(total).max()) > 1e-3
    _close(total, want)


def test_the_sliced_heads_logits_are_the_uncut_heads_rows(uncut):
    actor_params, critic_params, tokens = uncut
    rows = VOCAB // RANKS
    tokens = tokens % rows
    want = _reference_forward(actor_params, critic_params, tokens, _spec(held=EXPERTS))
    cut = lambda path, w: (
        w[:rows] if path[-1].key == "embed" else w[:, :rows] if path[-1].key == "lm_head" else w
    )
    actor = _actor(held=EXPERTS, vocab=rows)
    logits, _, _ = jax.jit(lambda p, t: actor.apply(p, t, method="forward"))(
        jax.tree_util.tree_map_with_path(cut, actor_params), tokens
    )
    _close(logits, want["logits"][..., :rows])
    sliced = _reference_forward(
        actor_params, critic_params, tokens, _spec(held=EXPERTS, vocab_slice=(0, rows))
    )
    _close(sliced["logits"], want["logits"][..., :rows])


# --------------------------------------------------------------------------- #
# The env's prompt
# --------------------------------------------------------------------------- #


def test_an_env_without_a_prompt_is_the_env_it_was():
    key = jax.random.PRNGKey(3)
    for got, want in zip(
        jax.tree.leaves(TokenTask(VOCAB, 9, 2, prompt_length=0).reset(key)),
        jax.tree.leaves(TokenTask(VOCAB, 9, 2).reset(key)),
    ):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert TokenTask(VOCAB, 9).prompt_length == 0
    assert TokenTask(VOCAB, 9).prompt(TokenTask(VOCAB, 9).reset(key)[0]).shape == (0,)


def test_the_prompt_is_the_episodes_own_through_every_wrapper():
    """`prompt(state)`: ids of the vocabulary, the same at every step of an
    episode, another after the auto-reset, an episode's own; read from the
    batched state inside the wrapped train env's (`unwrapped_state`) as from
    the bare env's; a wrapper's own state is refused, not read."""
    task = TokenTask(VOCAB, 3, 2, prompt_length=5)
    env = apply_core_wrappers(task, num_envs=4)
    prompt = lambda state: env.prompt(unwrapped_state(state))
    state, _ = env.reset(jax.random.split(jax.random.PRNGKey(0), 4))
    with pytest.raises(TypeError, match="own state"):
        env.prompt(state)
    first = prompt(state)
    assert first.shape == (4, 5) and first.dtype == jnp.int32
    assert int(first.min()) >= 0 and int(first.max()) < VOCAB
    assert len({tuple(row) for row in np.asarray(first).tolist()}) == 4
    # the observation and the state carry none of it
    assert sum(x.size for x in jax.tree.leaves(state)) < 4 * 16
    for _ in range(2):
        state, timestep = env.step(state, jnp.zeros((4,), jnp.int32))
        np.testing.assert_array_equal(np.asarray(prompt(state)), np.asarray(first))
    state, timestep = env.step(state, jnp.zeros((4,), jnp.int32))  # the episode's last step
    assert bool(timestep.last().all())
    assert not (np.asarray(prompt(state)) == np.asarray(first)).all(axis=1).any()
    bare, _ = task.reset(jax.random.PRNGKey(1))
    assert task.prompt(bare).shape == (5,)


@pytest.mark.parametrize("key,kept", [("sequence_prompt", False), ("rollout_action", True)])
def test_a_once_a_sequence_record_is_left_out_of_the_final_steps_by_name(key, kept):
    """`get_final_step_metrics` leaves out the keys under `ONCE_A_SEQUENCE`
    and nothing else: a step's record of another shape than the steps' still
    raises, as it did."""
    from stoix_tpu.envs.types import ONCE_A_SEQUENCE, get_final_step_metrics

    terminal = np.zeros((1, 9, 4), bool)
    terminal[0, -1] = True
    metrics = {"is_terminal_step": terminal, "episode_return": np.ones((1, 9, 4))}
    record = np.arange(60).reshape(1, 15, 4) if not kept else np.arange(36).reshape(1, 9, 4)
    out = get_final_step_metrics({**metrics, key: record})
    assert key.startswith(ONCE_A_SEQUENCE) != kept and (key in out) == kept
    assert out["episode_return"].shape == (4,)
    with pytest.raises(ValueError):  # (no silent drop of a mis-shaped step record)
        get_final_step_metrics({**metrics, "rollout_action": np.arange(60).reshape(1, 15, 4)})


# --------------------------------------------------------------------------- #
# The system
# --------------------------------------------------------------------------- #


def _config(extra=(), base=TINY):
    return config_lib.compose(
        config_lib.default_config_dir(), "default/anakin/default_ff_lm_ppo.yaml", list(base) + list(extra)
    )


def test_the_yaml_is_the_published_layer():
    """configs/network/mellum2_moe.yaml at its defaults: the published
    widths, the two rotation blocks to the digit, one whole period and the
    share; the parameter count the configuration file states."""
    config = config_lib.compose(
        config_lib.default_config_dir(), "default/anakin/default_ff_lm_ppo.yaml",
        ["env=token_task", "network=mellum2_moe"],
    )
    net = config.network.actor_network
    assert (net.hidden_size, net.num_heads, net.num_kv_heads, net.head_dim) == (2304, 32, 4, 128)
    assert net.sliding_window == 1024 and list(net.layer_types) == KINDS
    assert (net.num_dense_layers, net.n_shared_experts, net.dense_width) == (0, 0, 7168)
    assert (net.expert_width, net.num_experts, net.experts_per_token, net.experts_held) == (896, 64, 8, 8)
    assert (net.router_scoring, net.router_selection_bias, net.routed_scaling_factor) == ("softmax", False, 1.0)
    assert not net.attention_gate and not net.tie_word_embeddings and net.router_epsilon == 0.0
    full = {k: net.rope_parameters.full_attention[k] for k in net.rope_parameters.full_attention}
    assert full == {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
        "attention_factor": ATTENTION_FACTOR,
    }
    window = {k: net.rope_parameters.sliding_attention[k] for k in net.rope_parameters.sliding_attention}
    assert window == {"rope_type": "default", "rope_theta": 500000}
    actor = config_lib.instantiate(net, vocab_size=12288)
    shapes = jax.eval_shape(
        lambda key: actor.init(key, jnp.zeros((1, 2), jnp.int32), method="forward"), jax.random.PRNGKey(0)
    )
    assert sum(x.size for x in jax.tree.leaves(shapes)) + 2305 == 340_352_513
    mixer = shapes["params"]["layer_0"]["mixer"]
    assert mixer["wq"].shape == (2304, 4096) and mixer["wk"].shape == (2304, 512)
    assert "expert_bias" not in shapes["params"]["layer_0"]["ffn"]
    assert actor._rotation("full_attention") == (
        500000.0, None, olmoe.Yarn(16.0, 8192, 32.0, 1.0, ATTENTION_FACTOR)
    )
    assert actor._rotation("sliding_attention") == (500000.0, None, None)
    assert ATTENTION_FACTOR == pytest.approx(0.1 * np.log(16) + 1)


def _paths(hlo):
    strip = lambda part: re.sub(r"^(?:\w+\()+|\)+$", "", part)
    return [[strip(p) for p in path.split("/")] for path in re.findall(r'op_name="([^"]+)"', hlo)]


def _setup(config):
    from stoix_tpu.parallel import MeshRoles
    from stoix_tpu.utils.timestep_checker import check_total_timesteps

    mesh = MeshRoles.from_config(config).learn_mesh()
    config = check_total_timesteps(config, int(mesh.shape["data"]))
    env, eval_env = envs.make(config)
    return ff_lm_ppo.learner_setup(env, config, mesh, jax.random.PRNGKey(0)), eval_env, config, mesh


@pytest.fixture(scope="module")
def program_scopes(devices):
    """Path components of the tiny learner's compiled program, by phase, and
    of the evaluator's."""
    from stoix_tpu.evaluator import carry_evaluator_setup

    setup, eval_env, config, mesh = _setup(_config())
    paths = _paths(setup.learn.lower(setup.learner_state).compile().as_text())
    scopes = {
        phase: {part for path in paths if SCOPES[phase] in path for part in path}
        for phase in ("rollout", "update_epoch", "prefill")
    }
    scopes["prefill_beside_rollout"] = not any(
        SCOPES["rollout"] in path or SCOPES["update_epoch"] in path
        for path in paths if SCOPES["prefill"] in path
    )
    evaluator, _ = carry_evaluator_setup()(eval_env, setup.eval_act_fn, config, mesh)
    lowered = jax.jit(evaluator).lower(setup.eval_params_fn(setup.learner_state), jax.random.PRNGKey(1))
    scopes["evaluator"] = {part for path in _paths(lowered.compile().as_text()) for part in path}
    scopes["evaluator_returns"] = evaluator(setup.eval_params_fn(setup.learner_state), jax.random.PRNGKey(1))
    return scopes


@pytest.mark.parametrize("phase", ["rollout", "update_epoch", "evaluator", "prefill"])
@pytest.mark.parametrize("scope", WINDOW_SCOPES + ("attention_scores",) + BLOCK_SCOPES)
def test_the_scopes_are_in_every_phase_and_inside_the_prefill(program_scopes, phase, scope):
    if phase == "prefill" and scope == "lm_head":
        assert SCOPES[scope] not in program_scopes[phase]  # no head and no value on the prefix
    else:
        assert SCOPES[scope] in program_scopes[phase]


def test_the_prefill_has_a_scope_of_its_own_beside_the_rollout_and_in_the_evaluator(program_scopes):
    assert PROMPT_SCOPES == ("prefill",)
    # (`rollout` is decode steps alone, with a prompt as without one)
    assert program_scopes["prefill"] and program_scopes["prefill_beside_rollout"]
    assert SCOPES["prefill"] in program_scopes["evaluator"]
    assert SCOPES["prefill"] not in program_scopes["update_epoch"]
    assert not {SCOPES["dense_mlp"], SCOPES["shared_expert"]} & program_scopes["rollout"]
    returns = np.asarray(program_scopes["evaluator_returns"]["episode_return"])
    assert returns.shape == (8,) and ((returns >= 0.0) & (returns <= 1.0)).all()
    assert (np.asarray(program_scopes["evaluator_returns"]["episode_length"]) == RESPONSE).all()


def test_learner_setup_publishes_the_prompt_and_a_carry_of_prompt_and_response(program_scopes):
    by = lambda gauge, label: {
        dict(labels)[label]: value for labels, value in gauge.labels_and_values()
    }
    registry = get_registry()
    per_shard = 32 // 8  # sequences a shard of the 8 virtual devices
    row = per_shard * KV_HEADS * HEAD_DIM * 4 * 2
    assert by(registry.gauge("stoix_tpu_lm_carry_bytes"), "kind") == {
        "kv": (PROMPT + RESPONSE) * row, "window_kv": 3 * WINDOW * row,
    }
    assert [value for _, value in registry.gauge("stoix_tpu_lm_prompt_tokens").labels_and_values()] == [PROMPT]
    assert by(registry.gauge("stoix_tpu_lm_cache_write"), "form") == {"slice": 1.0, "scatter": 0.0}
    # off a TPU the held experts' SwiGLU keeps its three grouped matmuls
    assert by(registry.gauge("stoix_tpu_held_swiglu_form"), "form") == {"kernel": 0.0, "ragged_dot": 1.0}


# `setup.learn.lower(state).as_text()` of tests/test_laguna_ppo.py's tiny preset (its `_config()`,
# 8 virtual devices, PRNGKey(0)) as the tree BEFORE this file's change lowered it (commit 5d7c11e,
# made there with the same lines as below): 1,395,551 characters.
LAGUNA_LEARNER_SHA256 = "4d7e24cec1f7fb59b76c2663031cd311320e39a32f0f38a4ce5bef704110825c"


def test_without_a_prompt_an_accepted_cells_learner_is_the_program_it_was(devices):
    """With `prompt_length` 0 nothing of the prompt is traced: the Laguna tiny
    preset's learner lowers to the text it lowered to before `prefill`, the
    prompt and the softmax router were written, character for character (so
    its output is the same to the bit), and the prompt's gauge reads 0."""
    import test_laguna_ppo

    setup, _, _, _ = _setup(test_laguna_ppo._config())
    text = setup.learn.lower(setup.learner_state).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == LAGUNA_LEARNER_SHA256
    assert "sequence_prompt" not in jax.eval_shape(setup.learn, setup.learner_state).episode_metrics
    assert [v for _, v in get_registry().gauge("stoix_tpu_lm_prompt_tokens").labels_and_values()] == [0]


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


def test_a_short_run_from_prompts_learns_the_token_task(devices):
    """The greedy return of the trained policy — 8 evaluation episodes, each
    from its own prefilled prompt — is far above the untrained 0.5; every
    window logs top-3 routed pairs a token in the prefill, the rollout and
    the update (nothing dropped) and the share that landed on the held
    experts in each; the once-a-sequence prompt record is no step's and is
    not logged as one."""
    final, trains, acts = _logged_run([
        "arch.num_updates=12", "arch.num_evaluation=2", "arch.total_num_envs=64",
        "system.actor_lr=3e-3", "system.critic_lr=3e-3", "arch.evaluation_greedy=True",
    ])
    assert final > 0.75, final
    assert len(trains) == 2
    for train in trains:
        for phase in ("", "rollout_", "prefill_"):
            # (a float32 quotient by a count that is no power of two: a unit in the last place)
            assert float(train[phase + "routed_pairs_per_token"]) == pytest.approx(TOP_K, abs=1e-6)
            # (at this learning rate the router soon moves: a share of the top-3 is all it is)
            assert 0.0 <= float(train[phase + "held_pairs_per_token"]) <= TOP_K
        assert float(train["dropped_pairs"]) == 0.0
        assert "router_bias_changed_share" not in train
    for act in acts:  # one value a finished episode: 6 updates x 64 sequences
        assert {"rollout_action", "rollout_log_prob", "rollout_value"} <= set(act)
        assert "sequence_prompt" not in act
        assert np.asarray(act["rollout_log_prob"]).shape == (6 * 64,)


# --------------------------------------------------------------------------- #
# The embedding's deviation: who chooses the experts behind a long prefix
# --------------------------------------------------------------------------- #


def test_the_embeddings_deviation_moves_the_embedding_alone():
    """`embedding_init_std` at its default is the stack's normal(0.02) for
    every leaf; at 1.0 the embedding is the same draw fifty times as large
    and every other leaf is what it was, to the bit."""
    init = lambda actor: actor.init(jax.random.PRNGKey(6), jnp.zeros((1, 2), jnp.int32), method="forward")
    default, stated, wide = init(_actor()), init(_actor(embedding_init_std=0.02)), init(_actor(embedding_init_std=1.0))
    assert all(bool(jnp.array_equal(a, b)) for a, b in zip(jax.tree.leaves(default), jax.tree.leaves(stated)))
    wide_embed, embed = wide["params"].pop("embed"), default["params"].pop("embed")
    assert all(bool(jnp.array_equal(a, b)) for a, b in zip(jax.tree.leaves(default), jax.tree.leaves(wide)))
    np.testing.assert_allclose(np.asarray(wide_embed), 50.0 * np.asarray(embed), rtol=1e-5)
    assert 0.9 < float(jnp.std(wide_embed)) < 1.1


@pytest.fixture(scope="module")
def held_pairs_behind_a_prefix():
    """Held pairs a token by (layer, sequence) over the later half of four
    sequences of 512 tokens, at a size where the context's mean shows (hidden
    256, eight query heads a key/value head, window 128, top-4 of 16, 4 held:
    one held pair a token expected), for both deviations."""
    length, sequences, held = 512, 4, 4
    tokens = jax.random.randint(jax.random.PRNGKey(4), (sequences, length), 0, 256)

    def read(std):
        actor = _actor(
            held, vocab=256, hidden_size=256, num_heads=16, num_kv_heads=2, head_dim=16,
            sliding_window=128, num_experts=16, experts_per_token=4, embedding_init_std=std,
            rope_parameters={**ROPE, "full_attention": {**ROPE["full_attention"], "original_max_position_embeddings": 8192, "beta_fast": 32}},
        )
        params = actor.init(jax.random.PRNGKey(3), jnp.zeros((1, 2), jnp.int32), method="forward")
        stats = jax.jit(lambda p, t: actor.apply(p, t, method="forward"))(params, tokens)[2]
        index = np.asarray(stats["expert_index"]).reshape(len(KINDS), sequences, length, 4)
        return (index < held).sum(-1)[..., length // 2:].mean(-1)  # [layer, sequence]

    return {0.02: read(0.02), 1.0: read(1.0)}


def test_behind_a_long_prefix_a_small_embedding_leaves_the_choice_to_the_context(held_pairs_behind_a_prefix):
    """At normal(0.02) the layers after the first read the context's mean, so
    a sequence's positions choose alike and its held pairs a token are one
    draw a (layer, sequence): far from the expected one, either way."""
    later = held_pairs_behind_a_prefix[0.02][1:]
    assert later.var() > 0.05 and later.min() < 0.6 and later.max() > 1.3


@pytest.mark.parametrize("layer", range(len(KINDS)))
def test_with_a_unit_embedding_the_token_chooses_and_every_sequence_does_the_same_work(
    held_pairs_behind_a_prefix, layer
):
    pairs = held_pairs_behind_a_prefix[1.0][layer]
    assert np.all(np.abs(pairs - 1.0) < 0.2), pairs
    assert pairs.var() < held_pairs_behind_a_prefix[0.02][1:].var() / 10


def test_the_yaml_leaves_the_embedding_at_the_stacks_deviation_and_takes_an_override():
    assert _config().network.actor_network.embedding_init_std == 0.02
    assert _config(["network.actor_network.embedding_init_std=1.0"]).network.actor_network.embedding_init_std == 1.0


def test_the_benchmark_keeps_a_copy_of_the_reference(model):
    """benchmarks/references/ppo_mellum2.py carries its own copy of the plain
    forward and loss (it may import nothing of the program): they agree
    exactly, with the window, with the window ignored and on the response's
    positions alone."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.harness import loader

    copy = loader.load_reference("ppo_mellum2")
    _, actor_params, critic_params, tokens = model
    for spec, response in ((_spec(), None), (_spec(sliding_window=None), None), (_spec(), RESPONSE)):
        want = _reference_forward(actor_params, critic_params, tokens, spec, response)
        got = jax.jit(lambda a, c, t: copy.forward(a, c, t, spec, response=response))(
            actor_params, critic_params, tokens
        )
        for key in ("logits", "values", "expert_index"):
            np.testing.assert_array_equal(np.asarray(got[key]), np.asarray(want[key]))
    batch = {**{k: v for k, v in _batch(tokens).items() if k not in ("prefix", "token")}, "tokens": tokens}
    sums = lambda module: jax.jit(lambda p, b: module.loss_sums(p, b, _spec(), HYPER))(
        (actor_params, critic_params), batch
    )
    for got, want in zip(jax.tree.leaves(sums(copy)), jax.tree.leaves(sums(reference))):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
