"""The SDAR block-diffusion token policy (networks/sdar.py, networks/olmoe.py's
held-experts layer, systems/ppo/anakin/ff_sdar_ppo.py, envs/block_token_task.py)
against its plain reference (reference/sdar.py), at a tiny preset on the CPU:
hidden 64, 4 query heads and 2 key/value heads of 16, 16 experts top-4 of
width 32 of which a rank holds 2 (8 ranks) or 4, vocabulary 64 (mask id 63),
blocks of 4 positions, 2 denoise passes a block, a response of 16 tokens.
Tolerance 1e-5 throughout: both sides are float32 on the CPU and differ only
in summation order."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stoix_tpu import envs
from stoix_tpu.base_types import ActorCriticParams
from stoix_tpu.envs.block_token_task import BlockTokenTask
from stoix_tpu.networks import olmoe, sdar
from stoix_tpu.observability import BLOCK_SCOPES, DIFFUSION_SCOPES, SCOPES, get_registry
from stoix_tpu.ops import pallas_attention, qk_norm_rope
from stoix_tpu.reference import sdar as reference
from stoix_tpu.systems.ppo.anakin import ff_sdar_ppo
from stoix_tpu.utils import config as config_lib

TOL = 1e-5
VOCAB, RESPONSE, SIZE, PASSES = 64, 16, 4, 2
BLOCKS, STEPS = RESPONSE // SIZE, RESPONSE // SIZE * PASSES
EXPERTS, TOP_K = 16, 4
TINY = [
    "network.actor_network.hidden_size=64", "network.actor_network.num_heads=4",
    "network.actor_network.num_kv_heads=2", "network.actor_network.head_dim=16",
    f"network.actor_network.num_experts={EXPERTS}", "network.actor_network.experts_held=4",
    f"network.actor_network.experts_per_token={TOP_K}", "network.actor_network.expert_width=32",
    "network.actor_network.num_layers=2",
    f"env.kwargs.vocab_size={VOCAB}", f"env.kwargs.length={RESPONSE}",
    f"system.rollout_length={STEPS}", "arch.total_num_envs=16", "system.num_minibatches=4",
    "arch.num_eval_episodes=8", "arch.total_timesteps=~", "arch.num_updates=2",
    "arch.num_evaluation=1", "arch.absolute_metric=False", "logger.use_console=False",
    "logger.checkpointing.save_model=False",
]
HYPER = {"clip_eps": 0.2, "ent_coef": 0.01, "vf_coef": 0.5, "aux_coef": 0.01}


def _spec(layers, held=4, offset=0):
    return {
        "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "num_experts": held, "expert_offset": offset, "num_experts_per_tok": TOP_K,
        "num_hidden_layers": layers, "rms_norm_eps": 1e-6, "rope_theta": 1000000.0,
        "block_length": SIZE, "denoise_passes": PASSES, "mask_token_id": VOCAB - 1,
        "response_length": RESPONSE,
    }


def _model(layers, held=4, offset=0):
    actor = sdar.SdarLM(
        vocab_size=VOCAB, hidden_size=64, num_heads=4, num_kv_heads=2, head_dim=16,
        num_experts=EXPERTS, experts_held=held, expert_offset=offset, experts_per_token=TOP_K,
        expert_width=32, block_length=SIZE, num_layers=layers,
    )
    critic = olmoe.ValueHead()
    key = jax.random.PRNGKey(layers)
    # normal(0.02) leaves every router near uniform; scale the weights up so
    # that routing, attention and the norms all matter to the outputs.
    actor_params = jax.tree.map(lambda w: w * 8.0 if w.ndim > 1 else w, actor.init(key))
    critic_params = jax.tree.map(lambda w: w + 0.1, critic.init(key, jnp.zeros((1, 2, 64))))
    nets = ff_sdar_ppo.network_functions(actor, critic, SIZE + RESPONSE, PASSES)
    return nets, actor_params, critic_params


def _record(seed, sequences=4):
    """A seeded record of `sequences` whole episodes as the rollout stores
    them: every block starts as masks, a pass commits 2 of the masked."""
    rng = np.random.default_rng(seed)
    mask = VOCAB - 1
    block = np.zeros((sequences, BLOCKS, PASSES, SIZE), np.int32)
    commit = np.zeros((sequences, BLOCKS, PASSES, SIZE), bool)
    token = rng.integers(0, mask, (sequences, BLOCKS, PASSES, SIZE)).astype(np.int32)
    for n in range(sequences):
        for b in range(BLOCKS):
            now = np.full(SIZE, mask, np.int32)
            for s in range(PASSES):
                block[n, b, s] = now
                masked = np.flatnonzero(now == mask)
                chosen = rng.choice(masked, SIZE // PASSES, replace=False)
                commit[n, b, s, chosen] = True
                now = np.where(commit[n, b, s], token[n, b, s], now)
    flat = lambda x: jnp.asarray(x.reshape(sequences, STEPS, SIZE))
    shape = (sequences, STEPS)
    return {
        "prompt": jnp.asarray(rng.integers(0, mask, (sequences, SIZE)), jnp.int32),
        "block": flat(block), "commit": flat(commit), "token": flat(token),
        "log_prob": jnp.asarray(rng.normal(-8.0, 0.1, shape), jnp.float32),
        "value": jnp.asarray(rng.normal(0.0, 0.3, shape), jnp.float32),
        "advantage": jnp.asarray(rng.normal(size=shape), jnp.float32),
        "target": jnp.asarray(rng.normal(0.5, 0.3, shape), jnp.float32),
    }


@pytest.fixture(scope="module", params=[1, 2], ids=["1layer", "2layers"])
def model(request):
    return (request.param,) + _model(request.param)


def _close(got, want, tol=TOL):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol)


def _sets(index):
    return np.sort(np.asarray(index), axis=-1)


# --------------------------------------------------------------------------- #
# The expert layer that is told which experts it holds
# --------------------------------------------------------------------------- #


def _old_moe(x, router, gate, up, down, top_k):
    """networks/olmoe.py::moe as it was before it learnt of held experts."""
    tokens, num_experts = x.shape[0], router.shape[-1]
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, index = jax.lax.top_k(probs, top_k)
    flat = index.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    back = jnp.argsort(order)
    rows = olmoe._dispatch(x, order, back)
    experts = jnp.arange(num_experts, dtype=flat.dtype)
    counts = jnp.sum(flat[:, None] == experts[None, :], axis=0, dtype=jnp.int32)
    hidden = jax.nn.silu(jax.lax.ragged_dot(rows, gate, counts)) * jax.lax.ragged_dot(rows, up, counts)
    routed = jax.lax.ragged_dot(hidden, down, counts)
    pairs = olmoe._permute(routed, back, order).reshape(tokens, top_k, -1)
    return jnp.sum(pairs * weights[..., None].astype(pairs.dtype), axis=1), index, counts


def _olmoe_layer(seed):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(keys[0], (48, 64))
    router = jax.random.normal(keys[1], (64, 8))
    gate, up = (0.2 * jax.random.normal(k, (8, 64, 32)) for k in keys[2:4])
    down = 0.2 * jax.random.normal(keys[4], (8, 32, 64))
    return x, router, gate, up, down


@pytest.mark.parametrize("what", ["output", "gradient", "stats"])
def test_moe_with_the_defaults_is_bit_equal_to_what_it_was(what):
    """The OLMoE program passes neither `held` nor `renormalise`."""
    args = _olmoe_layer(0)
    if what == "gradient":
        new = jax.jit(jax.grad(lambda *a: jnp.sum(olmoe.moe(*a, 2)[0] ** 2), argnums=(0, 1, 2, 3, 4)))(*args)
        old = jax.jit(jax.grad(lambda *a: jnp.sum(_old_moe(*a, 2)[0] ** 2), argnums=(0, 1, 2, 3, 4)))(*args)
        for got, want in zip(new, old):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        return
    (out, stats), (old_out, old_index, old_counts) = jax.jit(lambda *a: olmoe.moe(*a, 2))(*args), jax.jit(
        lambda *a: _old_moe(*a, 2)
    )(*args)
    if what == "output":
        np.testing.assert_array_equal(np.asarray(out), np.asarray(old_out))
    else:
        np.testing.assert_array_equal(np.asarray(stats["expert_index"]), np.asarray(old_index))
        np.testing.assert_array_equal(np.asarray(stats["expert_count"]), np.asarray(old_counts))
        assert set(stats) == {"expert_index", "expert_count", "router_prob_sum", "router_entropy_sum"}


def _sdar_layer(seed, tokens=40, skew=0.0):
    """One layer's expert weights for all 16 experts; `skew` pushes the
    router towards experts 0..3."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(keys[0], (tokens, 64)) + 1.0
    router = (jax.random.normal(keys[1], (64, EXPERTS)) * 0.5).at[:, :4].add(skew / 8.0)
    gate, up = (0.2 * jax.random.normal(k, (EXPERTS, 64, 32)) for k in keys[2:4])
    down = 0.2 * jax.random.normal(keys[4], (EXPERTS, 32, 64))
    return x, {"router": router, "gate": gate, "up": up, "down": down}


def _held_moe(x, layer, offset, held):
    cut = lambda w: w[offset:offset + held]
    return olmoe.moe(
        x, layer["router"], cut(layer["gate"]), cut(layer["up"]), cut(layer["down"]), TOP_K,
        held=(offset, held), renormalise=True,
    )


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The held-experts layer run as ranks 0..7 (2 experts each), summed,
    is the reference's uncut layer: all 16 experts held."""
    x, layer = _sdar_layer(1)
    want, router = reference.moe(layer, x, _spec(1, held=EXPERTS))
    parts = [jax.jit(lambda x, layer, r=r: _held_moe(x, layer, 2 * r, 2))(x, layer) for r in range(8)]
    _close(sum(out for out, _ in parts), want)
    for out, stats in parts:  # every rank routes over all 16 and counts them all
        np.testing.assert_array_equal(_sets(stats["expert_index"]), _sets(router["index"]))
        assert int(jnp.sum(stats["expert_count"])) == x.shape[0] * TOP_K
    # and a rank's own part is the reference's for that rank
    cut = {k: (v if k == "router" else v[6:8]) for k, v in layer.items()}
    _close(parts[3][0], reference.moe(cut, x, _spec(1, held=2, offset=6))[0])


@pytest.mark.parametrize("skew", [0.0, 3.0], ids=["uniform", "skewed"])
def test_the_held_layer_drops_nothing_and_differentiates(skew):
    """However the router skews: with most pairs on the held experts the
    loop over chunks takes several turns, and output and gradients are still
    the reference's (a loop over the held experts on all tokens)."""
    x, layer = _sdar_layer(2, tokens=64, skew=skew)
    cut = {k: (v if k == "router" else v[:4]) for k, v in layer.items()}
    spec = _spec(1, held=4)
    out, stats = jax.jit(lambda x, layer: _held_moe(x, layer, 0, 4))(x, layer)
    held_pairs = int(jnp.sum(stats["expert_count"][:4]))
    if skew:
        assert held_pairs > 2 * x.shape[0]  # several chunks of 1.25 * N * k * 4 / 16 rows
    _close(out, reference.moe(cut, x, spec)[0])
    loss = lambda x, layer: jnp.sum(jnp.sin(_held_moe(x, layer, 0, 4)[0]))
    want_loss = lambda x, cut: jnp.sum(jnp.sin(reference.moe(cut, x, spec)[0]))
    got = jax.jit(jax.grad(loss, argnums=(0, 1)))(x, layer)
    want = jax.grad(want_loss, argnums=(0, 1))(x, cut)
    _close(got[0], want[0], 1e-4)
    for name in ("router", "gate", "up", "down"):
        leaf = got[1][name] if name == "router" else got[1][name][:4]
        _close(leaf, want[1][name], 1e-4)
        if name != "router":  # the absent experts' weights are not touched
            assert not np.any(np.asarray(got[1][name][4:]))


# --------------------------------------------------------------------------- #
# The block mask and the two entry points
# --------------------------------------------------------------------------- #


def test_the_block_mask_is_the_rule_enumerated():
    """A query in copy c, block b sees a key in copy c', block b' iff (c' = 0
    and b' < b) or (c' = c and b' = b): by brute force over every pair."""
    where = reference.layout(BLOCKS + 1, SIZE, PASSES)
    assert len(where["position"]) == SIZE + (1 + PASSES) * RESPONSE
    allowed = reference.block_mask(where["block"], where["copy"])
    elements = [(0, p) for p in range(SIZE + RESPONSE)] + [
        (c, p) for c in range(1, PASSES + 1) for p in range(SIZE, SIZE + RESPONSE)
    ]
    for q, (copy_q, pos_q) in enumerate(elements):
        assert where["position"][q] == pos_q and where["copy"][q] == copy_q
        for k, (copy_k, pos_k) in enumerate(elements):
            block_q, block_k = pos_q // SIZE, pos_k // SIZE
            want = (copy_k == 0 and block_k < block_q) or (copy_k == copy_q and block_k == block_q)
            assert bool(allowed[q, k]) == want, (q, k)
    # every query sees its own block, so no row is empty
    assert allowed.any(axis=1).all()


@pytest.mark.parametrize("output", ["logits", "values", "expert_index"])
def test_the_teacher_forced_pass_matches_the_plain_reference(model, output):
    """`trunk_copies` over [clean ; noisy copies] against the reference's
    forward under the explicit mask matrix, at every noisy position."""
    layers, nets, actor_params, critic_params = model
    batch = _record(3)
    copies = ff_sdar_ppo.record_copies(batch, PASSES)
    inputs = reference.record_inputs(batch, _spec(layers))
    where = reference.layout(BLOCKS + 1, SIZE, PASSES)
    want = reference.forward(
        actor_params, critic_params, inputs["tokens"], where["position"],
        reference.block_mask(where["block"], where["copy"]), _spec(layers),
    )
    clean = inputs["clean_length"]
    hidden, stats = jax.jit(nets.trunk_copies)(actor_params, copies["clean"], copies["noisy"])
    flat = hidden.reshape(hidden.shape[0], -1, hidden.shape[-1])
    if output == "logits":
        _close(nets.head(actor_params, flat), want["logits"][:, clean:])
    elif output == "values":
        _close(nets.value(critic_params, flat), want["values"][:, clean:])
    else:
        np.testing.assert_array_equal(_sets(stats["expert_index"]), _sets(want["expert_index"]))
        assert int(jnp.sum(stats["expert_count"])) == layers * inputs["tokens"].size * TOP_K


def test_the_update_keeps_a_few_sequences_scores_at_a_time(monkeypatch):
    """How many sequences' score matrices are live together changes what is
    live, not what is computed."""
    batch = _record(4)
    copies = ff_sdar_ppo.record_copies(batch, PASSES)
    nets, actor_params, _ = _model(2)
    outs = []
    for chunk in (1, 4):
        monkeypatch.setattr(sdar, "_ATTENTION_CHUNK", chunk)
        outs.append(jax.jit(nets.trunk_copies)(actor_params, copies["clean"], copies["noisy"])[0])
    _close(outs[0], outs[1])


@pytest.mark.parametrize("runs", [1, 2])
def test_the_updates_attention_in_runs_of_blocks_is_the_whole_rows(monkeypatch, runs):
    """The response blocks taken in fewer runs (one run: every query against
    all clean keys, as the mask matrix has it) give what one run a block gives."""
    batch = _record(4)
    copies = ff_sdar_ppo.record_copies(batch, PASSES)
    nets, actor_params, _ = _model(2)
    outs = []
    for groups in (BLOCKS, runs):
        monkeypatch.setattr(sdar, "_KEY_GROUPS", groups)
        outs.append(jax.jit(nets.trunk_copies)(actor_params, copies["clean"], copies["noisy"])[0])
    _close(outs[0], outs[1])


# --------------------------------------------------------------------------- #
# The update's attention as a Pallas kernel (ops/pallas_attention.py), through
# the interpreter: against the plain `_attend_copies`, which the tests above
# pin against the reference
# --------------------------------------------------------------------------- #

# (block length, copies, response, tile): a clean part of 36, 88 and 52
# positions is no whole number of tiles of 16, and 20 fills no tile of 128.
KERNEL_CASES = [(4, 1, 32, 16), (4, 2, 84, 16), (8, 1, 80, 16), (8, 2, 48, 16), (4, 2, 16, 128)]


@pytest.fixture(scope="module", params=KERNEL_CASES, ids=lambda c: "B%d-S%d-R%d-T%d" % c)
def kernel_and_plain(request):
    """Output and gradients (of a weighted sum) of both, at the tiny preset's
    heads: 4 query heads on 2 key/value heads of 16."""
    size, copies, response, tile = request.param
    clean = size + response
    positions = clean + copies * response
    model = sdar.SdarLM(
        vocab_size=VOCAB, hidden_size=64, num_heads=4, num_kv_heads=2, head_dim=16,
        num_experts=EXPERTS, experts_held=4, experts_per_token=TOP_K, expert_width=32,
        block_length=size,
    )
    keys = jax.random.split(jax.random.PRNGKey(size + copies), 4)
    q = jax.random.normal(keys[0], (2, positions, 4, 16))
    k, v = (jax.random.normal(key, (2, positions, 2, 16)) for key in keys[1:3])
    weights = jax.random.normal(keys[3], (2, positions, 64))
    plain = jax.vmap(lambda q, k, v: model._attend_copies(q, k, v, clean, copies))
    kernel = lambda q, k, v: pallas_attention.block_mask_attention(
        q, k, v, block_length=size, clean=clean, copies=copies, tile=tile, interpret=True
    )
    both = {}
    for name, attend in (("plain", plain), ("kernel", kernel)):
        loss = lambda q, k, v, attend=attend: jnp.sum(attend(q, k, v) * weights)
        grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
        both[name] = dict(zip(("output", "dq", "dk", "dv"), (jax.jit(attend)(q, k, v),) + grads))
    return both


@pytest.mark.parametrize("what", ["output", "dq", "dk", "dv"])
def test_the_block_kernel_is_the_plain_attention(kernel_and_plain, what):
    _close(kernel_and_plain["kernel"][what], kernel_and_plain["plain"][what])


@pytest.mark.parametrize("size,copies,response,tile", KERNEL_CASES + [(4, 2, 512, 128)])
def test_the_kernels_mask_is_the_rule_counted(size, copies, response, tile):
    """The numpy mask the kernels' tables are cut from allows B^2 (n + 1)(n +
    2) / 2 + S B^2 sum(b + 1) pairs of real positions (402,448 at the
    benchmark cell's shape), gives no padded position to a real query, and
    leaves no row empty; the tables list every tile with an allowed pair
    once (49 of 169 at the cell's shape)."""
    layout = pallas_attention.block_mask_layout(size, size + response, copies, tile)
    blocks, real = response // size, layout.positions
    want = size * size * ((blocks + 1) * (blocks + 2) // 2 + copies * sum(b + 1 for b in range(1, blocks + 1)))
    assert int(layout.mask[:real, :real].sum()) == want
    assert not layout.mask[:real, real:].any() and layout.mask.any(axis=1).all()
    where = reference.layout(blocks + 1, size, copies)
    np.testing.assert_array_equal(
        layout.mask[:real, :real], np.asarray(reference.block_mask(where["block"], where["copy"]))
    )
    by_tile = layout.mask.reshape(layout.tiles, tile, layout.tiles, tile).any(axis=(1, 3))
    assert layout.tiles_visited == int(by_tile.sum()) and layout.tiles_total == layout.tiles**2
    plan = layout.plan.reshape(layout.tiles, -1)
    listed = set()
    for i, row in enumerate(plan):
        at = lambda kind, width: row[layout.offsets[kind]:][:row[kind] * width].reshape(-1, width)
        tiles = [t for a in at(0, 1)[:, 0] for t in (a, a + 1)] + list(at(1, 1)[:, 0])
        tiles += list(at(2, 4)[:, [0, 2]].reshape(-1)) + list(at(3, 2)[:, 0])
        assert len(tiles) == len(set(tiles))
        listed |= {(i, int(t)) for t in tiles}
    assert listed == {(int(i), int(j)) for i, j in zip(*np.nonzero(by_tile))}
    if (size, copies, response, tile) == (4, 2, 512, 128):
        assert want == 402448 and (layout.tiles_visited, layout.tiles_total) == (49, 169)


def test_the_block_kernels_padded_queries_are_finite_dropped_and_without_cotangent():
    """The last tile's rows past the sequence's end hold whatever the
    interpreter (NaN) or the chip left there: nothing of them reaches a real
    row, forward or backward, the log-sum-exp rows they get are finite, and
    padded keys receive no gradient."""
    size, copies, response, tile = 4, 2, 24, 16
    layout = pallas_attention.block_mask_layout(size, size + response, copies, tile)
    real, padded = layout.positions, layout.padded
    assert padded - real == 4
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (2, real, 2, 2, 16))  # [n, P, kv heads, heads a kv head, hd]
    d_out = jax.random.normal(keys[1], (2, real, 64))
    k, v = (
        jnp.pad(jax.random.normal(key, (2, real, 32)), ((0, 0), (0, padded - real), (0, 0)))
        for key in keys[2:]
    )
    spec = ((size, size + response, copies, tile), 4, 2, True)
    out, lse = pallas_attention._block_mask_forward(q, k, v, spec)
    assert out.shape == d_out.shape
    assert bool(jnp.isfinite(out).all()) and bool(jnp.isfinite(lse).all())
    dq, dk, dv = pallas_attention._block_mask_backward(q, k, v, out, lse, d_out, spec)
    for grad in (dq, dk, dv):
        assert bool(jnp.isfinite(grad).all())
    assert dq.shape == q.shape
    np.testing.assert_array_equal(np.asarray(dk[:, real:]), 0.0)
    np.testing.assert_array_equal(np.asarray(dv[:, real:]), 0.0)


def _through_the_kernel(monkeypatch):
    """`trunk_copies` as a TPU runs it, the kernels through the interpreter."""
    monkeypatch.setattr(
        sdar.SdarLM, "copies_attention",
        lambda self, clean, copies: {"kernel": 1, "tiles_visited": 0, "tiles_total": 0},
    )
    monkeypatch.setattr(
        sdar, "block_mask_attention",
        lambda *args, **kwargs: pallas_attention.block_mask_attention(
            *args, **kwargs, tile=16, interpret=True
        ),
    )
    # and q's and k's norm and rotation through theirs (a TPU asks for heads
    # of whole lanes and a row tile a sequence; the interpreter for neither)
    monkeypatch.setattr(sdar, "norm_rope_form", lambda rows, head_dim: "kernel")
    monkeypatch.setattr(
        sdar, "qk_norm_rope",
        lambda *args, **kwargs: qk_norm_rope.qk_norm_rope(*args, **kwargs, interpret=True),
    )


@pytest.mark.parametrize("what", ["hidden", "gradient", "kernels"])
def test_the_teacher_forced_pass_through_the_kernel_is_the_plain_one(monkeypatch, what):
    """Off a TPU `trunk_copies` holds no `pallas_call`; told to take the
    kernel, its result and its gradient (through the rematerialised layers,
    whose policy keeps the kernel's residuals) are the plain path's, and a
    layer's backward pass runs the forward kernel no second time."""
    batch = _record(6, sequences=2)
    copies = ff_sdar_ppo.record_copies(batch, PASSES)
    nets, actor_params, _ = _model(2)

    def loss(params):
        hidden, _ = nets.trunk_copies(params, copies["clean"], copies["noisy"])
        return jnp.sum(jnp.sin(hidden))

    forward = lambda: jax.jit(nets.trunk_copies)(actor_params, copies["clean"], copies["noisy"])[0]
    jaxpr = lambda: str(jax.make_jaxpr(jax.grad(loss))(actor_params))
    plain = {"hidden": forward, "gradient": lambda: jax.jit(jax.grad(loss))(actor_params), "kernels": jaxpr}[what]()
    if what == "kernels":
        assert "pallas_call" not in plain
    _through_the_kernel(monkeypatch)
    if what == "kernels":
        text = jaxpr()
        assert text.count("block_mask_attention_fwd") == 2 and text.count("block_mask_attention_bwd") == 2
        # q and k of each of the two layers: forward, rematerialised, backward
        assert len(re.findall(r"name=qk_norm_rope\n", text)) == 8
        assert text.count("name=qk_norm_rope_bwd\n") == 4
    elif what == "hidden":
        _close(forward(), plain)
    else:
        got = jax.jit(jax.grad(loss))(actor_params)
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(plain)):
            scale = max(1.0, float(jnp.max(jnp.abs(b))))  # summation order, relative to the leaf
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5 * scale, err_msg=str(path)
            )


@pytest.mark.parametrize("output", ["log_probs", "value", "expert_index"])
def test_denoise_passes_through_the_cache_match_the_full_prefix_forward(model, output):
    """Block after block through `block_step` — the prompt's commit pass,
    then for every response block its denoise passes and its commit pass —
    against the reference's forward over the whole prefix, which has no
    cache. Logits, not tokens."""
    layers, nets, actor_params, critic_params = model
    spec, batch = _spec(layers), _record(5)
    sequences = batch["prompt"].shape[0]
    by_pass = lambda x: np.asarray(x).reshape(sequences, BLOCKS, PASSES, SIZE)
    before, commit, token = by_pass(batch["block"]), by_pass(batch["commit"]), by_pass(batch["token"])
    step = jax.jit(nets.block_step, static_argnums=4)
    cache = step(actor_params, nets.init_cache(sequences), batch["prompt"], 0, True)[1]
    prefix = np.asarray(batch["prompt"])
    for b in range(BLOCKS):
        for s in range(PASSES):
            hidden, same, stats = step(actor_params, cache, jnp.asarray(before[:, b, s]), b + 1, False)
            for kept, was in zip(jax.tree.leaves(same), jax.tree.leaves(cache)):  # nothing written
                np.testing.assert_array_equal(np.asarray(kept), np.asarray(was))
            want = reference.denoise_pass(
                actor_params, critic_params, jnp.asarray(prefix), jnp.asarray(before[:, b, s]), spec
            )
            if output == "log_probs":
                got = reference.policy_log_probs(nets.head(actor_params, hidden), spec)
                finite = np.isfinite(np.asarray(want["log_probs"]))
                assert not finite[..., VOCAB - 1].any() and finite[..., :-1].all()
                _close(jnp.where(finite, got, 0.0), jnp.where(finite, want["log_probs"], 0.0))
            elif output == "value":
                _close(ff_sdar_ppo.block_value(nets, critic_params, hidden), want["value"])
            else:
                np.testing.assert_array_equal(_sets(stats["expert_index"]), _sets(want["expert_index"]))
        final = np.where(commit[:, b, -1], token[:, b, -1], before[:, b, -1])
        cache = step(actor_params, cache, jnp.asarray(final), b + 1, True)[1]
        prefix = np.concatenate([prefix, final], axis=1)


# --------------------------------------------------------------------------- #
# The rollout, the loss, the env, the evaluator
# --------------------------------------------------------------------------- #


def _env(sequences):
    config = config_lib.compose(
        config_lib.default_config_dir(), "default/anakin/default_ff_sdar_ppo.yaml",
        TINY + [f"arch.total_num_envs={sequences}"],
    )
    return envs.make(config)


@pytest.fixture(scope="module")
def rolled():
    """One rollout of 8 sequences from a reset, two layers."""
    nets, actor_params, critic_params = _model(2)
    env, _ = _env(8)
    env_state, timestep = env.reset(jax.random.split(jax.random.PRNGKey(11), 8))
    params = ActorCriticParams(actor_params, critic_params)
    out = jax.jit(lambda p, k, s, t: ff_sdar_ppo.rollout(env, nets, p, k, s, t))(
        params, jax.random.PRNGKey(12), env_state, timestep
    )
    batch = {
        name: jnp.swapaxes(getattr(out.traj, name), 0, 1)
        for name in ("block", "commit", "token", "log_prob", "value", "reward")
    }
    batch["prompt"] = out.prompt
    return nets, params, out, batch


@pytest.mark.parametrize("what", ["log_prob", "value"])
def test_stored_and_recomputed_agree_at_unchanged_parameters(rolled, what):
    """What ties the two entry points together: the clean copy's keys and
    values are what the commit passes cached, copy s of block b sees what
    denoise pass s saw, so the update's ratio starts at one."""
    nets, params, _, batch = rolled
    new = jax.jit(lambda p, b: ff_sdar_ppo.teacher_forced(nets, p, b))(params, batch)
    _close(new[what], batch[what])


def test_the_rollout_keeps_the_schedule(rolled):
    """Every block starts as masks, a pass commits B / S of the masked
    positions — those of largest confidence among them —, and after the last
    pass nothing is masked; the stored log-prob is the commit set's."""
    nets, params, out, batch = rolled
    mask = VOCAB - 1
    by_pass = lambda x: np.asarray(x).reshape(8, BLOCKS, PASSES, SIZE)
    before, commit, token = by_pass(batch["block"]), by_pass(batch["commit"]), by_pass(batch["token"])
    assert (before[:, :, 0] == mask).all()
    assert (commit.sum(axis=-1) == SIZE // PASSES).all() and (commit.sum(axis=2) == 1).all()
    assert (commit <= (before == mask)).all() and (token != mask).all()
    np.testing.assert_array_equal(before[:, :, 1], np.where(commit[:, :, 0], token[:, :, 0], mask))
    copies = ff_sdar_ppo.record_copies(batch, PASSES)
    assert (np.asarray(copies["clean"]) != mask).all()
    # the reference's confidences at the sampled tokens pick the same sets
    spec = _spec(2)
    prefix = np.asarray(copies["clean"])
    for b in range(BLOCKS):
        for s in range(PASSES):
            want = reference.denoise_pass(
                params.actor_params, params.critic_params, jnp.asarray(prefix[:, :SIZE * (b + 1)]),
                jnp.asarray(before[:, b, s]), spec,
            )
            picked = jnp.take_along_axis(want["log_probs"], jnp.asarray(token[:, b, s])[..., None], axis=-1)[..., 0]
            chosen = reference.commit_set(jnp.exp(picked), jnp.asarray(before[:, b, s] == mask), SIZE // PASSES)
            np.testing.assert_array_equal(np.asarray(chosen), commit[:, b, s])
            stored = np.asarray(batch["log_prob"]).reshape(8, BLOCKS, PASSES)[:, b, s]
            _close(stored, jnp.sum(jnp.where(chosen, picked, 0.0), axis=-1))
    assert float(jnp.min(out.confidence)) > 0.0
    assert int(jnp.sum(out.routed)) == 2 * 8 * SIZE * (1 + BLOCKS * (PASSES + 1)) * TOP_K


def test_the_commit_set_breaks_ties_towards_the_lower_position():
    logits = jnp.zeros((1, SIZE, VOCAB))  # every confidence equal
    block = jnp.full((1, SIZE), VOCAB - 1, jnp.int32)
    choice = ff_sdar_ppo.choose(logits, block, VOCAB - 1, 2, None)
    np.testing.assert_array_equal(np.asarray(choice.commit), [[True, True, False, False]])
    again = ff_sdar_ppo.choose(logits, choice.block, VOCAB - 1, 2, None)
    np.testing.assert_array_equal(np.asarray(again.commit), [[False, False, True, True]])
    want = reference.commit_set(jnp.ones((1, SIZE)), block == VOCAB - 1, 2)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(choice.commit))
    assert (np.asarray(again.block) != VOCAB - 1).all()


@pytest.fixture(scope="module")
def losses_and_grads(rolled):
    nets, params, out, batch = rolled
    rng = np.random.default_rng(5)
    batch = dict(batch)
    # parameters moved since the rollout, so that ratio, clip and value clip all bite
    moved = jax.tree.map(lambda w: w * (1.0 + 0.05 * rng.standard_normal(w.shape).astype(np.float32)), params)
    batch["advantage"] = jnp.asarray(rng.normal(size=batch["value"].shape), jnp.float32)
    batch["target"] = jnp.asarray(rng.normal(0.5, 0.3, batch["value"].shape), jnp.float32)
    batch.pop("reward")
    got_grads, got_info = jax.jit(
        lambda p, b: jax.grad(ff_sdar_ppo.sdar_ppo_loss, argnums=1, has_aux=True)(nets, p, b, **HYPER)
    )(moved, batch)
    _, want_parts, want_grads = reference.ppo_loss_and_grads(
        (moved.actor_params, moved.critic_params), batch, _spec(2), HYPER
    )
    return got_info, (got_grads.actor_params, got_grads.critic_params), want_parts, want_grads


@pytest.mark.parametrize("part", [
    "total_loss", "actor_loss", "value_loss", "entropy", "aux_loss", "expert_load_max_over_mean",
    "routed_pairs_per_token", "held_pairs_per_token",
])
def test_the_loss_parts_match_the_reference(losses_and_grads, part):
    got, _, want, _ = losses_and_grads
    _close(got[part], want[part])


def test_the_gradients_match_jax_grad_of_the_reference_loss(losses_and_grads):
    _, got, _, want = losses_and_grads
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, leaf), ref in zip(flat_got, flat_want):
        scale = max(1e-3, float(jnp.max(jnp.abs(ref))))
        np.testing.assert_allclose(
            np.asarray(leaf) / scale, np.asarray(ref) / scale, atol=2e-4, err_msg=str(path)
        )
    assert any(float(jnp.max(jnp.abs(g))) > 1e-4 for g in jax.tree.leaves(want))


def test_the_block_token_tasks_reward_is_the_python_loop():
    env = BlockTokenTask(vocab_size=VOCAB, length=RESPONSE, block_length=SIZE, passes=PASSES, modulus=2)
    assert env.episode_steps == STEPS and env.mask_id == VOCAB - 1
    rng = np.random.default_rng(0)
    state, timestep = jax.jit(env.reset)(jax.random.PRNGKey(3))
    prompt = np.asarray(state.prompt)
    assert (prompt < VOCAB - 1).all()
    np.testing.assert_array_equal(np.asarray(timestep.observation.agent_view)[:SIZE], VOCAB - 1)
    np.testing.assert_array_equal(np.asarray(timestep.observation.agent_view)[SIZE:2 * SIZE], prompt)
    response, step = [], jax.jit(env.step)
    for b in range(BLOCKS):
        final = rng.integers(0, VOCAB - 1, SIZE)
        if b == 2:
            final[1] = VOCAB - 1  # a position left masked is a miss
        half = np.where(np.arange(SIZE) % 2 == 0, final, VOCAB - 1)
        for s, action in enumerate((half, final)):
            view = np.asarray(timestep.observation.agent_view)
            assert view[2 * SIZE] == b and view[2 * SIZE + 1] == s
            state, timestep = step(state, jnp.asarray(action, jnp.int32))
            last = b == BLOCKS - 1 and s == PASSES - 1
            assert bool(timestep.last()) == last
            if not last:
                assert float(timestep.reward) == 0.0
        response.extend(final.tolist())
    matches, before = 0, int(prompt[-1])
    for tok in response:
        matches += int(tok != VOCAB - 1 and tok % 2 == before % 2)
        before = tok
    assert float(timestep.reward) == pytest.approx(matches / RESPONSE)
    assert float(timestep.discount) == 0.0


def test_the_greedy_evaluators_sequence_is_the_references(rolled):
    """The evaluator's act function — prompt commit at the first call, a
    denoise pass a call, a commit every S-th — against greedy block-diffusion
    decoding written with the reference's full-prefix forward."""
    nets, params, _, _ = rolled
    _, eval_env = _env(4)
    act = jax.jit(ff_sdar_ppo.make_act_fn(nets, SIZE, greedy=True))
    state, timestep = jax.vmap(eval_env.reset)(jax.random.split(jax.random.PRNGKey(21), 4))
    prompt = np.asarray(timestep.observation.agent_view[:, SIZE:2 * SIZE])
    cache, blocks = nets.init_cache(4), []
    for t in range(STEPS):
        cache, action = act(params.actor_params, cache, timestep.observation, timestep.last(), None)
        state, timestep = jax.vmap(eval_env.step)(state, action)
        if t % PASSES == PASSES - 1:
            blocks.append(np.asarray(action))
    assert bool(jnp.all(timestep.last()))
    got = np.concatenate(blocks, axis=1)
    spec, mask = _spec(2), VOCAB - 1
    prefix = prompt
    for _ in range(BLOCKS):
        block = np.full((4, SIZE), mask, np.int32)
        for _ in range(PASSES):
            out = reference.denoise_pass(
                params.actor_params, params.critic_params, jnp.asarray(prefix), jnp.asarray(block), spec
            )
            token = jnp.argmax(out["log_probs"], axis=-1)
            conf = jnp.exp(jnp.max(out["log_probs"], axis=-1))
            chosen = reference.commit_set(conf, jnp.asarray(block == mask), SIZE // PASSES)
            block = np.where(np.asarray(chosen), np.asarray(token), block)
        prefix = np.concatenate([prefix, block], axis=1)
    np.testing.assert_array_equal(got, prefix[:, SIZE:])
    returns = np.asarray(timestep.extras["episode_metrics"]["episode_return"])
    before = np.concatenate([prompt[:, -1:], got[:, :-1]], axis=1)
    _close(returns, np.mean(got % 2 == before % 2, axis=1))


# --------------------------------------------------------------------------- #
# The program: scopes, a short run, the benchmark's copy of the reference
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def learner_scopes(devices):
    from stoix_tpu.parallel import MeshRoles
    from stoix_tpu.utils.timestep_checker import check_total_timesteps

    config = config_lib.compose(
        config_lib.default_config_dir(), "default/anakin/default_ff_sdar_ppo.yaml",
        TINY + ["arch.total_num_envs=32"],
    )
    mesh = MeshRoles.from_config(config).learn_mesh()
    config = check_total_timesteps(config, int(mesh.shape["data"]))
    env, _ = envs.make(config)
    setup = ff_sdar_ppo.learner_setup(env, config, mesh, jax.random.PRNGKey(0))
    hlo = setup.learn.lower(setup.learner_state).compile().as_text()
    strip = lambda part: re.sub(r"^(?:\w+\()+|\)+$", "", part)
    paths = [[strip(p) for p in path.split("/")] for path in re.findall(r'op_name="([^"]+)"', hlo)]
    return {
        phase: {part for path in paths if SCOPES[phase] in path for part in path}
        for phase in ("rollout", "update_epoch")
    }


@pytest.mark.parametrize("phase,scope", [
    ("rollout", s) for s in BLOCK_SCOPES + DIFFUSION_SCOPES + ("rollout_policy", "rollout_env")
] + [("update_epoch", s) for s in BLOCK_SCOPES + ("attention_scores", "update_minibatch", "minibatch_shuffle")])
def test_the_compiled_learner_carries_the_scopes(learner_scopes, phase, scope):
    assert SCOPES[scope] in learner_scopes[phase]


def test_a_short_run_learns_the_block_token_task(devices):
    """Through `run_experiment`, the path `main()` takes. The sampled
    rollouts' return rises well above the untrained 0.5, every window logs
    the share's counters, and the record rides out with the episode metrics."""
    from stoix_tpu.utils.logger import LogEvent, StoixLogger

    logged = {LogEvent.TRAIN: [], LogEvent.ACT: []}
    original = StoixLogger.log

    def log(self, metrics, t, t_eval, event):
        if event in logged:
            logged[event].append(metrics)
        return original(self, metrics, t, t_eval, event)

    StoixLogger.log = log
    try:
        config = config_lib.compose(
            config_lib.default_config_dir(), "default/anakin/default_ff_sdar_ppo.yaml",
            TINY + [
                "arch.total_num_envs=64", "arch.num_updates=40", "arch.num_evaluation=4",
                "system.actor_lr=3e-3", "system.critic_lr=3e-3", "system.epochs=2",
                "arch.evaluation_greedy=True",
            ],
        )
        final = ff_sdar_ppo.run_experiment(config)
    finally:
        StoixLogger.log = original
    assert np.isfinite(final)
    returns = [float(np.mean(m["episode_return"])) for m in logged[LogEvent.ACT]]
    assert returns[0] < 0.6 and returns[-1] > 0.75, returns
    for record in logged[LogEvent.TRAIN]:
        assert float(np.mean(record["routed_pairs_per_token"])) == pytest.approx(TOP_K)
        assert float(np.mean(record["rollout_routed_pairs_per_token"])) == pytest.approx(TOP_K)
        assert 0.0 < float(np.mean(record["held_pairs_per_token"])) < TOP_K
        assert float(np.mean(record["decode_passes_per_token"])) == pytest.approx(
            (1 + BLOCKS * (PASSES + 1)) / RESPONSE
        )
        assert float(np.mean(record["tokens_per_denoise_pass"])) == pytest.approx(SIZE / PASSES)
    episode = logged[LogEvent.ACT][-1]
    assert np.asarray(episode["rollout_block"]).shape[-1] == SIZE
    assert {"rollout_commit", "rollout_token", "rollout_log_prob", "rollout_value"} <= set(episode)
    # Which way the update's attention went is on the run's record: off a TPU
    # the plain products, and the tiles the kernel would have visited.
    layout = pallas_attention.block_mask_layout(SIZE, SIZE + RESPONSE, PASSES)
    want = {"kernel": 0, "tiles_visited": layout.tiles_visited, "tiles_total": layout.tiles_total}
    assert ff_sdar_ppo.LAST_RUN_STATS["update_attention"] == want
    gauge = get_registry().gauge("stoix_tpu_sdar_update_attention")
    assert {dict(labels)["field"]: int(value) for labels, value in gauge.labels_and_values()} == want
    # and which way q's and k's norm and rotation: `rms_norm` + `rope` off a TPU
    forms = get_registry().gauge("stoix_tpu_qk_norm_rope").labels_and_values()
    assert {dict(labels)["form"]: value for labels, value in forms} == {"kernel": 0.0, "plain": 1.0}
    # and the held experts' SwiGLU of a denoise pass: the `ragged_dot`s off a TPU
    forms = get_registry().gauge("stoix_tpu_held_swiglu_form").labels_and_values()
    assert {dict(labels)["form"]: value for labels, value in forms} == {"kernel": 0.0, "ragged_dot": 1.0}


def test_the_benchmark_keeps_a_copy_of_the_reference(model):
    """benchmarks/references/ppo_sdar.py carries its own copy of the plain
    forward and loss (it may import nothing of the program): the functions
    the two share have the same source, and agree exactly."""
    import inspect

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.harness import loader

    copy = loader.load_reference("ppo_sdar")
    for name in (
        "rms_norm", "_rotate_half", "_rope", "layout", "block_mask", "attention", "moe",
        "layer_forward", "forward", "policy_log_probs", "denoise_pass", "commit_set",
        "record_inputs", "loss_sums", "loss_of_sums",
    ):
        assert inspect.getsource(getattr(copy, name)) == inspect.getsource(getattr(reference, name)), name
    layers, _, actor_params, critic_params = model
    batch = _record(6)
    hyper, spec = HYPER, _spec(layers)
    want = reference.loss_sums((actor_params, critic_params), batch, spec, hyper)
    got = copy.loss_sums((actor_params, critic_params), batch, spec, hyper)
    for key in want:
        np.testing.assert_array_equal(np.asarray(got[key]), np.asarray(want[key]))
