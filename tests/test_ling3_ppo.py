"""The Ling-3.0-flash hybrid token policy (networks/kda.py's delta-rule mixer
over ops/delta_rule.py, networks/mla.py's mixer with its head-wise gate, the
group-limited router of networks/olmoe.py, all on networks/lfm2.py's stack,
systems/ppo/anakin/ff_lm_ppo.py with `network=ling3_flash_moe`) against its
plain reference (reference/ling3.py), at a tiny preset on the CPU: hidden 64,
five delta-attention layers to one latent-attention layer (one dense
feed-forward of width 96, then five routed ones), 4 heads of 16 with 4-tap
convolutions, the latent layer 16 + 8 rotated | 12 over a latent of 24, 32
experts in 4 groups of which the 2 best are open, top-3 of width 32 of which a
rank holds 4 (8 ranks) beside one shared expert, vocabulary 64, L = 20 (a
chunk of 16 and a remainder). Tolerance 1e-5 throughout: both sides are
float32 on the CPU and differ only in summation order."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stoix_tpu import envs
from stoix_tpu.base_types import ActorCriticParams
from stoix_tpu.networks import kda, lfm2, mla, olmoe
from stoix_tpu.observability import BLOCK_SCOPES, DELTA_SCOPES, LATENT_SCOPES, SCOPES, get_registry
from stoix_tpu.ops import delta_rule
from stoix_tpu.reference import ling3 as reference
from stoix_tpu.systems.ppo.anakin import ff_lm_ppo
from stoix_tpu.utils import config as config_lib

TOL = 1e-5
VOCAB, LENGTH, LAYERS, PERIOD = 64, 20, 6, 6
KINDS = ["delta_attention"] * 5 + ["latent_attention"]
EXPERTS, HELD, TOP_K, RANKS, GROUPS, TOP_GROUPS = 32, 4, 3, 8, 4, 2
HEADS, HEAD_DIM, RANK, NOPE, ROPE, V_DIM, TAPS = 4, 16, 24, 16, 8, 12, 4
SCALING, LOWER = 2.5, -5.0
TINY = [
    "network=ling3_flash_moe",
    "network.actor_network.hidden_size=64", "network.actor_network.dense_width=96",
    f"network.actor_network.num_heads={HEADS}", f"network.actor_network.num_kv_heads={HEADS}",
    f"network.actor_network.head_dim={HEAD_DIM}", f"network.actor_network.kv_lora_rank={RANK}",
    f"network.actor_network.qk_nope_head_dim={NOPE}", f"network.actor_network.qk_rope_head_dim={ROPE}",
    f"network.actor_network.v_head_dim={V_DIM}", f"network.actor_network.num_experts={EXPERTS}",
    f"network.actor_network.experts_held={HELD}", f"network.actor_network.experts_per_token={TOP_K}",
    f"network.actor_network.n_group={GROUPS}", f"network.actor_network.topk_group={TOP_GROUPS}",
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
        "layer_group_size": PERIOD, "num_attention_heads": HEADS, "head_dim": HEAD_DIM,
        "kda_lower_bound": LOWER, "kv_lora_rank": RANK, "qk_nope_head_dim": NOPE,
        "qk_rope_head_dim": ROPE, "v_head_dim": V_DIM, "num_experts": held,
        "expert_offset": offset, "num_experts_per_tok": TOP_K, "n_group": GROUPS,
        "topk_group": TOP_GROUPS, "rms_norm_eps": 1e-6, "rope_theta": 6000000.0,
        "routed_scaling_factor": SCALING, **extra,
    }


def _actor(held=HELD, offset=0, vocab=VOCAB, **extra):
    return lfm2.Lfm2LM(
        vocab_size=vocab, hidden_size=64, layer_types=KINDS, num_dense_layers=1, dense_width=96,
        num_heads=HEADS, num_kv_heads=HEADS, head_dim=HEAD_DIM, conv_kernel=TAPS,
        kda_lower_bound=LOWER, kv_lora_rank=RANK, qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPE,
        v_head_dim=V_DIM, attention_gate=True, num_experts=EXPERTS, experts_held=held,
        expert_offset=offset, experts_per_token=TOP_K, expert_width=32, n_shared_experts=1,
        n_group=GROUPS, topk_group=TOP_GROUPS, routed_scaling_factor=SCALING,
        router_epsilon=1e-20, expert_bias_scale=0.05, tie_word_embeddings=False,
        rope_theta=6000000.0, rms_eps=1e-6, **extra,
    )


def _model(held=HELD, offset=0, **extra):
    actor, critic = _actor(held, offset, **extra), olmoe.ValueHead()
    key = jax.random.PRNGKey(6)
    actor_params = actor.init(key, jnp.zeros((1, 2), jnp.int32), method="forward")
    # normal(0.02) leaves every router near uniform and every gate at a half;
    # scale the weights up so that routing, the decays, the write strengths,
    # the rotation and the gates all matter.
    actor_params = jax.tree.map(lambda w: w * 8.0 if w.ndim > 1 else w, actor_params)
    critic_params = jax.tree.map(lambda w: w + 0.1, critic.init(key, jnp.zeros((1, 2, 64))))
    tokens = jax.random.randint(jax.random.PRNGKey(7), (4, LENGTH), 0, VOCAB)
    return ff_lm_ppo.network_functions(actor, critic, LENGTH), actor_params, critic_params, tokens


@pytest.fixture(scope="module")
def model():
    return _model()


def _reference_forward(actor_params, critic_params, tokens, spec):
    """`reference.forward`, compiled (eagerly its position-by-position scan is
    traced anew at every call)."""
    return jax.jit(lambda a, c, t: reference.forward(a, c, t, spec))(actor_params, critic_params, tokens)


def _close(got, want, tol=TOL):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol)


def _sets(index):
    return np.sort(np.asarray(index), axis=-1)


@pytest.mark.parametrize(
    "output", ["logits", "values", "expert_index", "bias_changed", "group_limited_changed"]
)
def test_forward_matches_the_plain_reference(model, output):
    """The chunked update against the position-by-position recurrence."""
    nets, actor_params, critic_params, tokens = model
    want = _reference_forward(actor_params, critic_params, tokens, _spec())
    logits, hidden, stats = jax.jit(nets.forward)(actor_params, tokens)
    if output == "logits":
        _close(logits, want["logits"])
    elif output == "values":
        _close(nets.value(critic_params, hidden), want["values"])
    elif output == "expert_index":  # the chosen expert SETS are identical, layer by layer
        assert stats["expert_index"].shape == (LAYERS - 1, tokens.size, TOP_K)
        assert (_sets(stats["expert_index"]) == _sets(want["expert_index"])).all()
        assert int(stats["expert_count"].sum()) == (LAYERS - 1) * tokens.size * TOP_K
    else:  # what the bias, and what the group limit, re-routed: counted alike, and not nothing
        other = "plain_index" if output == "bias_changed" else "ungrouped_index"
        name = "bias_changed_sum" if output == "bias_changed" else "group_changed_sum"
        changed = np.any(_sets(want["expert_index"]) != _sets(want[other]), axis=-1)
        assert stats[name].tolist() == changed.sum(axis=-1).tolist()
        assert 0 < changed.sum() < changed.size


@pytest.fixture(scope="module", params=[True, False], ids=["one_position", "a_position_a_sequence"])
def decoded(request, model):
    """LENGTH steps from zero matrix states, empty tails and an empty latent
    cache, with `length` together or apart: what every step gave."""
    nets, actor_params, critic_params, tokens = model
    carry = _actor().init_carry(tokens.shape[0], LENGTH, together=request.param)

    def one(carry, token):
        logits, hidden, carry, _ = nets.step(actor_params, carry, token)
        return carry, (logits, nets.value(critic_params, hidden), carry.length)

    _, (logits, values, lengths) = jax.jit(lambda c: jax.lax.scan(one, c, tokens.T))(carry)
    return logits, values, lengths, request.param


@pytest.mark.parametrize("prefix", [1, 2, 3, 7, 15, 16, 17, LENGTH])
def test_decoding_through_the_matrix_state_is_the_reference_forward_of_every_prefix(
    model, decoded, prefix
):
    """`prefix` steps give, at the last of them, what the reference's whole
    forward of the first `prefix` tokens — the recurrence from S_0 = 0, no
    cache — gives at its last position; either side of a chunk's boundary
    too."""
    _, actor_params, critic_params, tokens = model
    logits, values, lengths, together = decoded
    want = _reference_forward(actor_params, critic_params, tokens[:, :prefix], _spec())
    _close(logits[prefix - 1], want["logits"][:, -1])
    _close(values[prefix - 1], want["values"][:, -1])
    assert lengths[prefix - 1].shape == (() if together else (tokens.shape[0],))
    assert (np.asarray(lengths[prefix - 1]) == prefix).all()


# --------------------------------------------------------------------------- #
# The recurrence's three forms
# --------------------------------------------------------------------------- #


def _recurrence_inputs(length=37, seed=0, batch=2, heads=3, d=8):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(keys[0], (batch, length, heads, d))) / np.sqrt(d)
    k = unit(jax.random.normal(keys[1], (batch, length, heads, d)))
    v = jax.random.normal(keys[2], (batch, length, heads, d))
    g = LOWER * jax.nn.sigmoid(jax.random.normal(keys[3], (batch, length, heads, d)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (batch, length, heads)))
    return q, k, v, g, beta


@pytest.mark.parametrize("length", [37, 16, 5])
@pytest.mark.parametrize("form", ["chunked", "one_token"])
def test_the_three_forms_of_the_delta_rule_give_one_result(form, length):
    """Chunks of 16 (a length that is no multiple of it, one that is, one
    under it) and token by token through `delta_rule_step`, against the
    sequential scan and the reference's own recurrence: outputs and the state
    they leave."""
    args = _recurrence_inputs(length)
    want, want_state = delta_rule.delta_rule_scan(*args)
    _close(want, reference.delta_rule(*args))
    if form == "chunked":
        got, state = jax.jit(delta_rule.delta_rule_chunked)(*args)
    else:
        state, outs = jnp.zeros_like(want_state), []
        for t in range(length):
            out, state = delta_rule.delta_rule_step(state, *(x[:, t] for x in args))
            outs.append(out)
        got = jnp.stack(outs, axis=1)
    _close(got, want)
    _close(state, want_state)
    assert float(jnp.abs(want).max()) > 0.05  # (a recurrence that holds something)


@pytest.mark.parametrize("batch,heads", [(2, 8), (3, 16)])
def test_the_decode_kernel_is_the_plain_step(batch, heads):
    """`delta_rule_step_kernel` (through the Pallas interpreter; heads of 128,
    eight a grid step) against the plain sums: the output and the state it
    writes in the old one's place."""
    keys = jax.random.split(jax.random.PRNGKey(1), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    vector = lambda key: jax.random.normal(key, (batch, heads, 128))
    state = jax.random.normal(keys[0], (batch, heads, 128, 128))
    q, k, v = unit(vector(keys[1])), unit(vector(keys[2])), vector(keys[3])
    g = LOWER * jax.nn.sigmoid(vector(keys[4]))
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (batch, heads)))
    want, want_state = delta_rule.delta_rule_step_plain(state, q, k, v, g, beta)
    got, got_state = delta_rule.delta_rule_step_kernel(state, q, k, v, g, beta, interpret=True)
    _close(got, want)
    _close(got_state, want_state)
    # off a TPU, and for a state that is no whole tile, `delta_rule_step` is the plain sums
    np.testing.assert_array_equal(
        np.asarray(delta_rule.delta_rule_step(state, q, k, v, g, beta)[1]), np.asarray(want_state)
    )


def test_the_chunked_form_carries_a_state_in_and_out():
    """Two halves, the second starting from the state the first left, are
    the whole."""
    args = _recurrence_inputs(40)
    want, want_state = delta_rule.delta_rule_scan(*args)
    chunked = jax.jit(delta_rule.delta_rule_chunked)
    first, state = chunked(*(x[:, :23] for x in args))
    second, state = chunked(*(x[:, 23:] for x in args), state=state)
    _close(jnp.concatenate([first, second], axis=1), want)
    _close(state, want_state)


@pytest.mark.parametrize("argument", ["q", "k", "v", "g", "beta"])
def test_the_chunked_forms_gradient_is_jax_grad_of_the_sequential_one(argument):
    args = _recurrence_inputs(37)
    at = ["q", "k", "v", "g", "beta"].index(argument)
    loss = lambda form: lambda *a: jnp.sum(jnp.sin(form(*a)[0])) + jnp.sum(form(*a)[1] ** 2)
    got = jax.jit(jax.grad(loss(delta_rule.delta_rule_chunked), argnums=at))(*args)
    want = jax.grad(loss(delta_rule.delta_rule_scan), argnums=at)(*args)
    assert float(jnp.abs(want).max()) > 0.1
    _close(got, want, tol=2e-5)


def test_the_chunked_form_is_finite_at_the_lower_bound_on_every_channel():
    """g = -5 on every channel of every position: exp(-G) reaches exp(80)
    inside a chunk of 16, which float32 holds; outputs and gradients are
    finite and the scan's."""
    q, k, v, g, beta = _recurrence_inputs(37)
    g = jnp.full_like(g, LOWER)
    got, state = jax.jit(delta_rule.delta_rule_chunked)(q, k, v, g, beta)
    want, want_state = delta_rule.delta_rule_scan(q, k, v, g, beta)
    assert bool(jnp.isfinite(got).all())
    _close(got, want)
    _close(state, want_state)
    grads = jax.jit(jax.grad(
        lambda *a: jnp.sum(delta_rule.delta_rule_chunked(*a)[0] ** 2), argnums=(0, 1, 2, 3, 4)
    ))(q, k, v, g, beta)
    assert all(bool(jnp.isfinite(x).all()) for x in grads)
    assert delta_rule.CHUNK * -LOWER < 88.0  # float32's largest exponent


# The update's kernel pair, through the Pallas interpreter: 8 heads of 128, one
# sequence (a grid step is a chunk of 8 heads).
KERNEL_HEADS, KERNEL_DIM = 8, 128


def _kernel_inputs(length, seed=4):
    q, k, v, g, beta = _recurrence_inputs(length, seed, batch=1, heads=KERNEL_HEADS, d=KERNEL_DIM)
    state = 0.1 * jax.random.normal(jax.random.PRNGKey(seed + 1), (1, KERNEL_HEADS, KERNEL_DIM, KERNEL_DIM))
    return q, k, v, g, beta, state


def _close_in_scale(got, want, tol=2e-5):
    """Within `tol` of the largest entry: sums of 128 and more products in
    another order."""
    scale = float(jnp.abs(want).max())
    assert scale > 0.01
    np.testing.assert_allclose(np.asarray(got) / scale, np.asarray(want) / scale, rtol=0, atol=tol)


@pytest.mark.parametrize("start", ["from_zeros", "from_a_state"])
@pytest.mark.parametrize("chunks", [1, 2, 3])
def test_the_update_kernel_is_the_sequential_recurrence(chunks, start):
    """`delta_rule_update_kernel` at its own chunk size over one, two and
    three chunks, from zeros and from a state: outputs and the state it
    leaves against `delta_rule_scan`."""
    *args, state = _kernel_inputs(chunks * delta_rule.UPDATE_CHUNK)
    state = state if start == "from_a_state" else None
    want, want_state = jax.jit(delta_rule.delta_rule_scan)(*args, state)
    got, got_state = delta_rule.delta_rule_update_kernel(*args, state, interpret=True)
    _close_in_scale(got, want)
    _close_in_scale(got_state, want_state)


@pytest.fixture(scope="module")
def kernel_gradients():
    """By q, k, v, g, beta and the starting state, of a loss over the outputs
    and the state left, two chunks: the kernel pair's and `jax.grad` of the
    sequential recurrence's."""
    args = _kernel_inputs(2 * delta_rule.UPDATE_CHUNK)
    loss = lambda form: lambda *a: jnp.sum(jnp.sin(8.0 * form(*a)[0])) + jnp.sum(form(*a)[1] ** 2)
    kernel = lambda *a: delta_rule.delta_rule_update_kernel(*a, interpret=True)
    got = jax.jit(jax.grad(loss(kernel), argnums=range(6)))(*args)
    want = jax.jit(jax.grad(loss(delta_rule.delta_rule_scan), argnums=range(6)))(*args)
    return dict(zip(["q", "k", "v", "g", "beta", "state"], zip(got, want)))


@pytest.mark.parametrize("argument", ["q", "k", "v", "g", "beta", "state"])
def test_the_update_kernels_gradient_is_jax_grad_of_the_sequential_one(kernel_gradients, argument):
    got, want = kernel_gradients[argument]
    assert got.shape == want.shape
    _close_in_scale(got, want)


def test_the_update_kernel_is_finite_at_the_lower_bound_on_every_channel():
    """g = -5 on every channel of every position of a chunk of 64: exp(-G)
    would reach exp(320); the kernel divides inside sub-blocks of 16 (exp(80),
    which float32 holds), so outputs and gradients are finite and the scan's."""
    q, k, v, g, beta, state = _kernel_inputs(delta_rule.UPDATE_CHUNK)
    g = jnp.full_like(g, LOWER)
    kernel = lambda *a: delta_rule.delta_rule_update_kernel(*a, interpret=True)
    got, got_state = kernel(q, k, v, g, beta, state)
    want, want_state = jax.jit(delta_rule.delta_rule_scan)(q, k, v, g, beta, state)
    assert bool(jnp.isfinite(got).all()) and bool(jnp.isfinite(got_state).all())
    _close_in_scale(got, want)
    _close_in_scale(got_state, want_state)
    grads = jax.jit(jax.grad(lambda *a: jnp.sum(kernel(*a)[0] ** 2), argnums=range(6)))(
        q, k, v, g, beta, state
    )
    assert all(bool(jnp.isfinite(x).all()) for x in grads)
    assert delta_rule._SUB * -LOWER < 88.0 < delta_rule.UPDATE_CHUNK * -LOWER


@pytest.mark.parametrize("why", ["off_the_chip", "a_remainder", "heads_of_16", "five_heads"])
def test_the_update_takes_the_chunked_form_off_the_chip_and_for_shapes_that_are_no_whole_tiles(
    why, monkeypatch
):
    """`delta_rule_update` is the kernel pair on a TPU for heads of 128 in
    blocks of 8 and whole chunks, and `delta_rule_chunked` anywhere else."""
    chunk = delta_rule.UPDATE_CHUNK
    shape = {
        "off_the_chip": (chunk, 8, 128), "a_remainder": (chunk + 16, 8, 128),
        "heads_of_16": (chunk, 8, 16), "five_heads": (chunk, 5, 128),
    }[why]
    if why != "off_the_chip":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert delta_rule.update_form(chunk, 8, 128, 256) == "kernel"
    length, heads, d = shape
    assert delta_rule.update_form(length, heads, d, d) == "chunked"
    args = _recurrence_inputs(length, batch=1, heads=heads, d=d)
    taken = []
    real = delta_rule.delta_rule_chunked
    monkeypatch.setattr(
        delta_rule, "delta_rule_chunked", lambda *a: taken.append("chunked") or real(*a)
    )
    got, state = delta_rule.delta_rule_update(*args)
    want, want_state = real(*args)
    assert taken == ["chunked"]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(state), np.asarray(want_state))


def _one_delta_mixer():
    mixer = kda.KimiDeltaAttention(64, HEADS, HEAD_DIM, TAPS, LOWER, 1e-6)
    u = jax.random.normal(jax.random.PRNGKey(2), (3, LENGTH, 64))
    params = mixer.init(jax.random.PRNGKey(3), u, method="forward")
    return mixer, jax.tree.map(lambda w: w * 8.0 if w.ndim > 1 else w, params), u


def test_the_log_decay_stays_between_the_lower_bound_and_zero():
    """Whatever the input: -5 < g < 0 a channel, spread over the channels at
    initialisation (a thousandth to one a token at u W_f = 0)."""
    mixer, params, u = _one_delta_mixer()
    gates = jax.jit(lambda p, x: mixer.apply(p, p["params"], x, method="_gates"))
    for scale in (0.0, 1.0, 100.0):
        g, beta, gate = gates(params, scale * u)
        assert g.shape == (3, LENGTH, HEADS, HEAD_DIM) and beta.shape == gate.shape == (3, LENGTH, HEADS)
        assert float(g.min()) >= LOWER and float(g.max()) <= 0.0
    at_rest, _, _ = gates(params, 0.0 * u)
    assert -1.0001 <= float(at_rest.min()) < -0.3 and -0.003 < float(at_rest.max()) <= -0.00099


@pytest.mark.parametrize("scale", [1.0, 30.0])
def test_the_delta_mixer_is_the_references_layer_and_step_by_step(scale):
    """One layer, one set of weights: `forward` (chunked) against the
    reference's layer (position by position), and `step` position by
    position against `forward`, on inputs that leave the decays near their
    initial rates and on ones that drive them to both ends; the gauge names
    the one form the update takes."""
    mixer, params, u = _one_delta_mixer()
    u = scale * u
    whole = jax.jit(lambda p, x: mixer.apply(p, x, method="forward"))(params, u)
    _close(whole, reference.delta_attention(params["params"], u, _spec()))
    gauge = get_registry().gauge("stoix_tpu_delta_rule_update")
    forms = ("kernel", "chunked", "scan")
    assert [gauge.value({"form": form}) for form in forms] == [0.0, 1.0, 0.0]
    state = kda.DeltaState(
        jnp.zeros((3, HEADS, HEAD_DIM, HEAD_DIM)), jnp.zeros((3, TAPS - 1, 3 * HEADS * HEAD_DIM)),
        jnp.zeros((3,), bool),
    )
    step = jax.jit(lambda p, x, s: mixer.apply(p, x, s, jnp.int32(0), method="step"))
    for t in range(LENGTH):
        out, state = step(params, u[:, t], state)
        _close(out, whole[:, t])
    assert state.s.shape == (3, HEADS, HEAD_DIM, HEAD_DIM)  # constant in the sequence length


def test_the_rematerialised_mixer_is_the_plain_ones_gradient():
    mixer, params, u = _one_delta_mixer()
    loss = lambda method, *w: lambda p, x: jnp.sum(jnp.sin(mixer.apply(p, *w, x, method=method)))
    got = jax.jit(jax.grad(loss("forward"), argnums=(0, 1)))(params, u)
    # (`_mix` is what `forward` wraps in `jax.checkpoint`, given the weights)
    want = jax.jit(jax.grad(lambda p, x: loss("_mix", p["params"])(p, x), argnums=(0, 1)))(params, u)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(g, w)


# --------------------------------------------------------------------------- #
# The carry
# --------------------------------------------------------------------------- #


def test_the_carry_holds_matrix_states_tails_and_latent_rows_and_says_how_much(model):
    nets, _, _, _ = model
    carry = nets.init_cache(3)
    assert [type(state).__name__ for state in carry.layers] == ["DeltaState"] * 5 + ["Latent"]
    assert carry.layers[0].s.shape == (3, HEADS, HEAD_DIM, HEAD_DIM)
    assert carry.layers[0].conv.shape == (3, TAPS - 1, 3 * HEADS * HEAD_DIM)
    assert carry.layers[5].rows.shape == (3, LENGTH, RANK + ROPE) and carry.length.shape == ()
    assert carry.layers[0].fresh.shape == (3,) and carry.layers[0].fresh.dtype == bool
    matrix, tails = HEADS * HEAD_DIM * HEAD_DIM, (TAPS - 1) * 3 * HEADS * HEAD_DIM
    delta = 5 * 3 * ((matrix + tails) * 4 + 1)  # (and a `fresh` flag a sequence)
    assert _actor().carry_bytes(3, LENGTH) == {
        "delta_state": delta, "latent": 3 * LENGTH * (RANK + ROPE) * 4,
    }
    # ... and a delta layer's state does not grow with the sequence
    assert _actor().carry_bytes(3, 8 * LENGTH)["delta_state"] == delta


def test_a_reset_on_done_zeroes_the_matrix_state_and_the_tails_of_that_sequence_only(model):
    """Unlike the rows of a cache beyond `length`, a matrix state is read
    whole at the next step: after `reset_carry` the done sequence's tails are
    zeros and its matrices count as zeros (`fresh`: the next step reads them
    so and overwrites them — to the bit what a zeroed matrix gives), its next
    steps equal a fresh carry's, and its neighbour goes on as if nothing had
    happened."""
    nets, actor_params, critic_params, tokens = model
    step = jax.jit(nets.step)
    carry = _actor().init_carry(2, LENGTH)  # a position a sequence: these two end apart
    for t in range(5):
        _, _, carry, _ = step(actor_params, carry, tokens[:2, t])
    before = carry
    carry = nets.reset_cache(carry, jnp.array([True, False]))
    assert carry.length.tolist() == [0, 5]
    for was, now in zip(before.layers[:5], carry.layers[:5]):
        assert float(jnp.abs(was.s[0]).max()) > 0.0 and float(jnp.abs(was.conv[0]).max()) > 0.0
        assert float(jnp.abs(now.conv[0]).max()) == 0.0 and now.fresh.tolist() == [True, False]
        np.testing.assert_array_equal(np.asarray(now.s[1]), np.asarray(was.s[1]))
        np.testing.assert_array_equal(np.asarray(now.conv[1]), np.asarray(was.conv[1]))
    assert float(jnp.abs(carry.layers[5].rows[0]).max()) > 0.0  # stale latent rows stay, unread
    # the same step on a carry whose matrices ARE zeros: the same states to the bit
    zeroed = carry._replace(layers=tuple(
        state._replace(s=state.s.at[0].set(0.0), fresh=jnp.zeros_like(state.fresh))
        if isinstance(state, kda.DeltaState) else state for state in carry.layers
    ))
    _, _, after_zeroed, _ = step(actor_params, zeroed, tokens[2:4, 0])
    fresh = _actor().init_carry(1, LENGTH)
    for t in range(3):
        logits, _, carry, _ = step(actor_params, carry, tokens[2:4, t])
        want, _, fresh, _ = step(actor_params, fresh, tokens[2:3, t])
        _close(logits[0], want[0])
        if t == 0:
            for got, same in zip(carry.layers[:5], after_zeroed.layers[:5]):
                np.testing.assert_array_equal(np.asarray(got.s), np.asarray(same.s))
                assert not got.fresh.any()
    whole = jnp.concatenate([tokens[1:2, :5], tokens[3:4, :3]], axis=1)
    continued = _reference_forward(actor_params, critic_params, whole, _spec())
    _close(logits[1], continued["logits"][0, -1])


def test_a_state_that_is_not_reset_is_another_result(model):
    """What the reset is for: without it the new sequence reads its
    predecessor's matrix."""
    nets, actor_params, _, tokens = model
    step = jax.jit(nets.step)
    carry = _actor().init_carry(1, LENGTH)
    for t in range(5):
        _, _, carry, _ = step(actor_params, carry, tokens[:1, t])
    stale = carry._replace(length=jnp.zeros_like(carry.length))  # the length alone goes back
    got, _, _, _ = step(actor_params, stale, tokens[2:3, 0])
    want, _, _, _ = step(actor_params, _actor().init_carry(1, LENGTH), tokens[2:3, 0])
    assert float(jnp.abs(got - want).max()) > 1e-3


# --------------------------------------------------------------------------- #
# The router's groups and the latent layer's gate
# --------------------------------------------------------------------------- #


def _spread_router():
    """A router whose best experts lie one in each of five groups of eight."""
    x = jax.random.normal(jax.random.PRNGKey(3), (40, 64)).at[:, 0].set(1.0)
    router = 0.05 * jax.random.normal(jax.random.PRNGKey(4), (64, 64))
    for rank, expert in enumerate((0, 8, 16, 24, 32, 1, 9, 17)):  # eight largest, five groups
        router = router.at[0, expert].set(6.0 - 0.3 * rank)
    return x, router


def test_a_token_whose_plain_top_8_spans_five_groups_chooses_otherwise():
    """64 experts in 8 groups, the 4 best open: the plain top-8 of score +
    bias lies in five groups, so the group-limited choice differs, stays
    inside four groups, and those are the four with the largest sum of their
    two best."""
    x, router = _spread_router()
    bias = 0.01 * jax.random.normal(jax.random.PRNGKey(5), (64,))
    routing = dict(score="sigmoid", bias=bias, epsilon=1e-20, scale=SCALING)
    scores, weights, index = olmoe.route(x, router, 8, True, groups=8, top_groups=4, **routing)
    _, _, plain = olmoe.route(x, router, 8, True, **routing)
    groups_of = lambda chosen: [set((np.asarray(row) // 8).tolist()) for row in chosen]
    assert all(len(g) == 5 for g in groups_of(plain))
    assert all(len(g) <= 4 for g in groups_of(index))
    assert (_sets(index) != _sets(plain)).any(axis=-1).all()
    choice = np.asarray(scores + bias).reshape(40, 8, 8)
    best = np.argsort(-np.sort(choice, axis=-1)[..., -2:].sum(-1), axis=-1)[:, :4]
    assert all(g <= set(row.tolist()) for g, row in zip(groups_of(index), best))
    # the weights are the scores themselves at the chosen experts, over their sum, times 2.5
    chosen = jnp.take_along_axis(scores, index, axis=-1)
    _close(weights, SCALING * chosen / chosen.sum(axis=-1, keepdims=True))
    # ... and the reference's own choice, written otherwise, is the same
    want = reference.group_limited(scores + bias, 8, 4)
    assert (_sets(jax.lax.top_k(want, 8)[1]) == _sets(index)).all()


@pytest.mark.parametrize("bias", ["with_bias", "without"])
def test_one_group_is_the_old_choice_to_the_bit(bias):
    x, router = _spread_router()
    routing = dict(score="sigmoid", epsilon=1e-20, scale=SCALING)
    if bias == "with_bias":
        routing["bias"] = 0.01 * jax.random.normal(jax.random.PRNGKey(5), (64,))
    old = jax.make_jaxpr(lambda a, b: olmoe.route(a, b, 8, True, **routing))(x, router)
    new = jax.make_jaxpr(
        lambda a, b: olmoe.route(a, b, 8, True, groups=1, top_groups=1, **routing)
    )(x, router)
    assert str(old) == str(new)
    for got, want in zip(
        olmoe.route(x, router, 8, True, groups=1, top_groups=1, **routing),
        olmoe.route(x, router, 8, True, **routing),
    ):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_the_stack_without_the_new_keys_traces_to_the_program_it_traced_to():
    """`Lfm2LM` with the new keys at their defaults (no gate, one group) and
    the Kanana-2 stack built as before the keys existed: one jaxpr, forward
    and step."""
    common = dict(
        vocab_size=VOCAB, hidden_size=64, layer_types=["latent_attention"] * 2, num_dense_layers=1,
        dense_width=96, num_heads=HEADS, num_kv_heads=HEADS, head_dim=ROPE, kv_lora_rank=RANK,
        qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPE, v_head_dim=V_DIM, num_experts=EXPERTS,
        experts_held=HELD, experts_per_token=TOP_K, expert_width=32, n_shared_experts=2,
        routed_scaling_factor=2.448, router_epsilon=1e-20, tie_word_embeddings=False, rms_eps=1e-6,
    )
    old = lfm2.Lfm2LM(**common)
    new = lfm2.Lfm2LM(**common, attention_gate=False, n_group=1, topk_group=1, kda_lower_bound=LOWER)
    tokens = jnp.zeros((2, 8), jnp.int32)
    params = old.init(jax.random.PRNGKey(0), tokens, method="forward")
    assert jax.tree.structure(params) == jax.tree.structure(
        new.init(jax.random.PRNGKey(0), tokens, method="forward")
    )
    forward = lambda m: str(jax.make_jaxpr(lambda p, t: m.apply(p, t, method="forward"))(params, tokens))
    assert forward(old) == forward(new)
    carry = old.init_carry(2, 8, together=True)
    step = lambda m: str(
        jax.make_jaxpr(lambda p, c, t: m.apply(p, c, t, method="step"))(params, carry, tokens[:, 0])
    )
    assert step(old) == step(new)


def _one_latent_mixer(gate):
    mixer = mla.LatentAttention(64, HEADS, RANK, NOPE, ROPE, V_DIM, 6000000.0, 1e-6, gate)
    u = jax.random.normal(jax.random.PRNGKey(2), (3, LENGTH, 64))
    params = mixer.init(jax.random.PRNGKey(3), u, method="forward")
    return mixer, jax.tree.map(lambda w: w * 8.0 if w.ndim > 1 else w, params), u


def test_the_head_wise_gate_off_is_latent_attention_as_it_was():
    """No `wg` leaf, and the jaxpr of the mixer built without the argument."""
    mixer, params, u = _one_latent_mixer(False)
    assert sorted(params["params"]) == ["kv_norm", "wkv_a", "wkv_b", "wo", "wq"]
    old = mla.LatentAttention(64, HEADS, RANK, NOPE, ROPE, V_DIM, 6000000.0, 1e-6)
    trace = lambda m: str(jax.make_jaxpr(lambda p, x: m.apply(p, x, method="forward"))(params, u))
    assert trace(mixer) == trace(old)


def test_the_head_wise_gate_multiplies_each_heads_result_in_both_entry_points():
    mixer, params, u = _one_latent_mixer(True)
    assert params["params"]["wg"].shape == (64, HEADS)
    gated = mixer.apply(params, u, method="forward")
    _close(gated, reference.latent_attention(params["params"], u, _spec()))
    # a gate of one half on every head is half the ungated layer
    halved = {"params": {**params["params"], "wg": jnp.zeros((64, HEADS))}}
    plain, _, _ = _one_latent_mixer(False)
    ungated = plain.apply({"params": {k: v for k, v in params["params"].items() if k != "wg"}}, u, method="forward")
    _close(mixer.apply(halved, u, method="forward"), 0.5 * ungated)
    assert float(jnp.abs(gated - 0.5 * ungated).max()) > 1e-3
    rows = jnp.zeros((3, LENGTH, RANK + ROPE))
    step = jax.jit(lambda p, x, state, at: mixer.apply(p, x, state, at, method="step"))
    for t in range(LENGTH):
        out, state = step(params, u[:, t], mla.Latent(rows), jnp.int32(t))
        rows = state.rows
        _close(out, gated[:, t])


# --------------------------------------------------------------------------- #
# The loss and its gradient
# --------------------------------------------------------------------------- #

DELTA = ["mixer/" + name for name in (
    "wq", "wk", "wv", "wf", "q_conv", "k_conv", "v_conv", "dt_bias", "a_log", "wbeta", "wg",
    "out_norm", "wo",
)]
LATENT = ["mixer/" + name for name in ("wq", "wkv_a", "kv_norm", "wkv_b", "wo", "wg")]
ACTOR_LEAVES = ["embed", "final_norm", "lm_head"] + [
    f"layer_{i}/{name}"
    for i in range(LAYERS)
    for name in ["operator_norm", "ffn_norm"] + (LATENT if (i + 1) % PERIOD == 0 else DELTA)
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
    "group_limited_changed_share",
])
def test_loss_matches_the_reference_loss(loss_and_grads, part):
    got, want, _, _ = loss_and_grads
    _close(got[part], want[part])


def test_the_loss_counts_no_dropped_pair(loss_and_grads):
    got, _, _, _ = loss_and_grads
    assert float(got["dropped_pairs"]) == 0.0 and float(got["routed_pairs_per_token"]) == TOP_K
    assert 0.0 < float(got["group_limited_changed_share"]) < 1.0


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_leaf_matches_jax_grad_of_the_reference_loss(loss_and_grads, leaf):
    """The chunked form's backward pass through six layers, leaf by leaf,
    against `jax.grad` of the position-by-position reference."""
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
    module, group-limited choice and all: its held experts' part and the
    shared expert, which every rank computes alike. The eight parts (64 in
    the deployment, each of 8 experts of 512), with the shared expert counted
    ONCE, sum to the uncut reference's layer."""
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
            64, EXPERTS, HELD, rank * HELD, TOP_K, 32, SCALING, 0.05, 1e-20, 32, GROUPS, TOP_GROUPS
        )
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
@pytest.mark.parametrize("scope", DELTA_SCOPES + LATENT_SCOPES + ("dense_mlp",) + BLOCK_SCOPES)
def test_the_scopes_are_in_both_phases_of_the_learner_and_in_the_evaluator(
    program_scopes, phase, scope
):
    assert SCOPES[scope] in program_scopes[phase]


def test_learner_setup_publishes_the_carry_kinds_and_the_updates_form(program_scopes):
    by = lambda gauge, label: {
        dict(labels)[label]: value for labels, value in gauge.labels_and_values()
    }
    registry = get_registry()
    per_shard = 32 // 8  # sequences a shard of the 8 virtual devices
    matrix, tails = HEADS * HEAD_DIM * HEAD_DIM, (TAPS - 1) * 3 * HEADS * HEAD_DIM
    assert by(registry.gauge("stoix_tpu_lm_carry_bytes"), "kind") == {
        "delta_state": 5 * per_shard * ((matrix + tails) * 4 + 1),
        "latent": per_shard * LENGTH * (RANK + ROPE) * 4,
    }
    assert by(registry.gauge("stoix_tpu_lm_cache_write"), "form") == {"slice": 1.0, "scatter": 0.0}
    assert by(registry.gauge("stoix_tpu_mla_decode"), "form") == {"absorbed": 1.0, "expanded": 0.0}
    assert by(registry.gauge("stoix_tpu_delta_rule_update"), "form") == {
        "kernel": 0.0, "chunked": 1.0, "scan": 0.0
    }


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
    rollout and update, the held experts' load, what the selection bias and
    what the group limit re-routed."""
    _, trains, _ = _logged_run(["arch.num_updates=2", "arch.num_evaluation=1"])
    (train,) = trains
    uniform = TOP_K * HELD / EXPERTS
    assert 0.3 * uniform < float(train["held_pairs_per_token"]) < 3 * uniform
    assert 0.3 * uniform < float(train["rollout_held_pairs_per_token"]) < 3 * uniform
    assert float(train["expert_load_max_over_mean"]) >= 1.0
    assert 0.0 < float(train["router_bias_changed_share"]) < 1.0
    assert 0.0 < float(train["group_limited_changed_share"]) < 1.0
    assert float(train["dropped_pairs"]) == 0.0


def test_the_benchmark_keeps_a_copy_of_the_reference(model):
    """benchmarks/references/ppo_ling3.py carries its own copy of the plain
    forward and loss (it may import nothing of the program): they agree
    exactly."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.harness import loader

    copy = loader.load_reference("ppo_ling3")
    _, actor_params, critic_params, tokens = model
    want = _reference_forward(actor_params, critic_params, tokens, _spec())
    got = jax.jit(lambda a, c, t: copy.forward(a, c, t, _spec()))(actor_params, critic_params, tokens)
    for key in ("logits", "values", "expert_index", "ungrouped_index", "plain_index"):
        np.testing.assert_array_equal(np.asarray(got[key]), np.asarray(want[key]))
