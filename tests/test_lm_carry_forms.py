"""The token policies' decode carry in its two forms (networks/olmoe.py,
networks/lfm2.py): `length [B]`, a position a sequence and a scatter a step,
and `length []`, one position for sequences that move together and one slab
written in place — the form `ff_lm_ppo.network_functions` asks for. Both
write the same row at the same position and read the same prefix, so a
decode of whole sequences gives the same floats either way; at the tiny
presets of tests/test_lm_ppo.py (two layers) and tests/test_lfm2_ppo.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stoix_tpu.networks import olmoe

import test_lfm2_ppo
import test_lm_ppo

TOL = test_lm_ppo.TOL


def _olmoe():
    nets, actor_params, _, tokens = test_lm_ppo._model(2)
    per_sequence = lambda batch: olmoe.init_cache(2, batch, test_lm_ppo.LENGTH, 4, 16)
    return nets, actor_params, tokens, per_sequence


def _lfm2():
    nets, actor_params, _, tokens = test_lfm2_ppo._model()
    per_sequence = lambda batch: test_lfm2_ppo._actor().init_carry(batch, test_lfm2_ppo.LENGTH)
    return nets, actor_params, tokens, per_sequence


@pytest.fixture(scope="module", params=[_olmoe, _lfm2], ids=["olmoe", "lfm2"])
def model(request):
    return request.param()


@pytest.fixture(scope="module", params=["op_by_op", "compiled"])
def decoded(request, model):
    """The whole sequences decoded through each form. Op by op every
    operation of the two decodes gets the same operands, so the results are
    the same bits; compiled as one program each, XLA fuses around a scatter
    otherwise than around a slice (a multiply-add contracted here and not
    there), and they agree as the sibling tests' two sides do."""
    nets, actor_params, tokens, per_sequence = model

    def decode(carry):
        def one(carry, token):
            logits, hidden, carry, stats = nets.step(actor_params, carry, token)
            return carry, {"logits": logits, "hidden": hidden, "stats": stats}

        carry, out = jax.lax.scan(one, carry, tokens.T)
        return {**out, "cache": carry}

    batch = tokens.shape[0]
    together, apart = nets.init_cache(batch), per_sequence(batch)
    assert together.length.shape == () and apart.length.shape == (batch,)
    if request.param == "compiled":
        return TOL, jax.jit(decode)(together), jax.jit(decode)(apart)
    with jax.disable_jit():
        return 0.0, decode(together), decode(apart)


@pytest.mark.parametrize("what", ["logits", "hidden", "stats", "cache"])
def test_one_position_for_all_decodes_to_what_a_position_a_sequence_does(decoded, what):
    tol, together, apart = decoded
    together, apart = together[what], apart[what]
    if what == "cache":  # the states of every layer; the positions say the same
        steps = int(together.length)
        assert steps > 0 and apart.length.tolist() == [steps] * apart.length.shape[0]
        together, apart = together._replace(length=None), apart._replace(length=None)
    got, want = jax.tree.leaves(together), jax.tree.leaves(apart)
    assert got and len(got) == len(want)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol, atol=tol)


@pytest.mark.parametrize("done", ["all", "none"])
def test_a_reset_of_the_one_position_starts_all_sequences_anew_once_they_are_done(model, done):
    """Done together: position 0 (and, of a conv tail, zeros), and the next
    steps are a fresh carry's. Not done: the carry goes on as it was."""
    nets, actor_params, tokens, _ = model
    step = jax.jit(nets.step)
    batch = tokens.shape[0]
    carry = nets.init_cache(batch)
    for t in range(5):
        _, _, carry, _ = step(actor_params, carry, tokens[:, t])
    reset = nets.reset_cache(carry, jnp.full((batch,), done == "all"))
    assert reset.length.shape == () and int(reset.length) == (0 if done == "all" else 5)
    want = nets.init_cache(batch) if done == "all" else carry
    for t in range(3):
        got_logits, _, reset, _ = step(actor_params, reset, tokens[:, t])
        want_logits, _, want, _ = step(actor_params, want, tokens[:, t])
        np.testing.assert_array_equal(np.asarray(got_logits), np.asarray(want_logits))


def test_init_carry_gives_a_position_a_sequence_unless_asked():
    """The scalar is a fact of the call: who does not say that its sequences
    move together gets `length [B]`."""
    assert test_lfm2_ppo._actor().init_carry(3, 8).length.shape == (3,)
    assert test_lfm2_ppo._actor().init_carry(3, 8, together=True).length.shape == ()
    assert olmoe.init_cache(1, 3, 8, 4, 16).length.shape == (3,)
    assert olmoe.init_cache(1, 3, 8, 4, 16, together=True).length.shape == ()


@pytest.mark.parametrize("form", ["slice", "scatter"])
def test_learner_setup_publishes_the_form_of_the_cache_write(devices, monkeypatch, form):
    """The gauge says which write the learner just set up took, read from the
    carry that `network_functions` built and from nothing else: 1 on that
    form, 0 on the other."""
    from stoix_tpu import envs
    from stoix_tpu.observability import get_registry
    from stoix_tpu.parallel import MeshRoles
    from stoix_tpu.systems.ppo.anakin import ff_lm_ppo
    from stoix_tpu.utils.timestep_checker import check_total_timesteps

    if form == "scatter":  # a caller whose sequences end apart
        functions = ff_lm_ppo.network_functions

        def a_position_a_sequence(actor, critic, max_len):
            apart = lambda batch: actor.init_carry(batch, max_len)
            return functions(actor, critic, max_len)._replace(init_cache=apart)

        monkeypatch.setattr(ff_lm_ppo, "network_functions", a_position_a_sequence)
    config = test_lm_ppo._config()
    mesh = MeshRoles.from_config(config).learn_mesh()
    config = check_total_timesteps(config, int(mesh.shape["data"]))
    env, _ = envs.make(config)
    ff_lm_ppo.learner_setup(env, config, mesh, jax.random.PRNGKey(0))
    series = get_registry().gauge("stoix_tpu_lm_cache_write").labels_and_values()
    assert {dict(labels)["form"]: value for labels, value in series} == {
        "slice": float(form == "slice"), "scatter": float(form == "scatter"),
    }
