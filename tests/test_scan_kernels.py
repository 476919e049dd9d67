"""Scan-kernel equivalence gate (docs/DESIGN.md §2.7).

Every `system.multistep_impl` must produce the same estimators:

  * `scan` is pinned BITWISE against an inlined copy of the pre-dispatch
    `_reverse_scan` — the default can never drift from what every system
    shipped with;
  * `assoc` (log-depth associative scan) matches `scan` within float32
    reassociation tolerance (1e-5) on all five estimator families — GAE,
    lambda-returns, n-step, retrace, V-trace — across layouts, truncation
    resets, and mid-trajectory terminations; bfloat16 tolerance is documented
    at 1e-2 (low-precision inputs lose bits to reassociation);
  * the `pallas` time-blocked kernel (interpret mode on CPU) is bitwise
    equal to `scan` for float32 — its in-block op order IS the sequential
    order.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stoix_tpu.ops import multistep as ms
from stoix_tpu.ops import scan_kernels as sk

F32_TOL = 1e-5  # documented float32 reassociation tolerance
BF16_TOL = 1e-2  # documented bfloat16 tolerance (inputs already carry ~3 digits)


def _inlined_reference_scan(weight_t, delta_t, init):
    """Byte-for-byte copy of the pre-dispatch multistep._reverse_scan body."""

    def body(acc, inputs):
        delta, weight = inputs
        acc = delta + weight * acc
        return acc, acc

    _, out = jax.lax.scan(body, init, (delta_t, weight_t), reverse=True)
    return out


def _random_recurrence(seed, t_len=17, batch=5, dtype=np.float32, with_zeros=True):
    rng = np.random.default_rng(seed)
    w = rng.uniform(0.0, 1.0, (t_len, batch)).astype(dtype)
    if with_zeros:
        # Mid-trajectory terminations: discount 0 resets the recurrence.
        w[rng.integers(0, t_len, size=3), rng.integers(0, batch, size=3)] = 0.0
    d = rng.normal(size=(t_len, batch)).astype(dtype)
    init = rng.normal(size=(batch,)).astype(dtype)
    return jnp.asarray(w), jnp.asarray(d), jnp.asarray(init)


# ---- kernel-level equivalence ------------------------------------------------


def test_scan_impl_bitwise_matches_inlined_reference():
    w, d, init = _random_recurrence(0)
    got = sk.linear_recurrence_reverse(w, d, init, impl="scan")
    want = _inlined_reference_scan(w, d, init)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_assoc_impl_matches_scan_float32():
    w, d, init = _random_recurrence(1)
    got = sk.linear_recurrence_reverse(w, d, init, impl="assoc")
    want = sk.linear_recurrence_reverse(w, d, init, impl="scan")
    np.testing.assert_allclose(got, want, atol=F32_TOL, rtol=F32_TOL)


def test_assoc_impl_matches_scan_bfloat16():
    w, d, init = _random_recurrence(2)
    w, d, init = (x.astype(jnp.bfloat16) for x in (w, d, init))
    got = sk.linear_recurrence_reverse(w, d, init, impl="assoc").astype(jnp.float32)
    want = sk.linear_recurrence_reverse(w, d, init, impl="scan").astype(jnp.float32)
    np.testing.assert_allclose(got, want, atol=BF16_TOL, rtol=BF16_TOL)


def test_pallas_kernel_bitwise_matches_scan_float32():
    # The kernel proper, interpret mode (off-TPU the DISPATCH falls back to
    # scan; the kernel itself must still be right): block_t smaller than T
    # exercises the cross-block carry, larger exercises time padding
    # (block_t is rounded up to whole 8-row float32 tiles).
    for seed, block_t in [(3, 8), (4, 16), (5, 64)]:
        w, d, init = _random_recurrence(seed, t_len=19, batch=3)
        got = sk.pallas_linear_recurrence_reverse(
            w, d, init, block_t=block_t, interpret=True
        )
        want = sk.linear_recurrence_reverse(w, d, init, impl="scan")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pallas_dispatch_falls_back_to_scan_off_tpu():
    w, d, init = _random_recurrence(6)
    got = sk.linear_recurrence_reverse(w, d, init, impl="pallas")
    want = sk.linear_recurrence_reverse(w, d, init, impl="scan")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_default_impl_plumbing_and_validation():
    assert sk.resolve_impl(None) == "scan"  # the shipped default
    with sk.use_impl("assoc"):
        assert sk.resolve_impl(None) == "assoc"
        assert sk.resolve_impl("pallas") == "pallas"  # explicit wins
    assert sk.resolve_impl(None) == "scan"  # restored
    with pytest.raises(ValueError, match="unknown multistep impl"):
        sk.resolve_impl("vectorized")

    class _Sys(dict):
        def get(self, k, default=None):
            return dict.get(self, k, default)

    class _Cfg:
        system = _Sys(multistep_impl="assoc")

    try:
        assert sk.configure_from_config(_Cfg()) == "assoc"
        assert sk.get_default_impl() == "assoc"
    finally:
        sk.set_default_impl("scan")


def test_assoc_emits_no_scan_primitive():
    # The point of assoc is log-depth: the traced program must contain NO
    # sequential scan. This also proves the config default actually routes
    # the estimators the systems call (GAE for PPO, Q(lambda) for the
    # q-family's PQN) through the parallel kernel.
    r = jnp.ones((8, 4))
    g = jnp.full((8, 4), 0.9)
    q = jnp.ones((8, 4, 3))
    v = jnp.ones((9, 4))
    with sk.use_impl("assoc"):
        gae_jaxpr = str(
            jax.make_jaxpr(
                lambda r_, g_, v_: ms.truncated_generalized_advantage_estimation(
                    r_, g_, 0.95, values=v_
                )
            )(r, g, v)
        )
        ql_jaxpr = str(
            jax.make_jaxpr(lambda r_, g_, q_: ms.q_lambda(r_, g_, q_, 0.9))(r, g, q)
        )
    assert " scan" not in gae_jaxpr and " scan" not in ql_jaxpr
    with sk.use_impl("scan"):
        default_jaxpr = str(
            jax.make_jaxpr(
                lambda r_, g_, v_: ms.truncated_generalized_advantage_estimation(
                    r_, g_, 0.95, values=v_
                )
            )(r, g, v)
        )
    assert " scan" in default_jaxpr


# ---- estimator-family equivalence (assoc vs scan) ----------------------------


def _family_outputs(impl: str, seed: int = 7):
    """All five estimator families under one impl, on shared random inputs
    with mid-trajectory terminations (discount 0) and a truncation reset."""
    rng = np.random.default_rng(seed)
    t_len, batch = 12, 4
    r = jnp.asarray(rng.normal(size=(t_len, batch)), jnp.float32)
    g = jnp.asarray(rng.uniform(0, 1, (t_len, batch)), jnp.float32)
    g = g.at[5].set(0.0)  # terminations reset the recurrence mid-trajectory
    values = jnp.asarray(rng.normal(size=(t_len + 1, batch)), jnp.float32)
    trunc = jnp.zeros((t_len, batch)).at[3].set(1.0)
    rho = jnp.asarray(rng.uniform(0.3, 2.0, (t_len, batch)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(t_len, batch, 5)), jnp.float32)
    q_k = jnp.asarray(rng.normal(size=(batch, t_len - 1)), jnp.float32)
    v_k = jnp.asarray(rng.normal(size=(batch, t_len)), jnp.float32)
    log_rhos = jnp.asarray(rng.normal(size=(batch, t_len - 1)), jnp.float32)

    gae_adv, gae_tgt = ms.truncated_generalized_advantage_estimation(
        r, g, 0.95, v_tm1=values[:-1], v_t=values[1:], truncation_t=trunc, impl=impl
    )
    lam_ret = ms.lambda_returns(r, g, values[1:], 0.9, impl=impl)
    nstep = ms.n_step_bootstrapped_returns(
        jnp.swapaxes(r, 0, 1), jnp.swapaxes(g, 0, 1), jnp.swapaxes(values[1:], 0, 1),
        n=5, impl=impl,
    )
    retrace = ms.retrace_continuous(
        jnp.ones((batch, t_len), jnp.float32),  # q_tm1 (any values)
        q_k, v_k, jnp.swapaxes(r, 0, 1), jnp.swapaxes(g, 0, 1), log_rhos, 0.95,
        impl=impl,
    )
    vt_err, vt_pg, vt_q = ms.vtrace_td_error_and_advantage(
        values[:-1, 0], values[1:, 0], r[:, 0], g[:, 0], rho[:, 0], 0.95, impl=impl
    )
    return {
        "gae_adv": gae_adv, "gae_tgt": gae_tgt, "lambda": lam_ret, "nstep": nstep,
        "retrace": retrace, "vtrace_err": vt_err, "vtrace_pg": vt_pg, "vtrace_q": vt_q,
    }


def test_all_five_families_assoc_matches_scan():
    want = _family_outputs("scan")
    got = _family_outputs("assoc")
    for name in want:
        np.testing.assert_allclose(
            got[name], want[name], atol=F32_TOL, rtol=F32_TOL,
            err_msg=f"family {name} diverged between assoc and scan",
        )


def test_families_batch_major_matches_time_major_under_assoc():
    rng = np.random.default_rng(8)
    r = jnp.asarray(rng.normal(size=(2, 9)), jnp.float32)
    g = jnp.asarray(rng.uniform(0, 1, (2, 9)), jnp.float32)
    values = jnp.asarray(rng.normal(size=(2, 10)), jnp.float32)
    a_bm, t_bm = ms.truncated_generalized_advantage_estimation(
        r, g, 0.95, values=values, batch_major=True, impl="assoc"
    )
    a_tm, t_tm = ms.truncated_generalized_advantage_estimation(
        r.T, g.T, 0.95, values=values.T, batch_major=False, impl="assoc"
    )
    np.testing.assert_allclose(a_bm, a_tm.T, atol=F32_TOL)
    np.testing.assert_allclose(t_bm, t_tm.T, atol=F32_TOL)


@pytest.mark.parametrize("n", [1, 3, 8, 16])
def test_nstep_window_fold_matches_reference_loop(n):
    # n spanning 1, < T, == T-ish, and > T: the doubling fold must agree with
    # the reference's n unrolled passes including the bootstrap-tail regime.
    rng = np.random.default_rng(100 + n)
    r = jnp.asarray(rng.normal(size=(3, 7)), jnp.float32)
    g = jnp.asarray(rng.uniform(0, 1, (3, 7)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(3, 7)), jnp.float32)
    for lam in (1.0, 0.65):
        want = ms.n_step_bootstrapped_returns(r, g, v, n=n, lambda_t=lam, impl="scan")
        got = ms.n_step_bootstrapped_returns(r, g, v, n=n, lambda_t=lam, impl="assoc")
        np.testing.assert_allclose(got, want, atol=F32_TOL, rtol=F32_TOL)


def test_termination_reset_propagates_identically():
    # A zero discount cuts the recurrence: everything before the cut must be
    # independent of everything after it, under every impl.
    w, d, init = _random_recurrence(9, t_len=10, batch=2, with_zeros=False)
    w = w.at[4].set(0.0)
    outs = {
        impl: np.asarray(sk.linear_recurrence_reverse(w, d, init, impl=impl))
        for impl in ("scan", "assoc")
    }
    outs["pallas_kernel"] = np.asarray(
        sk.pallas_linear_recurrence_reverse(w, d, init, block_t=4, interpret=True)
    )
    # Changing post-cut deltas must not leak into pre-cut outputs.
    d2 = d.at[7].add(100.0)
    for impl in ("scan", "assoc"):
        changed = np.asarray(sk.linear_recurrence_reverse(w, d2, init, impl=impl))
        np.testing.assert_allclose(changed[:5], outs[impl][:5], atol=F32_TOL)
    for name, out in outs.items():
        np.testing.assert_allclose(
            out, outs["scan"], atol=F32_TOL, err_msg=f"{name} broke the reset"
        )


# ---- system-level pin: the default is bit-identical, assoc is usable ---------


def test_ppo_learner_default_scan_bitwise_and_assoc_close(devices):
    """One learn() call of the real Anakin PPO learner on the 8-device mesh:
    the composed default must equal an explicit system.multistep_impl=scan
    BITWISE (pins default=scan end to end), and assoc must track it to float
    tolerance while training the same trajectory."""
    from stoix_tpu import envs
    from stoix_tpu.parallel import create_mesh
    from stoix_tpu.systems.ppo.anakin.ff_ppo import learner_setup
    from stoix_tpu.utils import config as config_lib
    from stoix_tpu.utils.timestep_checker import check_total_timesteps

    def one_learn(extra):
        config = config_lib.compose(
            config_lib.default_config_dir(),
            "default/anakin/default_ff_ppo.yaml",
            [
                "env=identity_game", "arch.total_num_envs=16",
                "arch.total_timesteps=~", "arch.num_updates=2",
                "arch.num_evaluation=1", "system.rollout_length=4",
                "system.epochs=1", "logger.use_console=False", *extra,
            ],
        )
        sk.configure_from_config(config)
        try:
            mesh = create_mesh({"data": -1})
            config = check_total_timesteps(config, int(mesh.shape["data"]))
            env, _ = envs.make(config)
            setup = learner_setup(env, config, mesh, jax.random.PRNGKey(0))
            out = setup.learn(setup.learner_state)
            return jax.tree.map(np.asarray, jax.tree.leaves(out.learner_state.params))
        finally:
            sk.set_default_impl("scan")

    default_params = one_learn([])
    scan_params = one_learn(["system.multistep_impl=scan"])
    assoc_params = one_learn(["system.multistep_impl=assoc", "system.fused_update=true"])
    for got, want in zip(scan_params, default_params):
        np.testing.assert_array_equal(got, want)
    for got, want in zip(assoc_params, default_params):
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
