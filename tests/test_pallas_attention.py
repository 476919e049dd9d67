"""Pallas flash attention vs the pure-JAX oracle (interpreter mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stoix_tpu.ops.pallas_attention import flash_attention
from stoix_tpu.ops.ring_attention import full_attention


def _rand_qkv(key, b, s, h, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), dtype)
    k = jax.random.normal(kk, (b, s, h, d), dtype)
    v = jax.random.normal(kv, (b, s, h, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("seq_len", [128, 256])
def test_flash_matches_full(causal, seq_len):
    q, k, v = _rand_qkv(jax.random.PRNGKey(0), 2, seq_len, 2, 64)
    got = flash_attention(
        q, k, v, causal=causal, block_q=128, block_k=128, interpret=True
    )
    want = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_handles_padding(causal):
    # Sequence NOT a multiple of the block sizes: padded keys must be masked
    # out and padded queries stripped.
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), 1, 100, 2, 32)
    got = flash_attention(
        q, k, v, causal=causal, block_q=64, block_k=64, interpret=True
    )
    want = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_flash_bf16_inputs():
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), 1, 128, 1, 64, jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True, interpret=True)
    want = full_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        causal=True,
    )
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        got.astype(jnp.float32), want, atol=2e-2, rtol=2e-2
    )


def test_flash_multiple_q_blocks_causal():
    # More query blocks than kv blocks exercises the early-exit bound.
    q, k, v = _rand_qkv(jax.random.PRNGKey(3), 1, 256, 1, 32)
    got = flash_attention(
        q, k, v, causal=True, block_q=64, block_k=128, interpret=True
    )
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_chunk_kernel_folds_to_full_attention(causal):
    # Fold three K/V chunks through the streaming accumulator exactly as
    # ring attention does; the result must equal full attention.
    b, s, h, d = 2, 192, 2, 32
    q, k, v = _rand_qkv(jax.random.PRNGKey(4), b, s, h, d)
    from stoix_tpu.ops.pallas_attention import flash_attention_chunk

    chunk = s // 3
    m_acc = jnp.full((b, h, s), -jnp.inf, jnp.float32)
    l_acc = jnp.zeros((b, h, s), jnp.float32)
    o_acc = jnp.zeros((b, s, h, d), jnp.float32)
    q_pos = jnp.arange(s)
    for c in range(3):
        k_blk = k[:, c * chunk:(c + 1) * chunk]
        v_blk = v[:, c * chunk:(c + 1) * chunk]
        k_pos = jnp.arange(c * chunk, (c + 1) * chunk)
        pv, m, l = flash_attention_chunk(
            q, k_blk, v_blk, q_pos, k_pos, causal=causal,
            block_q=64, block_k=64, interpret=True,
        )
        m_new = jnp.maximum(m_acc, m)
        alpha = jnp.exp(m_acc - m_new)
        beta = jnp.exp(m - m_new)
        l_acc = l_acc * alpha + l * beta
        o_acc = o_acc * jnp.transpose(alpha, (0, 2, 1))[..., None] + pv * jnp.transpose(
            beta, (0, 2, 1)
        )[..., None]
        m_acc = m_new
    l_safe = jnp.where(l_acc == 0.0, 1.0, l_acc)
    got = o_acc / jnp.transpose(l_safe, (0, 2, 1))[..., None]
    want = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("use_flash", [False, True])
def test_ring_attention_matches_full_attention(use_flash):
    # Both ring block paths — pure-JAX _block_attend and the Pallas chunk
    # kernel (interpreter off-TPU) — must reproduce single-device full
    # attention when sharded over all 8 virtual CPU devices.
    from functools import partial

    from jax.sharding import PartitionSpec as P

    from stoix_tpu.ops.ring_attention import ring_attention
    from stoix_tpu.parallel import create_mesh

    mesh = create_mesh({"data": -1})  # all 8 virtual CPU devices
    b, s, h, d = 1, 64, 2, 16
    q, k, v = _rand_qkv(jax.random.PRNGKey(5), b, s, h, d)
    spec = P(None, "data")
    # check_vma=False for the flash variant only: the Pallas HLO *interpreter*
    # re-traces kernel-internal constants under shard_map, which trips the
    # varying-axes checker (JAX's error text prescribes exactly this
    # workaround). The compiled Mosaic path on real TPU never interprets the
    # kernel body, so the check stays on everywhere else.
    ring = jax.jit(
        jax.shard_map(
            partial(
                ring_attention, axis_name="data", causal=True,
                use_flash=use_flash, interpret=use_flash,
            ),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=not use_flash,
        )
    )
    got = ring(q, k, v)
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


# ---- gradients: flash_attention's backward is a Pallas kernel, the chunk kernel's plain JAX ----

GRAD_TOL = 1e-4  # float32; forward kernel vs reference differ by reassociation


def _weighted_loss(attend, w):
    # A non-uniform cotangent so the backward sees more than ones.
    return lambda q, k, v: jnp.sum(attend(q, k, v) * w)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradient_matches_full_attention(causal):
    from functools import partial

    q, k, v = _rand_qkv(jax.random.PRNGKey(6), 2, 100, 2, 32)
    w = jax.random.normal(jax.random.PRNGKey(7), q.shape)
    flash = partial(
        flash_attention, causal=causal, block_q=64, block_k=64, interpret=True
    )
    got = jax.grad(_weighted_loss(flash, w), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(
        _weighted_loss(partial(full_attention, causal=causal), w), argnums=(0, 1, 2)
    )(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, atol=GRAD_TOL, rtol=GRAD_TOL)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_gradient_matches_full_attention(causal):
    from functools import partial

    from jax.sharding import PartitionSpec as P

    from stoix_tpu.ops.ring_attention import ring_attention
    from stoix_tpu.parallel import create_mesh

    mesh = create_mesh({"data": -1})
    q, k, v = _rand_qkv(jax.random.PRNGKey(8), 1, 64, 2, 16)
    w = jax.random.normal(jax.random.PRNGKey(9), q.shape)
    spec = P(None, "data")
    # check_vma=False: see test_ring_attention_matches_full_attention.
    ring = jax.shard_map(
        partial(
            ring_attention, axis_name="data", causal=causal,
            use_flash=True, interpret=True,
        ),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False,
    )
    got = jax.jit(jax.grad(_weighted_loss(ring, w), argnums=(0, 1, 2)))(q, k, v)
    want = jax.grad(
        _weighted_loss(partial(full_attention, causal=causal), w), argnums=(0, 1, 2)
    )(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, atol=GRAD_TOL, rtol=GRAD_TOL)


def test_ring_never_interprets_by_itself():
    # Interpretation is something a test asks for: with use_flash forced on and
    # interpret left at its default, the traced program holds a pallas_call
    # with interpret=False whatever the backend is.
    from functools import partial

    from jax.sharding import PartitionSpec as P

    from stoix_tpu.ops.ring_attention import ring_attention
    from stoix_tpu.parallel import create_mesh

    mesh = create_mesh({"data": -1})
    q, k, v = _rand_qkv(jax.random.PRNGKey(10), 1, 64, 2, 16)
    spec = P(None, "data")
    ring = jax.shard_map(
        partial(ring_attention, axis_name="data", use_flash=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False,
    )
    jaxpr = str(jax.make_jaxpr(ring)(q, k, v))
    assert "pallas_call" in jaxpr
    assert "interpret=False" in jaxpr and "interpret=True" not in jaxpr


# ---- flash_attention's backward kernel ------------------------------------------


# name -> (length, D, D_v, dtype, the blocks asked for, tolerance)
CASES = {
    "padded_length": (40, 16, 16, jnp.float32, (16, 16), GRAD_TOL),
    "narrower_values": (48, 24, 16, jnp.float32, (16, 16), GRAD_TOL),
    "several_query_blocks": (64, 16, 16, jnp.float32, (16, 32), GRAD_TOL),
    "lane_tiles_widened": (256, 16, 16, jnp.float32, (128, 128), GRAD_TOL),
    "lane_tiles_that_do_not_widen": (300, 8, 8, jnp.float32, (128, 128), GRAD_TOL),
    "bfloat16": (48, 16, 16, jnp.bfloat16, (16, 16), 4e-2),
}


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("case", CASES)
def test_the_backward_kernel_is_the_gradient_of_full_attention(case, causal):
    """dq, dk and dv of the kernel pair under a cotangent that is not all
    ones, against `jax.grad` of the plain path (on the widened inputs where
    they are bfloat16: the kernel accumulates in float32)."""
    length, d, d_v, dtype, (block_q, block_k), tol = CASES[case]
    q, k, v = (
        jax.random.normal(key, (2, length, 2, width), dtype)
        for key, width in zip(jax.random.split(jax.random.PRNGKey(11), 3), (d, d, d_v))
    )
    w = jax.random.normal(jax.random.PRNGKey(12), v.shape, jnp.float32)
    loss = lambda attend: lambda *a: jnp.sum(attend(*a).astype(jnp.float32) * w)
    flash = lambda *a: flash_attention(
        *a, causal=causal, block_q=block_q, block_k=block_k, interpret=True
    )
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    f32 = lambda x: x.astype(jnp.float32)
    want = jax.grad(loss(lambda *a: full_attention(*a, causal=causal)), argnums=(0, 1, 2))(
        f32(q), f32(k), f32(v)
    )
    for g, r, x in zip(got, want, (q, k, v)):
        assert g.dtype == x.dtype and g.shape == x.shape
        np.testing.assert_allclose(f32(g), r, atol=tol, rtol=tol)


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_the_forward_saves_each_rows_log_sum_exp(causal):
    """What the backward kernel reads beside the result: [B, H / heads a
    step, heads a step, padded S] float32, a row a head."""
    from stoix_tpu.ops.pallas_attention import _flash_forward

    q, k, v = _rand_qkv(jax.random.PRNGKey(13), 2, 40, 3, 16)
    out, lse = _flash_forward(q, k, v, causal, 16, 16, True)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 16**-0.5
    if causal:
        scores = jnp.where(jnp.tril(jnp.ones((40, 40), bool)), scores, -jnp.inf)
    want = jax.scipy.special.logsumexp(scores, axis=-1)  # [B, H, S]
    assert lse.dtype == jnp.float32 and lse.shape[0] == 2 and lse.shape[-1] == 48
    np.testing.assert_allclose(lse.reshape(2, 3, 48)[..., :40], want, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(out, full_attention(q, k, v, causal=causal), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_the_gradient_is_two_kernels_and_nothing_of_queries_by_keys(causal):
    """The traced gradient: the forward kernel under its name, the backward
    kernel, and no array with two axes of the sequence's (padded) length —
    the scores the plain backward wrote."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(14), 2, 200, 2, 16)
    loss = lambda *a: jnp.sum(flash_attention(*a, causal=causal, interpret=True) ** 2)
    eqns = list(_flat_eqns(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v).jaxpr))
    kernels = [eqn.params["name"] for eqn in eqns if eqn.primitive.name == "pallas_call"]
    assert kernels == ["flash_attention", "flash_attention_bwd"]
    shapes = [var.aval.shape for eqn in eqns for var in eqn.outvars]
    assert shapes and not [shape for shape in shapes if sum(n in (200, 256) for n in shape) > 1]


def _flat_eqns(jaxpr):
    """Every equation outside a pallas_call's body, nested calls opened."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _flat_eqns(sub)


def test_the_backward_rule_says_which_form_it_took():
    from stoix_tpu.observability import get_registry

    q, k, v = _rand_qkv(jax.random.PRNGKey(15), 1, 32, 1, 16)
    flash = lambda *a: jnp.sum(flash_attention(*a, causal=True, block_q=16, block_k=16, interpret=True))
    jax.grad(flash)(q, k, v)
    gauge = get_registry().gauge("stoix_tpu_attention_backward")
    assert gauge.value({"form": "pallas"}) == 1.0 and gauge.value({"form": "plain"}) == 0.0


@pytest.mark.parametrize(
    "block, length, tile",
    [(128, 512, 512), (128, 16, 128), (128, 4000, 512), (128, 640, 128), (128, 768, 384),
     (128, 256, 256), (16, 40, 16), (64, 256, 64)],
)
def test_the_tile_is_chosen_from_the_length(block, length, tile):
    from stoix_tpu.ops.pallas_attention import _tile

    assert _tile(block, length) == tile


@pytest.mark.parametrize(
    "shape, heads",
    [
        ((32, 192, 128, 512, 4), 8),  # the latent-attention cell
        ((16, 128, 128, 512, 4), 8),
        ((32, 64, 64, 512, 4), 8),
        ((8, 64, 64, 4096, 2), 4),  # a long bfloat16 sequence: dk and dv of 8 heads do not fit
        ((4, 32, 32, 128, 4), 4),
        ((3, 24, 16, 48, 4), 3),
        ((6, 128, 128, 65536, 4), 1),  # one head always goes
    ],
)
def test_the_heads_a_step_divide_the_heads_and_fit(shape, heads):
    from stoix_tpu.ops.pallas_attention import _heads_a_step

    assert _heads_a_step(*shape) == heads
