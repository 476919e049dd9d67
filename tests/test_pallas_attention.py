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


# ---- gradients: the kernels' custom_vjp backward is plain JAX -----------------

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
