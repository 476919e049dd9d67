"""Transformer torso tests: shapes, causality, and ring-attention pluggability
(the long-context path: time axis sharded over the mesh ring)."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from stoix_tpu.networks.attention import TransformerTorso
from stoix_tpu.ops.ring_attention import ring_attention
from stoix_tpu.parallel import create_mesh
from jax.sharding import PartitionSpec as P


def test_shapes_and_jit():
    torso = TransformerTorso(num_layers=2, num_heads=2, head_dim=8, ffn_dim=32)
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 16, 5))
    params = torso.init(jax.random.PRNGKey(1), x)
    out = jax.jit(torso.apply)(params, x)
    assert out.shape == (3, 16, 16)
    assert bool(jnp.all(jnp.isfinite(out)))


def test_causality():
    torso = TransformerTorso(num_layers=2, num_heads=2, head_dim=8, ffn_dim=32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 5))
    params = torso.init(jax.random.PRNGKey(1), x)
    out = torso.apply(params, x)
    # Perturb the future; the past must not change.
    x2 = x.at[:, 10:].add(3.0)
    out2 = torso.apply(params, x2)
    np.testing.assert_allclose(
        np.asarray(out[:, :10]), np.asarray(out2[:, :10]), rtol=1e-5, atol=1e-5
    )
    assert not np.allclose(np.asarray(out[:, 10:]), np.asarray(out2[:, 10:]))


def test_ring_attention_plugs_in_and_matches_full():
    # The same torso params, evaluated with full attention single-device vs
    # ring attention with the TIME axis sharded over the 8-device mesh, must
    # produce identical outputs.
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 5))
    full_torso = TransformerTorso(num_layers=1, num_heads=2, head_dim=8, ffn_dim=32)
    params = full_torso.init(jax.random.PRNGKey(1), x)

    mesh = create_mesh({"data": -1})
    ring_torso = TransformerTorso(
        num_layers=1,
        num_heads=2,
        head_dim=8,
        ffn_dim=32,
        attention_fn=partial(ring_attention, axis_name="data"),
    )

    def apply_sharded(params, x):
        return ring_torso.apply(params, x)

    # Inside shard_map each device sees a LOCAL time slice, so the learned
    # positional embedding would index with local t. This test pins the
    # attention swap in isolation: zero the positional embedding (making
    # local-vs-global indexing immaterial) and compare against the full
    # module on the same zeroed params. Global position offsets for sharded
    # embeddings are the caller's concern (add pos before shard_map).
    params["params"]["positional_embedding"] = jnp.zeros_like(
        params["params"]["positional_embedding"]
    )
    expected = full_torso.apply(params, x)

    sharded_apply = jax.jit(
        jax.shard_map(
            apply_sharded,
            mesh=mesh,
            in_specs=(P(), P(None, "data")),
            out_specs=P(None, "data"),
        )
    )
    out = sharded_apply(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), rtol=2e-4, atol=2e-4)
