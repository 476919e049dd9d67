"""Tensor-parallel block tests (stoix_tpu/parallel/tp.py): the Megatron-style
column->row split must match the unsharded oracle exactly (one psum per
block), forward and backward, on a 2D data x model mesh."""

import jax

import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from stoix_tpu.parallel.tp import (
    column_row_block,
    init_column_row_params,
    reference_block,
    tp_specs,
)


def _mesh(dp, model):
    devices = jax.devices("cpu")
    if len(devices) < dp * model:
        pytest.skip(f"needs {dp * model} virtual devices")
    return Mesh(np.asarray(devices[: dp * model]).reshape(dp, model), ("data", "model"))


def test_forward_matches_oracle():
    mesh = _mesh(2, 4)
    params = init_column_row_params(jax.random.PRNGKey(0), 6, 16, 3, num_shards=4)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 6), jnp.float32)
    param_specs, data_spec = tp_specs()

    fwd = jax.jit(
        jax.shard_map(
            lambda p, x: column_row_block(p, x, axis_name="model"),
            mesh=mesh,
            in_specs=(param_specs, data_spec),
            out_specs=data_spec,
        )
    )
    np.testing.assert_allclose(
        np.asarray(fwd(params, x)), np.asarray(reference_block(params, x)), rtol=1e-5
    )


def test_backward_matches_oracle():
    mesh = _mesh(2, 2)
    params = init_column_row_params(jax.random.PRNGKey(2), 5, 8, 2, num_shards=2)
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 5), jnp.float32)
    param_specs, data_spec = tp_specs()

    def sharded_loss(p, x):
        out = column_row_block(p, x, axis_name="model")
        return jax.lax.pmean(jnp.mean(out**2), "data")

    def step(p, x):
        loss, grads = jax.value_and_grad(sharded_loss)(p, x)
        return loss, jax.lax.pmean(grads, "data")

    loss, grads = jax.jit(
        jax.shard_map(
            step,
            mesh=mesh,
            in_specs=(param_specs, data_spec),
            out_specs=(P(), param_specs),
        )
    )(params, x)

    oracle_loss, oracle_grads = jax.value_and_grad(
        lambda p: jnp.mean(reference_block(p, x) ** 2)
    )(params)
    np.testing.assert_allclose(float(loss), float(oracle_loss), rtol=1e-5)
    for g, og in zip(jax.tree.leaves(grads), jax.tree.leaves(oracle_grads)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(og), rtol=1e-4, atol=1e-6)


def test_hidden_must_divide():
    with pytest.raises(ValueError, match="not divisible"):
        init_column_row_params(jax.random.PRNGKey(0), 4, 10, 2, num_shards=4)
