"""`ops/qk_norm_rope.py` — the per-head q/k norm and rotation as one Pallas
pass each way — against `rms_norm` + `rope`, the plain statement every backend
but a TPU runs, through the Pallas interpreter on the CPU. What Mosaic makes
of the pair at the cell's shape is tests/test_tpu_compile.py's to say, and
what the chip computes chip_smoke.py's."""

import jax
import jax.numpy as jnp
import pytest

from stoix_tpu.networks import sdar
from stoix_tpu.networks.olmoe import rms_norm, rope, rope_angles
from stoix_tpu.ops import qk_norm_rope

THETA, EPS = 1000000.0, 1e-6


def _copies_positions(clean: int, block: int, copies: int) -> jax.Array:
    """The positions of `[clean ; noisy copies]` as `trunk_copies` makes them:
    every copy's are the tokens' own, so they repeat."""
    own = jnp.arange(clean)
    return jnp.concatenate([own, jnp.tile(own[block:], copies)])


# rows a sequence, heads (a multiple of 8 is written a head a sublane, fewer side by side), head size
CASES = {
    "q_whole_tiles": (256, 32, 128),
    "q_partial_last_tile": (200, 32, 128),
    "k_whole_tiles": (128, 4, 128),
    "k_partial_last_tile": (200, 4, 128),
    "q_positions_repeated": (68 + 2 * 64, 8, 128),  # clean 68, two copies of 64
    "q_two_lane_groups_a_head": (136, 8, 256),
    "head_of_64_takes_the_plain_path": (200, 8, 64),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_pair_is_rms_norm_then_rope(monkeypatch, case):
    """The result and the gradients by the rows and by the norm's weight, to
    float32's rounding: 1e-5 of the reference's RMS at the worst element (the
    kernel takes the same products in the same order; the sums over a head's
    lanes and over the rows are added up in another order). A head of 64 is
    half a lane row: `gqa_qkv` keeps `rms_norm` + `rope` for it on a TPU too."""
    rows, heads, head_dim = CASES[case]
    batch = 2
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 4)
    x = 0.7 * jax.random.normal(keys[0], (batch, rows, heads * head_dim))
    weight = 1.0 + 0.1 * jax.random.normal(keys[1], (head_dim,))
    weigh = jax.random.normal(keys[2], (batch, rows, heads, head_dim))
    if case == "q_positions_repeated":
        positions = jnp.broadcast_to(_copies_positions(68, 4, 2), (batch, rows))
    else:
        positions = jax.random.randint(keys[3], (batch, rows), 0, 4096)

    def plain(x, weight):
        heads_apart = x.reshape(batch, rows, heads, head_dim)
        return rope(rms_norm(heads_apart, weight, EPS), positions, THETA)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if head_dim % 128:
        assert qk_norm_rope.norm_rope_form(rows, head_dim) == "plain"
        layer = {
            "wq": jnp.eye(heads * head_dim), "wk": jnp.eye(heads * head_dim)[:, :head_dim],
            "wv": jnp.eye(heads * head_dim)[:, :head_dim], "q_norm": weight, "k_norm": weight,
        }
        q, k, _ = sdar.gqa_qkv(layer, x, positions, heads, 1, head_dim, THETA, EPS)
        assert jnp.array_equal(q, plain(x, weight)) and jnp.array_equal(k, q[:, :, :1])
        return
    assert qk_norm_rope.norm_rope_form(rows, head_dim) == "kernel"
    assert qk_norm_rope.norm_rope_form(4, head_dim) == "plain"  # the rollout's block passes

    def kernel(x, weight):
        return qk_norm_rope.qk_norm_rope(
            x, weight, rope_angles(positions, head_dim, THETA), heads=heads, eps=EPS, interpret=True
        )

    def close(got, want):
        limit = 1e-5 * float(jnp.sqrt(jnp.mean(jnp.square(want))))
        assert float(jnp.max(jnp.abs(got - want))) <= limit

    close(kernel(x, weight), plain(x, weight))
    grads = lambda fn: jax.grad(lambda x, weight: jnp.sum(fn(x, weight) * weigh), (0, 1))(x, weight)
    for got, want in zip(grads(kernel), grads(plain)):
        close(got, want)


def test_off_a_tpu_the_projections_take_the_plain_path():
    assert qk_norm_rope.norm_rope_form(1540, 128) == "plain"
