"""The TPU's compiler on the kernels of the main path at their real widths,
without a chip: libtpu compiles for a v5e that is described, not attached
(the `on-chip-measurement` guide, section 2). What Mosaic refuses — a slice
off the tiling, more VMEM than a kernel may take — it refuses here, at no
chip time; nothing runs, so results and times stay the chip's to give.

The topology is described inside a fixture, never while a module is imported:
one process at a time may load the TPU's library, and every worker imports
every test file."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from stoix_tpu.ops import pallas_attention
from stoix_tpu.utils import config as config_lib


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu from describing a chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache and
    # cannot be read back without one: keep it out.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


# The benchmark's block-diffusion cell: a minibatch of 16 sequences of [clean
# 516 ; 2 noisy copies of 512], 32 query heads on 4 key/value heads of 128.
CELL = dict(block_length=4, clean=516, copies=2)
SEQUENCES, POSITIONS, HEADS, KV_HEADS, HEAD_DIM = 16, 1540, 32, 4, 128


def _compiled(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip) for shape in shapes]
    return jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).compile()


@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_mosaic_takes_the_block_mask_kernels_at_the_cells_shape(one_chip, what):
    attend = lambda q, k, v: pallas_attention.block_mask_attention(q, k, v, **CELL)
    q = (SEQUENCES, POSITIONS, HEADS, HEAD_DIM)
    kv = (SEQUENCES, POSITIONS, KV_HEADS, HEAD_DIM)
    if what == "forward":
        text = _compiled(attend, one_chip, q, kv, kv).as_text()
        assert text.count("block_mask_attention_fwd") and "block_mask_attention_bwd" not in text
    else:
        loss = lambda q, k, v, w: jnp.sum(attend(q, k, v) * w)
        compiled = _compiled(
            jax.grad(loss, argnums=(0, 1, 2)), one_chip, q, kv, kv, (SEQUENCES, POSITIONS, HEADS * HEAD_DIM)
        )
        text = compiled.as_text()
        assert "block_mask_attention_fwd" in text and "block_mask_attention_bwd" in text
        # q, the result and their cotangents pass to and from the kernels as
        # they lie: nothing of their size is copied around them but what the
        # entry's own layouts ask for.
        assert compiled.memory_analysis().temp_size_in_bytes < 2.5 * 4 * SEQUENCES * POSITIONS * HEADS * HEAD_DIM


# The benchmark's LFM2 cell: 128 sequences decode 512 tokens through the
# published widths' first six layers (configs/network/lfm2_moe.yaml), one of
# them attention over a cache [512, 128, 8, 64]: head size 64 is half a lane
# row, so the cache's reads want the 128-wide batch axis minor.
LFM2_SEQUENCES, LFM2_TOKENS, LFM2_VOCAB = 128, 512, 16384


@pytest.mark.parametrize("together", [True, False], ids=["one_position", "a_position_a_sequence"])
def test_the_lfm2_decode_copies_no_cache_where_the_sequences_move_together(one_chip, together):
    """The decode scan of `Lfm2LM.step` at the cell's shape. With a position a
    sequence the row is scattered, the scatter keeps the cache as it is
    carried, and every branch of `_attend_cache` begins with a copy of the
    whole cache, keys and values, into the layout its reads want: 1.07 GB a
    step beside a decode that reads 0.27 GB at most (PERF.md section 6, PR
    35). With one position for all, one slab is written in place into a cache
    that lies as it is read, and nothing of the cache's size is copied."""
    network = config_lib.compose(config_lib.default_config_dir(), "network/lfm2_moe.yaml", [])
    actor = config_lib.instantiate(network.actor_network, vocab_size=LFM2_VOCAB)
    struct = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    params = jax.tree.map(struct, jax.eval_shape(
        lambda: actor.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32), method="forward")
    ))
    tokens = jax.ShapeDtypeStruct((LFM2_TOKENS, LFM2_SEQUENCES), jnp.int32, sharding=one_chip)

    def decode(params, tokens):
        def one(carry, token):
            logits, _, carry, _ = actor.apply(params, carry, token, method="step")
            return carry, jnp.argmax(logits, axis=-1)

        carry = actor.init_carry(LFM2_SEQUENCES, LFM2_TOKENS, together=together)
        return jax.lax.scan(one, carry, tokens)[1]

    lowered = jax.jit(decode).trace(params, tokens).lower(lowering_platforms=("tpu",))
    text = lowered.compile().as_text()
    cache = rf"f32\[{LFM2_TOKENS},{LFM2_SEQUENCES},{actor.num_kv_heads},{actor.head_dim}\]"
    assert re.search(cache, text)  # the cache is in the program under this name
    copies = re.findall(rf"= {cache}\{{[^}}]*\}} copy\(", text)
    assert (not copies) if together else copies


# The benchmark's Kanana-2 cell: 128 sequences decode 512 tokens through five
# latent-attention layers at the published widths
# (configs/network/kanana2_moe.yaml), each against latent rows [128, 512, 576].


def test_the_latent_decode_copies_no_cache_and_goes_through_the_kernel(one_chip, monkeypatch):
    """The decode scan of the stack's `step` at the cell's shape, steered onto
    the TPU's branches (code that asks `jax.default_backend()` sees the CPU
    here): the new row is one slab written in place into rows that lie as the
    Pallas kernel reads them, sequence-major, and nothing of a cache's size is
    copied — XLA's own plan for the two batched products wrote a bfloat16
    copy of the cache in another layout every step (PERF.md section 6, PR 38)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    network = config_lib.compose(config_lib.default_config_dir(), "network/kanana2_moe.yaml", [])
    actor = config_lib.instantiate(network.actor_network, vocab_size=16032)
    struct = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    params = jax.tree.map(struct, jax.eval_shape(
        lambda: actor.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32), method="forward")
    ))
    tokens = jax.ShapeDtypeStruct((512, 128), jnp.int32, sharding=one_chip)

    def decode(params, tokens):
        def one(carry, token):
            logits, _, carry, _ = actor.apply(params, carry, token, method="step")
            return carry, jnp.argmax(logits, axis=-1)

        return jax.lax.scan(one, actor.init_carry(128, 512, together=True), tokens)[1]

    lowered = jax.jit(decode).trace(params, tokens).lower(lowering_platforms=("tpu",))
    text = lowered.compile().as_text()
    cache = r"f32\[128,512,576\]"
    assert re.search(cache, text) and "latent_decode_attention" in text
    assert not re.findall(rf"= {cache}\{{[^}}]*\}} copy\(", text)
    assert set(re.findall(rf"{cache}\{{(\d,\d,\d)", text)) == {"2,1,0"}  # sequence-major, rows minor
    assert not re.search(r"bf16\[128,\d+,576\]", text)  # no lower-precision copy of the rows
