"""Attention networks: multi-head self-attention + a transformer torso.

The reference's network zoo has no attention ("no transformer in the network
zoo", SURVEY.md §5 long-context); sequence memory is RNN-only. The TPU build
adds a causal transformer torso as a first-class sequence model: MXU-friendly
batched matmuls end to end, usable anywhere the recurrent torsos are (time-
major stored-sequence learners like rec_r2d2/rec_ppo consume [B, T, ...]
windows), and wired for sequence parallelism — `attention_fn` accepts the
ring-attention primitive (stoix_tpu/ops/ring_attention.py) so the SAME module
runs single-device (full attention) or with the time axis sharded over a mesh
ring (shard_map + ppermute).

Pre-LN blocks (the stable variant for RL-scale training), learned positional
embeddings, causal masking by default.
"""

from __future__ import annotations

from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from stoix_tpu.ops import best_attention

AttentionFn = Callable[..., jax.Array]  # (q, k, v, causal=...) -> out


class MultiHeadSelfAttention(nn.Module):
    num_heads: int = 4
    head_dim: int = 32
    causal: bool = True
    attention_fn: Optional[AttentionFn] = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        # x: [B, T, F] -> [B, T, H*D]
        b, t, _ = x.shape
        proj = nn.DenseGeneral(
            (3, self.num_heads, self.head_dim),
            kernel_init=nn.initializers.orthogonal(1.0),
            name="qkv",
        )(x)  # [B, T, 3, H, D]
        q, k, v = proj[:, :, 0], proj[:, :, 1], proj[:, :, 2]
        # Default dispatch: the Pallas flash kernel on TPU (fused online
        # softmax forward, no [S, S] score matrix in HBM; plain-JAX backward
        # through its custom_vjp), pure-JAX full attention elsewhere.
        attend = self.attention_fn or best_attention
        out = attend(q, k, v, causal=self.causal)  # [B, T, H, D]
        out = out.reshape(b, t, self.num_heads * self.head_dim)
        return nn.Dense(
            self.num_heads * self.head_dim,
            kernel_init=nn.initializers.orthogonal(1.0),
            name="out",
        )(out)


class TransformerBlock(nn.Module):
    num_heads: int = 4
    head_dim: int = 32
    ffn_dim: int = 256
    causal: bool = True
    attention_fn: Optional[AttentionFn] = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        width = self.num_heads * self.head_dim
        attn = MultiHeadSelfAttention(
            num_heads=self.num_heads,
            head_dim=self.head_dim,
            causal=self.causal,
            attention_fn=self.attention_fn,
        )(nn.LayerNorm()(x))
        x = x + attn
        h = nn.LayerNorm()(x)
        h = nn.Dense(self.ffn_dim, kernel_init=nn.initializers.orthogonal(jnp.sqrt(2)))(h)
        h = nn.silu(h)
        h = nn.Dense(width, kernel_init=nn.initializers.orthogonal(jnp.sqrt(2)))(h)
        return x + h


class TransformerTorso(nn.Module):
    """Causal transformer over the time axis: [B, T, F] -> [B, T, width].

    Drop-in sequence torso for stored-sequence learners; set
    `attention_fn=partial(ring_attention, axis_name=...)` inside a shard_map
    to shard T over a mesh ring for long-context training.
    """

    num_layers: int = 2
    num_heads: int = 4
    head_dim: int = 32
    ffn_dim: int = 256
    max_timesteps: int = 512
    causal: bool = True
    attention_fn: Optional[AttentionFn] = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, t, _ = x.shape
        width = self.num_heads * self.head_dim
        x = nn.Dense(width, kernel_init=nn.initializers.orthogonal(jnp.sqrt(2)))(x)
        pos = self.param(
            "positional_embedding",
            nn.initializers.normal(0.02),
            (self.max_timesteps, width),
        )
        x = x + pos[:t][None]
        for i in range(self.num_layers):
            x = TransformerBlock(
                num_heads=self.num_heads,
                head_dim=self.head_dim,
                ffn_dim=self.ffn_dim,
                causal=self.causal,
                attention_fn=self.attention_fn,
                name=f"block_{i}",
            )(x)
        return nn.LayerNorm()(x)
