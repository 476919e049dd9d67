"""Kimi Delta Attention (`model_type` `bailing_hybrid`'s linear-attention
layer; Kimi Linear, arXiv:2510.26692 section 3) as a mixer of
networks/lfm2.py's stack: a decode state that is a MATRIX a head, constant in
the sequence length, which every token rewrites.

With u the operator-normed hidden state, H heads of d (published: 32 of 128),
K = `conv_kernel` taps (4), no bias anywhere:

    q = SiLU(conv(u W_q)),  k = SiLU(conv(u W_k)),  v = SiLU(conv(u W_v))  (depthwise, causal)
    q_h <- q_h / |q_h|_2 / sqrt(d),   k_h <- k_h / |k_h|_2
    g_t = lower_bound * sigmoid(exp(A_log_h) * (u W_f + dt_bias))    (a channel, in (lower_bound, 0))
    beta_t = sigmoid(u W_beta)                                        (one a head)
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T   (S [d, d], S_0 = 0)
    o_t = S_t^T q_t
    y = W_o [ RMSNorm(o_t) * sigmoid(u W_g)_h ]                      (ONE norm over all H d outputs)

The layer is not rotated: its decay carries position. `lower_bound` = -5
(`kda_lower_bound`, `kda_safe_gate`) is what lets the chunked forms of the
recurrence divide by cumulative decays over 16 positions in float32
(ops/delta_rule.py, which holds the four forms of the recurrence).

  * `KimiDeltaAttention.forward` (whole sequences, the update):
    `delta_rule_update` — on a TPU, for heads of 128 in blocks of 8 and
    sequences of whole chunks of 64, the Pallas kernel pair (a head's state
    in VMEM across the chunks, its own backward pass, whose residuals are
    the operands and 128 MiB a layer of chunk-starting states at 8 sequences
    of 512); elsewhere the chunked form in plain JAX. The mixer is
    rematerialised in the backward pass (`jax.checkpoint`): what its five
    projections keep for their gradient does not fit five times beside 10.7
    GiB of state, and the block's input is 40 MiB (the learner compiled for
    a described v5e: 13.08 GiB of the chip's 15.75; PERF.md section 6, PR
    41). The gauge `stoix_tpu_delta_rule_update{form}` reads 1 on the form
    the most recently traced update took — `kernel` or `chunked` — and 0 on
    the others (`scan`, the position-by-position recurrence, is the
    reference's and the tests').
  * `KimiDeltaAttention.step` (one token, the decode): the convolutions
    against their tails, then `delta_rule_step` against the matrix state.

`DeltaState` is the layer's decode state: `s` [B, H, d, d] float32 (2 MiB a
sequence a layer at the published widths), `conv` [B, K - 1, 3 H d], the
last K - 1 inputs of the three convolutions side by side (q | k | v), and
`fresh` [B]. Unlike the rows of a cache beyond `length`, a matrix state is
read whole at the next step: a new sequence MUST start from zeros
(`Lfm2LM.reset_carry`). Zeroing 2 MiB a sequence a layer is a pass over the
state of its own — as much as the step's — so `reset_carry` zeroes the tails
and marks the sequence `fresh`, and the next step decays a fresh sequence's
matrix to nothing (g = -inf: it is read as zeros whatever it holds, and
overwritten) in the pass it makes anyway.

Parameters, by name: wq wk wv wf [D, H d], q_conv k_conv v_conv [K, H d],
dt_bias [H d], a_log [H], wbeta wg [D, H], out_norm [H d], wo [H d, D].
normal(0.02), the norm starts at one, `a_log` at 0 (a unit rate), and
`dt_bias` so that a layer's channels forget at rates spread evenly in the
logarithm between a thousandth and one a token at u W_f = 0 (the public
implementation draws its rates so; the published values are the
checkpoint's).
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from stoix_tpu.networks.olmoe import rms_norm
from stoix_tpu.observability import SCOPES, annotate, get_registry
from stoix_tpu.ops.delta_rule import delta_rule_step, delta_rule_update, update_form

_INIT = nn.initializers.normal(0.02)
_L2_EPS = 1e-6  # joins the sum of squares a head's q and k are divided by the root of


class DeltaState(NamedTuple):
    s: jax.Array  # [B, H, d, d] float32: the matrix a head, rows by key channel
    conv: jax.Array  # [B, K - 1, 3 H d] the convolutions' last inputs (q | k | v), oldest first
    fresh: jax.Array  # [B] bool: a new sequence starts here: `s` counts as zeros at the next step


def _rates_init(lower_bound: float):
    """dt_bias [H d]: the logit of (rate / -lower_bound), rates log-uniform in
    [1e-3, 1] — a channel's decay a token at u W_f = 0 and `a_log` = 0."""

    def init(key: jax.Array, shape: Tuple[int, ...], dtype=jnp.float32) -> jax.Array:
        rate = jnp.exp(jax.random.uniform(key, shape, dtype, jnp.log(1e-3), 0.0))
        share = rate / -lower_bound
        return jnp.log(share) - jnp.log1p(-share)

    return init


def _update_form_gauge():
    return get_registry().gauge(
        "stoix_tpu_delta_rule_update",
        "1 on the form of the gated delta rule the most recently traced update (a delta-attention "
        "layer's pass over whole sequences) took, 0 on the others: kernel (the Pallas kernel "
        "pair, a head's state in VMEM across chunks of 64 positions), chunked (chunks of 16 "
        "positions, a loop over chunks, plain JAX) or scan (position by position)",
    )


def _l2_normalise(x: jax.Array) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + _L2_EPS)


class KimiDeltaAttention(nn.Module):
    """The delta-rule linear-attention layer. Input: the operator-normed
    hidden state."""

    hidden_size: int
    num_heads: int
    head_dim: int
    conv_kernel: int
    lower_bound: float
    rms_eps: float
    trace_scope = "delta_mixer"

    def setup(self) -> None:
        d, heads, width = self.hidden_size, self.num_heads, self.num_heads * self.head_dim
        shapes = {
            **{name: (_INIT, (d, width)) for name in ("wq", "wk", "wv", "wf")},
            **{name: (_INIT, (self.conv_kernel, width)) for name in ("q_conv", "k_conv", "v_conv")},
            "dt_bias": (_rates_init(self.lower_bound), (width,)),
            "a_log": (nn.initializers.zeros, (heads,)),
            "wbeta": (_INIT, (d, heads)), "wg": (_INIT, (d, heads)),
            "out_norm": (nn.initializers.ones, (width,)), "wo": (_INIT, (width, d)),
        }
        # One dict of the leaves by name: what the rematerialised pass is a function of.
        self.weights = {
            name: self.param(name, init, shape) for name, (init, shape) in shapes.items()
        }

    def _heads(self, x: jax.Array) -> jax.Array:
        return x.reshape(x.shape[:-1] + (self.num_heads, self.head_dim))

    def _gates(self, w: Dict[str, jax.Array], u: jax.Array):
        """u [..., D] -> (log-decay g [..., H, d], beta [..., H], output gate [..., H])."""
        rate = jnp.exp(w["a_log"])[:, None] * self._heads(u @ w["wf"] + w["dt_bias"])
        return (
            self.lower_bound * jax.nn.sigmoid(rate), jax.nn.sigmoid(u @ w["wbeta"]),
            jax.nn.sigmoid(u @ w["wg"]),
        )

    def _qkv(self, mixed: Tuple[jax.Array, ...]):
        """The convolutions' results -> q, k, v [..., H, d], q and k normalised."""
        q, k, v = (self._heads(jax.nn.silu(x)) for x in mixed)
        return _l2_normalise(q) * self.head_dim**-0.5, _l2_normalise(k), v

    def _out(self, w: Dict[str, jax.Array], out: jax.Array, gate: jax.Array) -> jax.Array:
        """o [..., H, d] -> y [..., D]: one norm over all H d, a gate a head, W_o."""
        flat = out.reshape(out.shape[:-2] + (-1,))
        normed = self._heads(rms_norm(flat, w["out_norm"], self.rms_eps))
        return (normed * gate[..., None]).reshape(normed.shape[:-2] + (-1,)) @ w["wo"]

    def _mix(self, w: Dict[str, jax.Array], u: jax.Array) -> jax.Array:
        length, taps = u.shape[1], self.conv_kernel

        def conv(x: jax.Array, weight: jax.Array) -> jax.Array:
            x = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
            return sum(weight[j] * x[:, j:j + length] for j in range(taps))

        projected = tuple(u @ w[name] for name in ("wq", "wk", "wv"))
        with annotate(SCOPES["delta_conv"]):
            q, k, v = self._qkv(tuple(
                conv(x, w[name]) for x, name in zip(projected, ("q_conv", "k_conv", "v_conv"))
            ))
        g, beta, gate = self._gates(w, u)
        with annotate(SCOPES["delta_rule"]):
            out, _ = delta_rule_update(q, k, v, g, beta)
        return self._out(w, out, gate)

    def forward(self, u: jax.Array) -> jax.Array:
        """u [B, T, D]: every position reads the state its predecessors left."""
        took = update_form(u.shape[1], self.num_heads, self.head_dim, self.head_dim)
        for form in ("kernel", "chunked", "scan"):
            _update_form_gauge().set(float(form == took), {"form": form})
        return jax.checkpoint(self._mix)(dict(self.weights), u)

    def step(self, u: jax.Array, state: DeltaState, length: jax.Array):
        """u [B, D] against the tails and the matrix; both move on by one
        token. `length` is not read: the decay carries position."""
        w = self.weights
        projected = jnp.concatenate([u @ w[name] for name in ("wq", "wk", "wv")], axis=-1)
        with annotate(SCOPES["delta_conv"]):
            window = jnp.concatenate([state.conv, projected[:, None]], axis=1)  # [B, K, 3 H d]
            taps = jnp.concatenate([w[name] for name in ("q_conv", "k_conv", "v_conv")], axis=-1)
            q, k, v = self._qkv(jnp.split(jnp.sum(taps * window, axis=1), 3, axis=-1))
        g, beta, gate = self._gates(w, u)
        with annotate(SCOPES["delta_rule"]):
            g = jnp.where(state.fresh[:, None, None], -jnp.inf, g)
            out, s = delta_rule_step(state.s, q, k, v, g, beta)
        return self._out(w, out, gate), DeltaState(s, window[:, 1:], jnp.zeros_like(state.fresh))
