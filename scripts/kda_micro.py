"""Chip microbenchmark of the delta mixer at the Ling-3 cell's shapes (PERF.md
section 6, PR 40, has its readings): the forward and the gradient of one
mixer at 8 x 512 tokens by head groups, then a decode step at 64 and 32
sequences through the Pallas state update and through the plain one, the
whole mixer and the rule alone. Prints ms a call. Run on the chip from the
root of the checkout: `python3 scripts/kda_micro.py`."""
import os, sys, time
sys.path.insert(0, os.getcwd())
import jax, jax.numpy as jnp
from stoix_tpu.networks import kda

D, H, d, K = 2560, 32, 128, 4
W = H * d
key = jax.random.PRNGKey(0)
print(jax.devices(), flush=True)


def timed(fn, *args, n=5):
    out = fn(*args); jax.block_until_ready(out)
    t = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / n * 1e3


u = jax.random.normal(key, (8, 512, D))
mixer = kda.KimiDeltaAttention(D, H, d, K, -5.0, 1e-6)
params = mixer.init(key, u, method="forward")
for groups in (4, 1, 2, 8):
    kda._HEAD_GROUPS = groups
    grad = jax.jit(jax.grad(lambda p, x: mixer.apply(p, x, method="forward").sum(), argnums=(0, 1)))
    fwd = jax.jit(lambda p, x: mixer.apply(p, x, method="forward"))
    print("groups", groups, "forward ms", round(timed(fwd, params, u), 2), "gradient ms", round(timed(grad, params, u), 2), flush=True)
kda._HEAD_GROUPS = 4
from stoix_tpu.ops import delta_rule
for form in ("kernel", "plain"):
  kda.delta_rule_step = {"kernel": delta_rule.delta_rule_step_kernel, "plain": delta_rule.delta_rule_step_plain}[form]
  for batch in (64, 32):
    state = kda.DeltaState(jnp.zeros((batch, H, d, d)), jnp.zeros((batch, K - 1, 3 * W)), jnp.zeros((batch,), bool))
    x = jax.random.normal(key, (batch, D))

    def steps(p, x, state):
        def one(state, _):
            out, state = mixer.apply(p, x, state, jnp.int32(0), method="step")
            return state, out[0, 0]
        return jax.lax.scan(one, state, None, 64)

    print(form, "decode batch", batch, "ms a step", round(timed(jax.jit(steps), params, x, state) / 64, 4), flush=True)
    rule = jax.jit(lambda s, *a: jax.lax.scan(lambda s, _: (kda.delta_rule_step(s, *a)[1], None), s, None, 64)[0])
    args = [jax.random.normal(key, (batch, H, d)) for _ in range(3)] + [-jax.nn.sigmoid(jax.random.normal(key, (batch, H, d))), jax.nn.sigmoid(jax.random.normal(key, (batch, H)))]
    print(form, "rule alone batch", batch, "ms a step", round(timed(rule, state.s, *args) / 64, 4), flush=True)
