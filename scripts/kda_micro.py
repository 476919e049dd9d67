"""Chip microbenchmark of the delta rule at the Ling-3 cell's shapes (PERF.md
section 6, PRs 40 and 41, has its readings). `update` (the default): the
rule alone over 8 x 512 tokens of 32 heads of 128, forward and forward +
backward, the Pallas kernel pair by chunk size against `delta_rule_chunked`,
each beside its error against the position-by-position recurrence; then one
whole mixer's forward and gradient. `decode`: a decode step at 64 and 32
sequences through the Pallas state update and through the plain one, the
whole mixer and the rule alone. Prints ms a call. Run on the chip from the
root of the checkout: `python3 scripts/kda_micro.py [update] [decode]`."""
import os, sys, time
sys.path.insert(0, os.getcwd())
import jax, jax.numpy as jnp
from stoix_tpu.networks import kda
from stoix_tpu.ops import delta_rule

D, H, d, K = 2560, 32, 128, 4
W = H * d
key = jax.random.PRNGKey(0)
what = sys.argv[1:] or ["update"]
print(jax.devices(), flush=True)


def timed(fn, *args, n=5):
    out = fn(*args); jax.block_until_ready(out)
    t = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return round((time.perf_counter() - t) / n * 1e3, 2)


u = jax.random.normal(key, (8, 512, D))
mixer = kda.KimiDeltaAttention(D, H, d, K, -5.0, 1e-6)
params = mixer.init(key, u, method="forward")

if "update" in what:
    keys = jax.random.split(key, 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q, k = (unit(jax.random.normal(x, (8, 512, H, d))) for x in keys[:2])
    v = jax.random.normal(keys[2], (8, 512, H, d))
    g = -5.0 * jax.nn.sigmoid(jax.random.normal(keys[3], (8, 512, H, d)) * 2.0 - 2.0)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (8, 512, H)))
    weight = jax.random.normal(keys[5], (8, 512, H, d))
    args = (q * d**-0.5, k, v, g, beta)
    loss = lambda rule: lambda *a: jnp.sum(rule(*a)[0] * weight)
    # The recurrence keeps a state a position for its gradient (8 GiB at this size): it is
    # the reference on one sequence's first 8 heads, which no other sequence or head enters.
    part = lambda x: x[:1, :, :8]
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda *a: delta_rule.delta_rule_scan(*a)[0])(*map(part, args))
        want_grad = jax.jit(jax.grad(
            lambda *a: jnp.sum(delta_rule.delta_rule_scan(*a)[0] * part(weight)), argnums=range(5)
        ))(*map(part, args))
    rms = lambda x: float(jnp.sqrt(jnp.mean(jnp.square(x))))
    forms = {"chunked 16": delta_rule.delta_rule_chunked}
    for chunk in (64, 32, 16):
        forms[f"kernel {chunk}"] = lambda *a, chunk=chunk: delta_rule.delta_rule_update_kernel(*a, chunk=chunk)
    for name, rule in forms.items():
        forward, grad = jax.jit(lambda *a: rule(*a)[0]), jax.jit(jax.grad(loss(rule), argnums=range(5)))
        print(
            name, "forward ms", timed(forward, *args), "forward + backward ms", timed(grad, *args),
            "error (RMS over the reference's RMS) out", round(rms(part(forward(*args)) - want) / rms(want), 5),
            "gradients", [round(rms(part(a) - b) / rms(b), 5) for a, b in zip(grad(*args), want_grad)], flush=True,
        )
    print("the mixer takes the form", delta_rule.update_form(512, H, d, d))
    grad = jax.jit(jax.grad(lambda p, x: mixer.apply(p, x, method="forward").sum(), argnums=(0, 1)))
    fwd = jax.jit(lambda p, x: mixer.apply(p, x, method="forward"))
    print("mixer forward ms", timed(fwd, params, u), "gradient ms", timed(grad, params, u), flush=True)

if "decode" in what:
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
