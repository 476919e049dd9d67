"""Chip microbenchmark of the held experts' SwiGLU at a decode step's chunk
(PERF.md section 6, PR 48, has its readings): three forms of
`networks/olmoe.py::_held_swiglu`, ms a call, at the decode shape of each of
the six held-expert cells (rollout and, where its chunk differs, evaluator)
and at made-up shapes that part the widths and the rows: (a) the three
`jax.lax.ragged_dot`s; (b) XLA's batched product over ALL held experts under
a row-to-expert mask; (c) the Pallas kernel `ops/held_swiglu.py`, wherever its
blocks fit. Each form runs inside a `lax.scan` of `--steps` steps of `LAYERS`
calls, each layer with weights of its own and rows that depend on the call
before, so the weights come from HBM every call as in the rollout; the sizes
of a step's calls are drawn as uniform routing gives them
(`tokens * top_k` pairs thrown at all the router's experts, the held ones'
counts kept, cut to the chunk). Beside each time: the form's largest error
against the `ragged_dot`s at HIGHEST precision, over that product's largest
entry. Writes `chiprun_out/held_swiglu_micro.json`. Run on the chip from the
root of the checkout: `python3 scripts/held_swiglu_micro.py`; `--tiny` runs
small shapes through the Pallas interpreter (a rehearsal off the chip)."""
import argparse, functools, json, os, sys, time
sys.path.insert(0, os.getcwd())
import jax, jax.numpy as jnp, numpy as np
from stoix_tpu.networks import olmoe
from stoix_tpu.ops import held_swiglu

# name: tokens a decode step, top_k, the router's experts, held, hidden, width, room_sigmas
SHAPES = {
    "mellum2": (16, 8, 64, 8, 2304, 896, 5.0),
    "kanana2 rollout": (128, 6, 128, 16, 2048, 768, 5.0),
    "kanana2 evaluator": (32, 6, 128, 16, 2048, 768, 5.0),
    "lfm2 rollout": (128, 4, 32, 8, 2048, 1792, 5.0),
    "lfm2 evaluator": (32, 4, 32, 8, 2048, 1792, 5.0),
    "ling3": (64, 8, 512, 8, 2560, 768, 5.0),
    "laguna": (32, 8, 256, 8, 2048, 512, 5.0),
    "sdar rollout": (512, 8, 128, 16, 2048, 768, 0.0),
    "sdar evaluator": (128, 8, 128, 16, 2048, 768, 0.0),
    "made up: mellum2's hidden, kanana2's width": (16, 8, 64, 8, 2304, 768, 5.0),
    "made up: kanana2's hidden, mellum2's width": (16, 8, 64, 8, 2048, 896, 5.0),
    "made up: the same at 48 tokens": (48, 8, 64, 8, 2048, 896, 5.0),
}
TINY = {"tiny": (16, 8, 64, 4, 384, 384, 5.0)}
HBM_BYTES_PER_S = 819e9  # a v5e's
# Layers a scan step runs through, each with weights of its own, as a decode
# step does: with one layer's weights XLA rounds them to bfloat16 once, outside
# the scan, and keeps that copy in the v5e's 128 MiB of vector memory, and form
# (b) then reads 0.04 ms a call at [8, 2304, 896], a fifth of the weights' bytes
# at HBM's pace (PERF.md section 6, PR 48, call 1).
LAYERS = 4


def masked_batched(gathered, gate, up, down, sizes):
    """Form (b): every row against every held expert, each row's own kept."""
    ends = jnp.cumsum(sizes)
    row = jnp.arange(gathered.shape[0])
    own = (row[None, :] >= (ends - sizes)[:, None]) & (row[None, :] < ends[:, None])  # [E, rows]
    hidden = jax.nn.silu(jnp.einsum("rd,edf->erf", gathered, gate)) * jnp.einsum(
        "rd,edf->erf", gathered, up
    )
    return jnp.einsum("erf,efd->rd", jnp.where(own[..., None], hidden, 0.0), down)


def draw_sizes(rng, steps, tokens, top_k, num_experts, count, rows):
    landed = rng.integers(0, num_experts, size=(steps, tokens * top_k))
    counts = (landed[..., None] == np.arange(count)).sum(axis=1)  # [steps, held]
    ends = np.minimum(np.cumsum(counts, axis=1), rows)
    return np.diff(ends, axis=1, prepend=0).astype(np.int32)


def time_form(form, args, sizes, repeats=3):
    gathered, layers = args  # a layer: (gate, up, down), arrays of its own (nothing is sliced)

    @jax.jit
    def calls(gathered, layers, sizes):
        def one(x, sizes_t):
            for gate, up, down in layers:
                x = x * 0.5 + form(x, gate, up, down, sizes_t)
            return x, None
        return jax.lax.scan(one, gathered, sizes)[0]

    jax.block_until_ready(calls(gathered, layers, sizes))
    best = float("inf")
    for _ in range(repeats):
        start = time.perf_counter()
        jax.block_until_ready(calls(gathered, layers, sizes))
        best = min(best, time.perf_counter() - start)
    return best / (sizes.shape[0] * len(layers)) * 1e3


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=64)
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("--only", default="")
    flags = parser.parse_args()
    print(jax.devices(), flush=True)
    rng = np.random.default_rng(48)
    results = {}
    for name, (tokens, top_k, num_experts, count, hidden, width, sigmas) in (TINY if flags.tiny else SHAPES).items():
        if flags.only and flags.only not in name:
            continue
        rows = olmoe.held_chunk_rows(tokens, top_k, count, num_experts, sigmas)
        sizes = draw_sizes(rng, flags.steps, tokens, top_k, num_experts, count, rows)
        keys = jax.random.split(jax.random.PRNGKey(48), 1 + 3 * LAYERS)
        weight = lambda key, *shape: jax.random.normal(key, shape) * 0.02
        layers = tuple(
            (weight(g, count, hidden, width), weight(u, count, hidden, width), weight(d, count, width, hidden))
            for g, u, d in zip(keys[1::3], keys[2::3], keys[3::3])
        )
        args = (jax.random.normal(keys[0], (rows, hidden)), layers)
        first = (args[0],) + layers[0]
        reached = float((sizes > 0).sum(axis=1).mean())
        line = {
            "rows": rows, "operands": [count, hidden, width], "experts_reached": round(reached, 3),
            "rows_filled": round(float(sizes.sum(axis=1).mean()), 2),
            "bytes_ms": round(3 * 4 * hidden * width * reached / HBM_BYTES_PER_S * 1e3, 4),
            "form_here": olmoe.held_swiglu_form(rows, hidden, width, count),
        }
        forms = {"a_ragged_dot": olmoe._held_swiglu_ragged, "b_masked_batched": masked_batched}
        if held_swiglu.fits(rows, hidden, width, count):
            forms["c_kernel"] = functools.partial(held_swiglu.held_swiglu_decode, interpret=flags.tiny)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(olmoe._held_swiglu_ragged)(*first, sizes[0])
        valid = int(sizes[0].sum())
        for form_name, form in forms.items():
            try:
                got = jax.jit(form)(*first, sizes[0])
                error = float(jnp.max(jnp.abs(got[:valid] - want[:valid])) / jnp.max(jnp.abs(want[:valid])))
                line[form_name] = {"ms": round(time_form(form, args, jnp.asarray(sizes)), 4), "error": round(error, 6)}
            except Exception as e:  # a form the compiler refuses is a finding, not a failure
                line[form_name] = {"failed": repr(e)[:400]}
        results[name] = line
        print(name, json.dumps(line), flush=True)
        del args, layers, first
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/held_swiglu_micro{'_tiny' if flags.tiny else ''}.json", "w") as f:
        json.dump({"device": str(jax.devices()[0].device_kind), "steps": flags.steps, "layers": LAYERS, "shapes": results}, f, indent=1)


if __name__ == "__main__":
    main()
