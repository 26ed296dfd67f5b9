"""``ops.moe.moe_layer_dropless``'s routed experts alone on the chip at the
shapes of the benchmark's three expert cells: one layer's call on ``[2, E, D,
F]`` stacks (the second layer's groups), at the step's second width (512
positions, a chunk row and its neighbours real) and at the budget (256, a
handful of decoding rows real), the router random, so a held expert is hit
about as the cell's mix hits it.

    trinity  D x F = 3072 x 3072, 32 held of 256, k 4   (trinity-large.mixed-queue)
    kimi     7168 x 2048, 12 held of 384, k 8           (kimi-k2.5.longdoc-reask)
    keye     2048 x 768, all 128 held, k 8              (keye-vl2-30b-a3b.longdoc-sessions)

Times the whole layer call in its ``jax.numpy`` form (three ``lax.ragged_dot``
calls, sort, gathers) and with the kernel (:mod:`ray_tpu.ops.expert_mlp`),
then the kernel's call alone (order and counts given) with its arithmetic in
and taken out (every copy still made: what bounds it), and prints a JSON line
a measurement: ms a layer and the HIT experts' bytes (3 x D x F x 2 each,
once) over it.

    python experiments/expert_mlp_bench.py [--cells trinity,kimi,keye] [--seed 3] [--n 30]
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops.attention import set_default_attention_impl
from ray_tpu.ops.expert_mlp import expert_mlp_pairs
from ray_tpu.ops.moe import moe_layer_dropless

#: D, F, experts held, experts routed over, k, the first held expert
CELLS = {"trinity": (3072, 3072, 32, 256, 4, 64),
         "kimi": (7168, 2048, 12, 384, 8, 24),
         "keye": (2048, 768, 128, 128, 8, None)}
#: (positions run, positions real)
STEPS = {"second_width": (512, 480), "budget": (256, 24)}
LAYERS, LAYER = 2, 1


def timed(fn, *args, n):
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return out, (time.perf_counter() - t) / n * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--n", type=int, default=30, help="calls a timing")
    args = ap.parse_args()
    print(json.dumps({"device": jax.devices()[0].device_kind}))
    for cell in args.cells.split(","):
        d, f, e, e_all, k, first = CELLS[cell]
        key = jax.random.split(jax.random.PRNGKey(args.seed), 5)
        bf = lambda i, shape, s: (jax.random.normal(key[i], shape, jnp.bfloat16)
                                  * jnp.bfloat16(s))
        w_gate, w_up = (bf(i, (LAYERS, e, d, f), d ** -0.5) for i in (0, 1))
        w_down = bf(2, (LAYERS, e, f, d), f ** -0.5)
        # logits of unit scale: a sigmoid router that saturates has ties,
        # and ties all go to the first experts
        router = jax.random.normal(key[3], (d, e_all), jnp.float32) * d ** -0.5
        share = {} if first is None else dict(first=first, scoring="sigmoid")
        for step, (t, real) in STEPS.items():
            x = bf(4, (t, d), 1.0)
            valid = jnp.arange(t) < real
            outs = {}
            for form in ("xla", "pallas"):
                # the form is chosen where the layer is traced: a function
                # (and a trace) a form
                set_default_attention_impl(form)
                layer = lambda x, *ws: moe_layer_dropless(
                    x, router, *ws, k=k, norm_topk=True, valid=valid,
                    layer=jnp.int32(LAYER), **share)
                (outs[form], counts), ms = timed(
                    jax.jit(layer), x, w_gate, w_up, w_down, n=args.n)
                hit = int((counts > 0).sum())
                hit_bytes = hit * 3 * d * f * 2
                line = {"cell": cell, "step": step, "positions": t,
                        "pairs": t * k, "pairs_held": int(counts.sum()),
                        "experts_hit": hit, "hit_MB": round(hit_bytes / 1e6, 1)}
                print(json.dumps({
                    **line, "what": "layer", "form":
                    "kernel" if form == "pallas" else "jax.numpy",
                    "ms": round(ms, 4),
                    "hit_GB_per_s": round(hit_bytes / ms / 1e6, 1)}))
            set_default_attention_impl(None)
            got, want = (np.asarray(outs[f], np.float32)
                         for f in ("pallas", "xla"))
            print(json.dumps({**line, "max_abs_diff_from_jax.numpy":
                              float(np.abs(got - want).max()),
                              "max_abs": float(np.abs(want).max())}))
            # the kernel's call alone: the order the layer would hand it
            top = jax.random.randint(key[3], (t * k,), 0, e_all)
            top = top - (first or 0)
            flat_e = jnp.where((top >= 0) & (top < e)
                               & jnp.repeat(valid, k), top, e)
            order = jnp.argsort(flat_e, stable=True)
            counts = jnp.zeros((e + 1,), jnp.int32).at[flat_e].add(1)[:e]
            hit = int((counts > 0).sum())
            hit_bytes = hit * 3 * d * f * 2
            for multiply in (True, False):
                call = lambda x, *ws: expert_mlp_pairs(
                    x, order, counts, LAYER * e,
                    *(w.reshape(-1, *w.shape[2:]) for w in ws),
                    k=k, multiply=multiply)
                _, ms = timed(jax.jit(call), x, w_gate, w_up, w_down,
                              n=args.n)
                print(json.dumps({
                    "cell": cell, "step": step, "positions": t,
                    "pairs_held": int(counts.sum()), "experts_hit": hit,
                    "hit_MB": round(hit_bytes / 1e6, 1),
                    "what": "kernel_call" if multiply else "copies_only",
                    "ms": round(ms, 4),
                    "hit_GB_per_s": round(hit_bytes / ms / 1e6, 1)}))
        del w_gate, w_up, w_down


if __name__ == "__main__":
    main()
