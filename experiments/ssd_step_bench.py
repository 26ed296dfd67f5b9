"""``ops.ssm.mamba2_rows``' one-turn update alone on the chip at the shapes of
the benchmark's ``falcon-h1-34b.chat-concurrent`` cell: a state pool of 6
layers x 48 slots of 32 heads x 128 x 256 float32 (1.2 GB), one layer's call
with 26 of its 48 slots live, every live row feeding one position | one of
them a chunk row of 32 beside 25 (the kernel never sees that row; the
``jax.numpy`` form passes over its slot like every other). Times the
``jax.numpy`` form (:func:`ray_tpu.ops.ssm.ssd_step_slots`: one pass over
the layer's slots and the read-out) against the kernel
(:func:`ray_tpu.ops.ssd_step.ssd_step_live`) for a few heads-per-tile sizes,
the pool donated and handed on from call to call as the layers' scan hands it
on, and prints a JSON line a measurement: ms a layer, and the live rows'
state bytes (read once and written once) over it.

    python experiments/ssd_step_bench.py [--tiles 4,8,16] [--seed 3] [--n 30]
"""

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops.ssd_step import ssd_step_live
from ray_tpu.ops.ssm import ssd_step_slots

LAYERS, SLOTS, LIVE, LAYER = 6, 48, 26, 3
H, P, G, N = 32, 128, 2, 256
K = H // G


def timed(fn, pool, *args, n):
    pool, y = fn(pool, *args)
    jax.block_until_ready(y)
    t = time.perf_counter()
    for _ in range(n):
        pool, y = fn(pool, *args)
    jax.block_until_ready((pool, y))
    return pool, (time.perf_counter() - t) / n * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiles", default="4,8,16", help="heads a tile, each")
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--n", type=int, default=30, help="calls a timing")
    args = ap.parse_args()
    rng = np.random.default_rng(args.seed)
    device = jax.devices()[0]
    key = jax.random.split(jax.random.PRNGKey(args.seed), 6)
    draw = lambda i, shape: jax.random.normal(key[i], shape, jnp.float32)
    x, bm, cm = draw(0, (SLOTS, G, K, P)), draw(1, (SLOTS, G, N)), \
        draw(2, (SLOTS, G, N))
    delta = jax.nn.softplus(draw(3, (SLOTS, G, K)))
    a = -jnp.exp(draw(4, (G, K)))
    first = jnp.int32(LAYER * SLOTS)
    fresh = jnp.zeros((SLOTS,), bool)
    alive = np.zeros(SLOTS, bool)
    alive[rng.permutation(SLOTS)[:LIVE]] = True
    print(json.dumps({"device": device.device_kind,
                      "live_slots": np.flatnonzero(alive).tolist()}))
    pool = draw(5, (LAYERS * SLOTS, H, P, N))
    for rows in ("token_rows", "one_chunk_row"):
        nvalid = alive.astype(np.int32)
        if rows == "one_chunk_row":
            nvalid[np.flatnonzero(alive)[LIVE // 2]] = 32
        single = jnp.asarray(nvalid == 1)
        live_bytes = int(single.sum()) * H * P * N * 4 * 2
        line = {"rows": rows, "single_rows": int(single.sum()),
                "live_MB": round(live_bytes / 1e6, 1)}
        turn = (first, single, fresh, x, bm, cm, delta, a)
        xla = jax.jit(ssd_step_slots, donate_argnums=(0,))
        skip = jnp.zeros((G, K), jnp.float32)
        before = np.asarray(pool[int(first):int(first) + SLOTS, 0, :2, :4])
        pool, ms = timed(xla, pool, *turn, skip, n=args.n)
        _, want = xla(jnp.zeros_like(pool), *turn, skip)
        print(json.dumps({**line, "form": "jax.numpy", "ms": round(ms, 4),
                          "live_GB_per_s": round(live_bytes / ms / 1e6, 1)}))
        for hpt in (int(t) for t in args.tiles.split(",")):
            kernel = jax.jit(functools.partial(
                ssd_step_live, heads_per_tile=hpt), donate_argnums=(0,))
            pool, ms = timed(kernel, pool, *turn, n=args.n)
            _, got = kernel(jnp.zeros_like(pool), *turn)
            err = float(jnp.abs(jnp.where(
                single[:, None, None, None], got - want, 0.0)).max())
            print(json.dumps({
                **line, "form": "kernel", "heads_per_tile": hpt,
                "ms": round(ms, 4),
                "live_GB_per_s": round(live_bytes / ms / 1e6, 1),
                "max_abs_diff_from_jax.numpy": round(err, 6)}))
        # slots that fed no single position kept their bytes through it all
        after = np.asarray(pool[int(first):int(first) + SLOTS, 0, :2, :4])
        kept = bool((after[nvalid != 1] == before[nvalid != 1]).all())
        print(json.dumps({**line, "other_slots_untouched": kept}))


if __name__ == "__main__":
    main()
