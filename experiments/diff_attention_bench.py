"""``ops.diff_attention`` alone on the chip at the shapes of the benchmark's
``phi4-mini-flash.reason-longgen`` cell: 32 slots of which 22 are live,
contexts drawn from the cell's mix (a prompt, log-normal around 256, and the
part of a log-normal answer around 768 generated so far), 40 query heads over
20 KV heads of 64, blocks of 16; the shared pool's layers (8192 blocks, a
256-wide table) and the window layers (eight pools of 1120 blocks in one, a
35-wide table, window 512); every live row a token row | one of them a chunk
row of 32. Times one layer's call of each form (the kernel; the ``jax.numpy``
form over a context gathered beforehand, and the gather by itself, which the
first form paid once for eight layers) and prints a JSON line a measurement:
ms a layer, and the live keys' bytes (K and V, the pages a row reads) over it.

    python experiments/diff_attention_bench.py [--keys 512,1024] [--seed 3] [--n 30]
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import diff_attention as da
from ray_tpu.ops.attention import set_default_attention_impl

SLOTS, LIVE, CHUNK, BS = 32, 22, 32, 16
HEADS, KV_HEADS, HD = 40, 20, 64
MAX_LEN, WINDOW = 4096, 512
KVW = KV_HEADS * HD


def contexts(rng):
    """Tokens cached by each slot; 0 marks a slot that holds no request."""
    prompt = np.clip(rng.lognormal(np.log(256), 0.8, SLOTS), 64, 960)
    answer = np.clip(rng.lognormal(np.log(768), 0.7, SLOTS), 256, 3072)
    # a request alive at a random instant is one drawn by its length
    answer = rng.choice(answer, SLOTS, p=answer / answer.sum())
    pos = (prompt + rng.uniform(0, 1, SLOTS) * answer).astype(np.int32)
    pos[rng.permutation(SLOTS)[LIVE:]] = 0
    return np.minimum(pos, MAX_LEN - CHUNK)


def timed(fn, *args, n):
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / n * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--keys", default=str(da.KEYS_PER_STEP))
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--n", type=int, default=30, help="calls a timing")
    args = ap.parse_args()
    rng = np.random.default_rng(args.seed)
    pos = contexts(rng)
    live = pos > 0
    device = jax.devices()[0]
    print(json.dumps({"device": device.device_kind, "live_rows": int(live.sum()),
                      "contexts": sorted(int(p) for p in pos[live])}))
    key = jax.random.PRNGKey(args.seed)
    scalars = (jnp.float32(0.37), jnp.float32(0.55),
               jnp.ones((2 * HD,), jnp.bfloat16))
    q = jax.random.normal(key, (SLOTS, CHUNK, HEADS * HD), jnp.bfloat16)
    layers = {
        # name: (blocks, table width, window)
        "shared_kv": (SLOTS * MAX_LEN // BS, MAX_LEN // BS, 0),
        "window": (8 * SLOTS * 35, 35, WINDOW),
    }
    for layer, (n_blocks, m, window) in layers.items():
        k_pool, v_pool = (jax.random.normal(
            jax.random.fold_in(key, i), (n_blocks, BS, KVW), jnp.bfloat16)
            for i in (1, 2))
        tables = jnp.asarray(
            rng.permutation(SLOTS * m).reshape(SLOTS, m) + 3 * SLOTS * m
            * bool(window), jnp.int32)
        # a window layer's table starts at the block of the window's start
        first = np.maximum(pos - window + 1, 0) // BS * BS if window else 0
        row_pos = jnp.asarray(pos - first, jnp.int32)
        for rows in ("token_rows", "one_chunk_row"):
            nvalid = live.astype(np.int32)
            if rows == "one_chunk_row":
                nvalid[np.flatnonzero(live)[LIVE // 2]] = CHUNK
            n_keys = np.where(nvalid > 0, pos - first + nvalid, 0)
            pages = -(-n_keys // BS)
            live_bytes = int(pages.sum()) * BS * KVW * 2 * 2
            nv = jnp.asarray(nvalid)
            geometry = dict(window=window, heads=HEADS, kv_heads=KV_HEADS,
                            eps=1e-5)
            line = {"layer": layer, "rows": rows,
                    "live_MB": round(live_bytes / 1e6, 1)}
            gather = jax.jit(lambda k, v, t: (da.gather_context(k, t),
                                              da.gather_context(v, t)))
            kctx, vctx = gather(k_pool, v_pool, tables)
            xla = jax.jit(lambda q, kc, vc: da._diff_attention_xla(
                q, kc, vc, row_pos, nv, *scalars, **geometry))
            ms = timed(xla, q, kctx, vctx, n=args.n)
            want = np.asarray(xla(q, kctx, vctx).astype(jnp.float32))
            print(json.dumps({**line, "form": "jax.numpy", "ms": round(ms, 4),
                              "gather_ms": round(timed(
                                  gather, k_pool, v_pool, tables, n=args.n),
                                  4)}))
            del kctx, vctx
            set_default_attention_impl("pallas")
            for keys in (int(k) for k in args.keys.split(",")):
                da.KEYS_PER_STEP = keys
                kernel = jax.jit(lambda q, k, v: da.paged_diff_attention(
                    q, k, v, tables, row_pos, nv, *scalars, window=window,
                    heads=HEADS, kv_heads=KV_HEADS))
                ms = timed(kernel, q, k_pool, v_pool, n=args.n)
                got = np.asarray(kernel(q, k_pool, v_pool).astype(jnp.float32))
                err = max(float(np.abs(got[r, :n] - want[r, :n]).max())
                          for r, n in enumerate(nvalid) if n)
                print(json.dumps({
                    **line, "form": "kernel", "keys_per_step": keys,
                    "ms": round(ms, 4),
                    "live_GB_per_s": round(live_bytes / ms / 1e6, 1),
                    "max_abs_diff_from_jax.numpy": round(err, 4)}))
            set_default_attention_impl(None)


if __name__ == "__main__":
    main()
