"""``ops.paged_attention``'s kernel alone on the chip at the shapes of the five
cells that run it (``mistral-7b.chat-steady``, ``qwen2-7b.agent-prefix``,
``falcon-h1-34b.chat-concurrent``, ``trinity-large.mixed-queue`` (its full
layer and a sliding one through the window table),
``olmo-hybrid-7b.multiturn-sessions``): slots, chunk, heads, table and pool
as the cell's engine has them, contexts drawn between the cell's shortest and
longest, and three steps a shape by what the rows feed: token rows only,
chunk rows only, the cell's mix (the other slots feed nothing). A JSON line a
measurement: ms a call on the device's clock (``--n`` calls inside one
program), and the live bytes (K and V, the pages a row reads) over it (the
contexts are drawn in the order of ``--shapes``: compare runs of one list); ``--without arithmetic`` times the copies alone, ``--without
copies`` the arithmetic alone (both patch the module here, not the kernel:
their outputs are wrong by design). ``--lower`` times trace + lower of each
shape's call, and its compile, for a DESCRIBED v5e instead (no chip needed;
run it with ``JAX_PLATFORMS=cpu``), ``--keys`` overrides the keys a step.

    PYTHONPATH=. python experiments/paged_attention_bench.py
        [--shapes cell7_full,cell2] [--keys 512,1024]
        [--without arithmetic|copies] [--lower]

It reads the module's public call only, so a parent checkout runs it too
(``PYTHONPATH=<parent> python experiments/paged_attention_bench.py``; there
``--without arithmetic`` has nothing to patch and is refused).
"""

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import paged_attention as pa
from ray_tpu.ops.attention import set_default_attention_impl

HD, BS = 128, 16
#: name: slots, chunk, heads, KV heads (of the pool), table width, blocks,
#: window, (shortest, longest context), token rows and chunk rows of the mix
SHAPES = {
    "cell1": (16, 32, 32, 8, 128, 3072, 1 << 30, (96, 1792), 6, 1),
    "cell2": (16, 32, 28, 4, 256, 6144, 1 << 30, (2048, 2816), 5, 1),
    "cell6": (48, 32, 20, 4, 128, 4096, 1 << 30, (64, 1900), 30, 1),
    "cell7_full": (32, 64, 48, 8, 2048, 20480, 1 << 30, (512, 30720), 7, 3),
    # the window table holds the row's live window only: 4096 + 64 keys
    "cell7_swa": (32, 64, 48, 8, 262, 20480, 4096, (512, 4150), 7, 3),
    "cell8": (32, 64, 32, 32, 160, 5120, 1 << 30, (128, 2400), 20, 2),
}


def set_keys(keys: int, kvh: int):
    """``keys`` a step in whichever constants this checkout's module has."""
    if hasattr(pa, "STEP_BYTES"):
        pa.STEP_BYTES = keys * kvh * HD * 2
    else:
        pa.KEYS_PER_STEP, pa.BYTES_PER_STEP = keys, 1 << 30


def without(what: str):
    if what == "copies":
        from jax.experimental.pallas import tpu as pltpu

        class NoCopy:
            start = wait = lambda self: None
        pltpu.make_async_copy = lambda *a, **k: NoCopy()
    elif what == "arithmetic":
        if not hasattr(pa, "_head_step"):
            raise SystemExit("this checkout's kernel has no _head_step")
        pa._head_step = lambda q, k, v, vis, m, l, acc, **_: (m, l, acc)


@functools.cache
def one_chip():
    """A v5e that is described, not attached: what ``--lower`` compiles
    for."""
    import os
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    return SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])


def timed(call, *step, n):
    """ms a call on the DEVICE's clock: ``n`` calls in one program, each
    made to wait for the one before (its positions plus a zero the compiler
    cannot know), so neither the host's dispatch nor its jitter is in it."""
    @jax.jit
    def many(q, k, v, t, p, nv):
        def body(_, zero):
            o = call(q, k, v, t, p + zero, nv)
            return (o[0, 0, 0, 0] != o[0, 0, 0, 0]).astype(jnp.int32)
        return jax.lax.fori_loop(0, n, body, jnp.int32(0))
    jax.block_until_ready(many(*step))
    t = time.perf_counter()
    jax.block_until_ready(many(*step))
    return (time.perf_counter() - t) / n * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--keys", default="", help="e.g. 512,1024; default: "
                    "the module's own rule")
    ap.add_argument("--without", choices=["arithmetic", "copies"])
    ap.add_argument("--lower", action="store_true")
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--n", type=int, default=50, help="calls a timing")
    args = ap.parse_args()
    if args.without:
        without(args.without)
    set_default_attention_impl("pallas")
    rng = np.random.default_rng(args.seed)
    key = jax.random.PRNGKey(args.seed)
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "without": args.without}))
    for name in args.shapes.split(","):
        slots, chunk, heads, kvh, m, blocks, window, (lo, hi), n_tok, \
            n_chunk = SHAPES[name]
        call = lambda q, k, v, t, p, nv: pa.paged_attention(
            q, k, v, t, p, nv, window=jnp.int32(window), scale=HD ** -0.5)
        shapes = [jax.ShapeDtypeStruct(s, d) for s, d in (
            ((slots, chunk, heads, HD), jnp.bfloat16),
            ((blocks, BS, kvh, HD), jnp.bfloat16),
            ((blocks, BS, kvh, HD), jnp.bfloat16),
            ((slots, m), jnp.int32), ((slots,), jnp.int32),
            ((slots,), jnp.int32))]
        for keys in [int(k) for k in args.keys.split(",") if k] or [None]:
            if keys:
                set_keys(keys, kvh)
            line = {"shape": name, "keys_per_step": keys or "rule"}
            if args.lower:
                described = [jax.ShapeDtypeStruct(
                    s.shape, s.dtype, sharding=one_chip()) for s in shapes]
                t = time.perf_counter()
                lowered = jax.jit(call).trace(*described).lower()
                t_lower = time.perf_counter() - t
                lowered.compile()
                print(json.dumps({
                    **line, "trace_lower_s": round(t_lower, 2),
                    "compile_s": round(time.perf_counter() - t - t_lower, 2)
                }), flush=True)
                continue
            q = jax.random.normal(key, shapes[0].shape, jnp.bfloat16)
            k_pool, v_pool = (jax.random.normal(
                jax.random.fold_in(key, i), shapes[1].shape, jnp.bfloat16)
                for i in (1, 2))
            tables = jnp.asarray(
                rng.permutation(blocks)[:slots * m].reshape(slots, m)
                if blocks >= slots * m else
                rng.integers(0, blocks, (slots, m)), jnp.int32)
            order = rng.permutation(slots)
            ctx = rng.integers(lo, hi, slots)
            kinds = {"token_rows": (n_tok + n_chunk, 0),
                     "chunk_rows": (0, n_tok + n_chunk),
                     "mix": (n_tok, n_chunk)}
            fn = jax.jit(call)
            for rows, (tok, chk) in kinds.items():
                nvalid = np.zeros(slots, np.int32)
                nvalid[order[:tok]] = 1
                nvalid[order[tok:tok + chk]] = chunk
                pos = np.minimum(ctx, m * BS - chunk).astype(np.int32)
                first = np.maximum(pos - window + 1, 0) // BS
                pages = np.where(nvalid > 0,
                                 -(-(pos + nvalid) // BS) - first, 0)
                live = int(pages.sum()) * BS * kvh * HD * 2 * 2
                step = (q, k_pool, v_pool, tables, jnp.asarray(pos),
                        jnp.asarray(nvalid))
                ms = timed(call, *step, n=args.n)
                line = {**line, "rows": rows, "token_rows": tok,
                        "chunk_rows": chk, "live_MB": round(live / 1e6, 1),
                        "ms": round(ms, 4),
                        "live_GB_per_s": round(live / ms / 1e6, 1)}
                if not args.without and m * BS <= 4352:
                    # (the other form gathers every row's whole table)
                    got = np.asarray(fn(*step).astype(jnp.float32))
                    want = np.asarray(jax.jit(
                        lambda *a: pa._paged_attention_xla(
                            *a, window, 0.0, HD ** -0.5))(
                        *step[:5]).astype(jnp.float32))
                    line["max_abs_diff_from_jax.numpy"] = round(max(
                        float(np.abs(got[r, :n] - want[r, :n]).max())
                        for r, n in enumerate(nvalid) if n), 4)
                print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
