"""``ops.sparse_attention``'s one-query scores alone on the chip at the shape
of ``keye-vl2-30b-a3b.longdoc-sessions`` (8 slots, a 2048-wide table over one
layer's 14336 blocks of 16 keys of 64, 16 indexer heads, two keys a lane
row): 2 and 8 of the slots sparse, contexts of 20,480 and 29,184 keys. A
JSON line a measurement: ms a call on the device's clock (``--n`` calls inside
one program) of the Pallas kernel and of the ``jax.numpy`` form on the same
pool, tables and positions, whether both select the same 2048 keys a sparse
row, and the live bytes of ``ki`` (the pages a sparse row reads) over the
kernel's time. ``--without arithmetic`` times the kernel's
copies alone, ``--without copies`` its products alone (both patch the module
here, not the kernel: their outputs are wrong by design). ``--lower`` times
trace + lower and the compile of the kernel's call for a DESCRIBED v5e
instead (no chip needed; run it with ``JAX_PLATFORMS=cpu``).

    PYTHONPATH=. python experiments/indexer_bench.py
        [--without arithmetic|copies] [--lower] [--n 50]
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import sparse_attention as sa
from ray_tpu.ops.attention import set_default_attention_impl

SLOTS, M, BLOCKS, BS, DI, HEADS = 8, 2048, 14336, 16, 64, 16
LIVE_ROWS, CONTEXTS = (2, 8), (20480, 29184)


def without(what: str):
    if what == "copies":
        from jax.experimental.pallas import tpu as pltpu

        class NoCopy:
            start = wait = lambda self: None
        pltpu.make_async_copy = lambda *a, **k: NoCopy()
    elif what == "arithmetic":
        # (the scores' planes are still written: a step's stores stay)
        sa._lane_row_scores = lambda q, keys, w, *, heads, per_row: [
            jnp.zeros((1, keys.shape[0]), jnp.float32)] * per_row


def timed(form: str, *step, n):
    """ms a call on the DEVICE's clock: ``n`` calls in one program, each
    made to wait for the one before (its table and positions plus a zero the
    compiler cannot know), so neither the host's dispatch nor its jitter is
    in it."""
    set_default_attention_impl(form)

    @jax.jit
    def many(qi, w, pool, tables, pos, sparse):
        def body(_, zero):
            # (the table too: a gather that depends on nothing the loop
            # carries is lifted out of the loop, and its time with it)
            s = sa._last_query_scores(qi, w, pool, tables + zero, pos + zero,
                                      sparse, BS)
            # (every score counts: one element alone, and the compiler
            # computes that element alone in the ``jax.numpy`` form)
            return (jnp.sum(jnp.where(s > -jnp.inf, s, 0.0)) == 1.25).astype(
                jnp.int32)
        return jax.lax.fori_loop(0, n, body, jnp.int32(0))
    jax.block_until_ready(many(*step))
    t = time.perf_counter()
    jax.block_until_ready(many(*step))
    return (time.perf_counter() - t) / n * 1e3


def selections_equal(*step) -> bool:
    """Whether both forms select the same 2048 keys in every sparse row."""
    picked = []
    for form in ("xla", "pallas"):
        set_default_attention_impl(form)
        scores = jax.jit(lambda *a: sa._last_query_scores(*a, BS))(*step)
        picked.append(np.sort(np.asarray(jax.lax.top_k(scores, 2048)[1])))
    sparse = np.asarray(step[-1])
    return bool(np.array_equal(picked[0][sparse], picked[1][sparse]))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--without", choices=["arithmetic", "copies"])
    ap.add_argument("--lower", action="store_true")
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--n", type=int, default=50, help="calls a timing")
    args = ap.parse_args()
    rng = np.random.default_rng(args.seed)
    key = jax.random.PRNGKey(args.seed)
    stored = sa.index_pool_shape(BS, DI)
    shapes = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((SLOTS, HEADS, DI), jnp.float32), ((SLOTS, HEADS), jnp.float32),
        ((BLOCKS, *stored), jnp.bfloat16), ((SLOTS, M), jnp.int32),
        ((SLOTS,), jnp.int32), ((SLOTS,), jnp.bool_))]
    if args.lower:
        import os
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        chip = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        set_default_attention_impl("pallas")
        t = time.perf_counter()
        lowered = jax.jit(
            lambda *a: sa._last_query_scores(*a, BS)).trace(*(
                jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip)
                for s in shapes)).lower()
        t_lower = time.perf_counter() - t
        lowered.compile()
        print(json.dumps({
            "trace_lower_s": round(t_lower, 2),
            "compile_s": round(time.perf_counter() - t - t_lower, 2)}))
        return
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "without": args.without, "stored_block": stored}))
    qi = jax.random.normal(key, shapes[0].shape, jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), shapes[1].shape,
                          jnp.float32)
    pool = jax.random.normal(jax.random.fold_in(key, 2), shapes[2].shape,
                             jnp.bfloat16)
    tables = jnp.asarray(rng.integers(0, BLOCKS, (SLOTS, M)), jnp.int32)
    order = rng.permutation(SLOTS)
    for live in LIVE_ROWS:
        for context in CONTEXTS:
            sparse = np.zeros(SLOTS, bool)
            sparse[order[:live]] = True
            pos = np.where(sparse, context - 1, 0).astype(np.int32)
            step = (qi, w, pool, tables, jnp.asarray(pos),
                    jnp.asarray(sparse))
            live_bytes = live * -(-context // BS) * BS * DI * 2
            line = {"live_rows": live, "context": context,
                    "live_MB": round(live_bytes / 1e6, 2)}
            if not args.without:
                line["jax_numpy_ms"] = round(timed("xla", *step, n=args.n), 4)
                line["top_2048_equal"] = selections_equal(*step)
            if args.without:
                without(args.without)
            ms = timed("pallas", *step, n=args.n)
            print(json.dumps({**line, "kernel_ms": round(ms, 4),
                              "kernel_live_GB_per_s":
                                  round(live_bytes / ms / 1e6, 1)}),
                  flush=True)


if __name__ == "__main__":
    main()
