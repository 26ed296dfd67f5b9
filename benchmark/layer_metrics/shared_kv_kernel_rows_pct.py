"""Kernels (ops/diff_attention.py): of the rows whose attention read the
pool that eight layers share in the window, the share attended by the Pallas
kernel that reads the K and V pools through the block table, live blocks
only: ``engine.stats["shared_kv_kernel_rows"]`` over
``["shared_kv_rows_attended"]`` (``rtpu_serve_shared_kv_kernel_rows_total``
over ``rtpu_serve_shared_kv_rows_attended_total``), counted a row a step
where ``shared_kv_keys_read`` is. The program chooses the form from what it
can observe (backend, pool dtype, lane width of a KV pair, block size): 100 %
on a TPU over bf16 pools whose KV pair is whole lanes, 0 % where the
``jax.numpy`` form runs. Nothing to read in a program without the counters.
Moves tpot_p95_ms."""


def read(run):
    marks = run.get("marks", {})
    start, end = (marks.get(k, {}).get("stats", {}) for k in ("start", "end"))
    if "shared_kv_kernel_rows" not in end:
        return None
    rows = end.get("shared_kv_rows_attended", 0) \
        - start.get("shared_kv_rows_attended", 0)
    if not rows:
        return None
    return 100.0 * (end["shared_kv_kernel_rows"]
                    - start.get("shared_kv_kernel_rows", 0)) / rows
