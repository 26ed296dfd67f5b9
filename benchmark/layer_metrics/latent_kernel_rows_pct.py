"""Kernels (ops/latent_attention.py): of the rows whose attention read the
latent pool in the window, the share attended by the Pallas kernel that walks
the pool's live blocks: ``engine.stats["latent_kernel_rows"]`` over
``["latent_rows_attended"]`` (``rtpu_serve_latent_kernel_rows_total`` over
``rtpu_serve_latent_rows_attended_total``), counted a row a step where
``latent_tokens_read`` is. The program chooses the form from what it can
observe (backend, pool dtype, lane width, block size): 100 % on a TPU over a
bf16 pool of whole lanes, 0 % where the ``jax.numpy`` form runs. Nothing to
read in a program without the counters. Moves tpot_p95_ms."""


def read(run):
    marks = run.get("marks", {})
    start, end = (marks.get(k, {}).get("stats", {}) for k in ("start", "end"))
    if "latent_kernel_rows" not in end:
        return None
    rows = end.get("latent_rows_attended", 0) \
        - start.get("latent_rows_attended", 0)
    if not rows:
        return None
    return 100.0 * (end["latent_kernel_rows"]
                    - start.get("latent_kernel_rows", 0)) / rows
