"""Kernels (ops/sparse_attention.py): of the rows whose last query the
sparse-attention indexer scored in the window (rows that fed a query past
``index_topk`` keys, a row a step), the share whose scores ran in the Pallas
kernel that walks the row's block table as far as its position and reads the
indexer's keys from the pool in place (``indexer_scores_fwd``: no gather of
every row's whole table, no float32 products in HBM):
``engine.stats["indexer_kernel_rows"]`` over ``["indexer_rows_scored"]``
(``rtpu_serve_indexer_kernel_rows_total`` over
``rtpu_serve_indexer_rows_scored_total``), grown together on the host from
the step's own rows. The program chooses the form from what it can observe
(backend, the pool's dtype and stored shape): 100 % on a TPU over a bfloat16
pool stored in whole lane rows, 0 % where the ``jax.numpy`` form runs.
Nothing to read in a program without the counter, or in a window in which no
row was sparse. Moves tpot_p95_ms."""


def read(run):
    marks = run.get("marks", {})
    start, end = (marks.get(k, {}).get("stats", {}) for k in ("start", "end"))
    if "indexer_kernel_rows" not in end:
        return None
    rows = end.get("indexer_rows_scored", 0) \
        - start.get("indexer_rows_scored", 0)
    if not rows:
        return None
    return 100.0 * (end["indexer_kernel_rows"]
                    - start.get("indexer_kernel_rows", 0)) / rows
