"""Kernels (ops/paged_attention.py): of the rows a step's attention attended
(a row a step, layers not counted), the share that fed ONE token to the
Pallas kernel that walks the pool through the block table, which gives such
a row a tile of its own (its ``rep`` query heads a KV head in one sublane
tile, not the ``prefill_chunk x rep`` rows a chunk row multiplies):
``engine.stats["attn_token_tile_rows"]`` over ``["attn_rows_attended"]``
(``rtpu_serve_attn_token_tile_rows_total`` over
``rtpu_serve_attn_rows_attended_total``), counted a row a step where
``attn_blocks_live`` is. The rest fed a chunk, or ran the ``jax.numpy`` form
(0 % there). Nothing to read in a program without the counters. Moves
tpot_p95_ms."""


def read(run):
    marks = run.get("marks", {})
    start, end = (marks.get(k, {}).get("stats", {}) for k in ("start", "end"))
    if "attn_token_tile_rows" not in end:
        return None
    rows = end.get("attn_rows_attended", 0) \
        - start.get("attn_rows_attended", 0)
    if not rows:
        return None
    return 100.0 * (end["attn_token_tile_rows"]
                    - start.get("attn_token_tile_rows", 0)) / rows
