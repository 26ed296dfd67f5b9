"""Kernels (ops/ssd_step.py): of the rows that fed the Mamba-2 mixers ONE
position in the window (decoding rows and one-token prompts: a turn of the
recurrence each), the share whose turn was taken by the Pallas kernel that
walks the LIVE rows' states in the pool, each read and written once:
``engine.stats["ssd_kernel_rows"]`` over ``["ssd_rows_stepped"]``
(``rtpu_serve_ssd_kernel_rows_total`` over
``rtpu_serve_ssd_rows_stepped_total``), counted a row a step where
``ssd_positions_real`` is. The program chooses the form from what it can
observe (backend, the pool's dtype, whether a head's states are whole lanes
and its channels whole sublanes): 100 % on a TPU over a float32 pool of 128 x
256 heads, 0 % where the ``jax.numpy`` pass over every slot runs. Nothing to
read in a program without the counters. Moves tpot_p95_ms."""


def read(run):
    marks = run.get("marks", {})
    start, end = (marks.get(k, {}).get("stats", {}) for k in ("start", "end"))
    if "ssd_kernel_rows" not in end:
        return None
    rows = end.get("ssd_rows_stepped", 0) - start.get("ssd_rows_stepped", 0)
    if not rows:
        return None
    return 100.0 * (end["ssd_kernel_rows"]
                    - start.get("ssd_kernel_rows", 0)) / rows
