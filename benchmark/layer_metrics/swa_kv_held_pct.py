"""Serve engine: blocks the sliding layers' pool held for the window's rows,
over what tables as wide as each request's whole context would hold for the
same rows: ``engine.stats["window_blocks_held"]`` over
``["window_blocks_full_table"]``, counted a row a step in ``_plan``, in a
uniform decoder whose window layers release their blocks
(``TransformerConfig.window_pool``; ``window_kv_held_pct`` reads the same
counters for the SambaY layout's cell). Lower is better: it is what
admission can pack into a window pool of a given size. Nothing to read in an
engine without the counters. Moves ttft_p90_ms."""

from benchmark import reduce


def read(run):
    end = run.get("marks", {}).get("end", {}).get("stats", {})
    if "window_blocks_full_table" not in end:
        return None
    table = reduce.window_delta(run, "window_blocks_full_table")
    return 100.0 * reduce.window_delta(run, "window_blocks_held") / table \
        if table else None
