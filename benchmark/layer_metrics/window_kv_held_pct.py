"""Serve engine: blocks the window layers' pool held for the window's rows,
over what a table as wide as each request's whole context holds for the same
rows: ``engine.stats["window_blocks_held"]`` over
``["window_blocks_full_table"]``, counted a row a step in ``_advance_paged``.
A model whose layers all keep every key holds 100 % (and has no such
counter: nothing to read). Lower is better: it is what admission can pack
into a pool of a given size. Moves ttft_p90_ms."""


def read(run):
    start, end = (run["marks"][k]["stats"] for k in ("start", "end"))
    if "window_blocks_full_table" not in end:
        return None
    table = (end["window_blocks_full_table"]
             - start.get("window_blocks_full_table", 0))
    if not table:
        return None
    return 100.0 * (end["window_blocks_held"]
                    - start.get("window_blocks_held", 0)) / table
