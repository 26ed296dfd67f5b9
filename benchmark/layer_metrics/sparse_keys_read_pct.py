"""Serve engine: keys whose K and V the window's single-token (decode) rows
read, over those rows' live keys: ``engine.stats["attn_keys_selected"]`` over
``["attn_keys_live"]``, exact counts made where ``attn_blocks_live`` is. A
model with learned sparse attention reads its indexer's top-k (2048 of 20-29
thousand keys in ``longdoc-sessions``); a dense model reads 100 %. Nothing to
read in a program without the counters. Moves tpot_p95_ms."""


def read(run):
    start, end = (run["marks"][k]["stats"] for k in ("start", "end"))
    if "attn_keys_live" not in end:
        return None
    live = end["attn_keys_live"] - start["attn_keys_live"]
    if not live:
        return None
    return 100.0 * (end["attn_keys_selected"]
                    - start["attn_keys_selected"]) / live
