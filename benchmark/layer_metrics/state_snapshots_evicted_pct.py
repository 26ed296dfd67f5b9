"""Serve engine: snapshots the trie gave up in the window WITH NOTHING TO STAND
IN FOR THEM (``engine.stats["state_snapshots_evicted"]``: the least recently
used when the snapshot pool was full and none lay between two others of its
path, or one gone with its node's block) over the snapshots taken in it
(``["state_snapshots_taken"]``), in per cent. A conversation's older
snapshots are superseded by its newest and are not counted; what is counted
is a path's DEEPEST or ONLY snapshot, after which a prompt on that path lands
shallower or nowhere: a chain its conversation has left (one a restart: the
floor of this reading), or, where the pool is too small, a live one. Nothing
to read in an engine without the counters, or in a window that took none.
Moves ttft_p90_ms."""

from benchmark import reduce


def read(run):
    end = run.get("marks", {}).get("end", {}).get("stats", {})
    if "state_snapshots_evicted" not in end:
        return None
    taken = reduce.window_delta(run, "state_snapshots_taken")
    return 100.0 * reduce.window_delta(run, "state_snapshots_evicted") \
        / taken if taken else None
