"""Serve engine: share of the prompt tokens admitted in the window that a
prefix hit THROUGH A STATE SNAPSHOT served, from
``engine.stats["prefix_hit_tokens"]`` over the prompt tokens of the requests
the clients sent: an exact count. In a layout with recurrent state a hit
lands only where the trie keeps a snapshot, so this is what the snapshots
bought; ``prefix_hit_token_pct`` reads the same counter in the mixes with a
shared system prompt. Nothing to read in an engine that restores no
snapshots (no ``state_snapshots_restored`` counter). Moves ttft_p90_ms."""

from benchmark import reduce


def read(run):
    end = run.get("marks", {}).get("end", {}).get("stats", {})
    if "state_snapshots_restored" not in end:
        return None
    sent = sum(len(c.req.prompt) for c in run["clients"] if c.sent is not None)
    return 100.0 * reduce.window_delta(run, "prefix_hit_tokens") / sent \
        if sent else None
