"""Serve engine: share of the prompt tokens admitted in the window that the
prefix cache served, from ``engine.stats["prefix_hit_tokens"]`` over the
prompt tokens of the requests the clients sent: an exact count. Nothing to
read in a mix without shared prefixes. Moves ttft_p90_ms."""

from benchmark import reduce


def read(run):
    if not run["cell"]["traffic_file"].get("shared_prefix_tokens"):
        return None
    sent = sum(len(c.req.prompt) for c in run["clients"] if c.sent is not None)
    return 100.0 * reduce.window_delta(run, "prefix_hit_tokens") / sent
