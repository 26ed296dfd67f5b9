"""Model step: of the (token, expert) pairs the routers chose in the window,
the share whose expert this program holds and computed:
``engine.stats["moe_pairs_held"]`` over ``["moe_pairs_routed"]``, in the
windowed MoE family's cell (32 of 256 experts held: 12.5 % under even
routing; ``held_expert_pairs_pct`` reads the same counters for the latent
family's cell). Higher is more work here for the same routed traffic.
Nothing to read in an engine without the counters. Moves tpot_p95_ms."""

from benchmark import reduce


def read(run):
    end = run.get("marks", {}).get("end", {}).get("stats", {})
    if "moe_pairs_routed" not in end:
        return None
    routed = reduce.window_delta(run, "moe_pairs_routed")
    return 100.0 * reduce.window_delta(run, "moe_pairs_held") / routed \
        if routed else None
