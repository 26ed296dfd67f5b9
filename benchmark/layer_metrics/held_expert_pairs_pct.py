"""Model step: of the (token, expert) pairs the routers chose in the window,
the share whose expert this program holds and computed:
``engine.stats["moe_pairs_held"]`` over ``["moe_pairs_routed"]``. A program
that holds 12 of 384 experts expects 3.1 % under even routing; one that
holds every expert reads 100 % (and a program without the counters reads
nothing). Higher is more work here for the same routed traffic.
Moves tpot_p95_ms."""


def read(run):
    start, end = (run["marks"][k]["stats"] for k in ("start", "end"))
    routed = end.get("moe_pairs_routed", 0) - start.get("moe_pairs_routed", 0)
    if not routed:
        return None
    return 100.0 * (end["moe_pairs_held"]
                    - start.get("moe_pairs_held", 0)) / routed
