"""Model step (ops/moe.py under the scope ``moe_router``): the device time a
step program spends making its layers' routes (the router's matmul in
float32, softmax, top-k, the sort of the pairs by expert and their counts),
summed over the layers, in ms a program run of the traced window. In the
pre-routed MoE family the route is made in the layer's FIRST half, beside the
q, k and v projections and ahead of the attention; on one core it costs what
it would cost after it, and this number is what a later change that hides it
under the attention has to take away. Nothing to read in an untraced run or
in a family whose kind does not time ``moe_router``. Moves tpot_p95_ms."""

from benchmark import reduce


def read(run):
    tr = reduce.traced(run)
    if not tr or not tr.get("program_runs_ms") \
            or run["config_file"].get("reference") != "preroute_moe_decoder":
        return None
    took = tr.get("scope_s", {}).get("moe_router")
    if not took:
        return None
    return 1e3 * took / len(tr["program_runs_ms"])
