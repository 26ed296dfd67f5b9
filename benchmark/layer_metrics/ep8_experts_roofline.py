"""Kernels (ops/moe.py and models/transformer.py, scopes ``moe_router``,
``moe_experts`` and ``shared_expert`` together, the windowed MoE family):
an expert layer's router, the grouped matmuls over the experts HELD here
(one chip's 32 of 256) and the shared expert, as a share of their roofline
over the traced window. Needed: the router and the shared expert once a
layer, each held expert HIT once (the step's own ``moe_experts_hit``), 2
FLOPs a weight a fed token or a pair routed to a held expert
(``moe_pairs_held``), tokens and pairs in and out. Moves tpot_p95_ms."""

from benchmark import family_rooflines as rooflines
from benchmark import reduce

SCOPES = ("moe_router", "moe_experts", "shared_expert")


def read(run):
    tr = reduce.traced(run)
    least = rooflines.least_seconds(run, "ep8_experts")
    took = sum((tr or {}).get("scope_s", {}).get(s, 0.0) for s in SCOPES)
    if not least or not took:
        return None
    return 100.0 * sum(least) / took
