"""Kernels (ops/expert_mlp.py and ops/moe.py, scopes ``moe_router`` and
``moe_experts`` together, the pre-routed MoE family): an expert layer's
router and the kernel over its 64 experts of ``[2560, 768]``, held WHOLE, as
a share of their roofline over the traced window: the expert kernel's share
at a row width that is whole lanes and not whole ``[8, 128]`` tiles. Needed
(the family's ``whole_experts``): the router once a layer, each expert HIT
once (the step's own ``moe_experts_hit``), 2 FLOPs a weight a fed token
(router) or a routed pair (``moe_pairs_held``), tokens and pairs in and out.
Nothing to read in a family without that need. Moves tpot_p95_ms."""

from benchmark import family_rooflines as rooflines
from benchmark import reduce

SCOPES = ("moe_router", "moe_experts")


def read(run):
    tr = reduce.traced(run)
    least = rooflines.least_seconds(run, "whole_experts")
    took = sum((tr or {}).get("scope_s", {}).get(s, 0.0) for s in SCOPES)
    if not least or not took:
        return None
    return 100.0 * sum(least) / took
