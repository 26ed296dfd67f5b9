"""Kernels (ops/moe.py, scope ``moe_experts``) where the program holds a
SHARE of its experts: the grouped matmuls over the held experts as a share
of their roofline over the traced window. Needed: each held expert HIT read
once, 2 FLOPs a weight for each (token, expert) pair routed to it, the
pairs' activations in and out (the step's own ``moe_experts_hit`` and
``moe_pairs_held``). ``moe_experts_roofline`` reads the same scope for the
family that holds every expert. Moves tpot_p95_ms."""

from benchmark import family_rooflines as rooflines


def read(run):
    return rooflines.scope_share(run, "moe_experts")
