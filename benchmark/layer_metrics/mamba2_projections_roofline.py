"""Kernels (models/parallel_hybrid.py, scopes ``mamba2_in_proj`` and
``mamba2_out_proj`` together): the Mamba-2 mixer's in-projection (``z | x B
C | dt`` from the layer's one normed input) and out-projection as a share of
their roofline over the traced window. Needed: their weights once a layer, 2
FLOPs a weight a fed position, the positions' activations in and out. Moves
tpot_p95_ms."""

from benchmark import family_rooflines as rooflines
from benchmark import reduce

SCOPES = ("mamba2_in_proj", "mamba2_out_proj")


def read(run):
    tr = reduce.traced(run)
    took = sum((tr or {}).get("scope_s", {}).get(s, 0.0) for s in SCOPES)
    if not took:
        return None
    least = rooflines.least_seconds(run, "mamba2_projections")
    if not least:
        return None
    return 100.0 * sum(least) / took
