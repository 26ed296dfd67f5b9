"""Kernels (models/linear_hybrid.py and ops/delta_rule.py, scopes
``delta_proj`` and ``delta_out`` together): a linear-attention layer's six
projections (q | k | v, the output gate, the two head-wide ones behind alpha
and beta), its depthwise conv, the head norm with its gate and the
out-projection, as a share of their roofline over the traced window. Needed:
their weights once a delta layer, 2 FLOPs a projection weight a fed position,
the conv and the norm a channel, a live row's conv inputs read and written
once, the positions' activations in and out. Moves tpot_p95_ms."""

from benchmark import family_rooflines as rooflines
from benchmark import reduce

SCOPES = ("delta_proj", "delta_out")


def read(run):
    tr = reduce.traced(run)
    took = sum((tr or {}).get("scope_s", {}).get(s, 0.0) for s in SCOPES)
    if not took:
        return None
    least = rooflines.least_seconds(run, "delta_projections")
    if not least:
        return None
    return 100.0 * sum(least) / took
