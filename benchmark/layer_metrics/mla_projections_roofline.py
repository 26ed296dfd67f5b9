"""Kernels (models/latent.py, scopes ``mla_q_proj``, ``mla_kv_proj``,
``mla_out_proj`` together): the latent attention's projections (``W_qa``,
``W_qb`` and ``W_uk`` folded into the query; ``W_kva``; ``W_uv`` folded into
the output and ``W_o``) as a share of their roofline over the traced window.
Needed: their weights once a layer, 2 FLOPs a weight a fed token, the
tokens' activations in and out. Moves tpot_p95_ms."""

from benchmark import family_rooflines as rooflines
from benchmark import reduce

SCOPES = ("mla_q_proj", "mla_kv_proj", "mla_out_proj")


def read(run):
    tr = reduce.traced(run)
    least = rooflines.least_seconds(run, "mla_projections")
    took = sum((tr or {}).get("scope_s", {}).get(s, 0.0) for s in SCOPES)
    if not least or not took:
        return None
    return 100.0 * sum(least) / took
