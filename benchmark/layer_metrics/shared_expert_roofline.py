"""Kernels (models/transformer.py, scope ``shared_expert``): the expert every
token takes, beside the routed sum, as a share of its roofline over the
traced window. Needed: its weights once an expert layer, 2 FLOPs a weight a
fed token, the tokens in and out. Moves tpot_p95_ms."""

from benchmark import family_rooflines as rooflines


def read(run):
    return rooflines.scope_share(run, "shared_expert")
