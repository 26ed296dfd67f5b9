"""Kernels (models/transformer.py, scope ``gated_attn_proj`` in both
position-wise stages of a layer of the windowed MoE family): the five
projections (q, k, v, the output gate, ``Wo``) with the head norms, the gate
and the branch's two norms, as a share of their roofline over the traced
window. Needed: their weights once a layer, 2 FLOPs a weight a fed token,
the tokens' activations in and out. Moves tpot_p95_ms."""

from benchmark import family_rooflines as rooflines


def read(run):
    return rooflines.scope_share(run, "gated_attn_proj")
