"""Kernels (ops/latent_attention.py, scope ``mla_attention``): the absorbed
latent attention over the paged latent pool, as a share of its roofline over
the traced window. Needed: a row's live tokens' 576-value vectors read ONCE a
layer for all 64 heads and the step's new ones written; per causal (query,
key) pair and head a score over the vector and a sum over its latent part.
The first form gathers each row's table at its full width and scores chunk
rows in float32, which counts as overhead. Moves tpot_p95_ms."""

from benchmark import family_rooflines as rooflines


def read(run):
    return rooflines.scope_share(run, "mla_attention")
