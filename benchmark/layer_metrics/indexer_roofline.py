"""Kernels (ops/sparse_attention.py and the indexer's projections, scope
``dsa_indexer``): the sparse-attention indexer's share of its roofline over
the traced window. Needed: its projections for every fed token and, for rows
past ``topk`` keys, the indexer keys of the row's live context read ONCE and
one product per (query, head, causal key); the selection itself (top-k)
needs nothing more and counts as overhead. Moves ttft_p90_ms: chunk rows
score 128 queries against 20-29 thousand keys."""

from benchmark import family_rooflines as rooflines


def read(run):
    return rooflines.scope_share(run, "dsa_indexer")
