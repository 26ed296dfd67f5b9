"""Kernels (ops/sparse_attention.py, scope ``paged_sparse_attention``):
attention over the selected keys, share of its roofline over the traced
window. Needed, for rows past ``topk`` keys: K and V of the selected keys
read once (``topk`` for a single-token row; the row's live keys once for a
chunk row, whose queries select within them) and the score and value products
over ``topk`` keys a query. Moves tpot_p95_ms."""

from benchmark import family_rooflines as rooflines


def read(run):
    return rooflines.scope_share(run, "paged_sparse_attention")
