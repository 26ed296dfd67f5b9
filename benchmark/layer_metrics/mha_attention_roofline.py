"""Kernels (ops/paged_attention.py, scope ``paged_attention`` in the linear
hybrid family): the full-attention layers' 30 MHA heads reading K and V
through the block table (the kernel ``paged_attention_fwd`` on a TPU, over a
pool whose head axis is padded to 32), as a share of their roofline over the
traced window. Needed: a row's live K and V of the 30 heads once a full
layer, the queries in and the output out, 4 hd a query head a causal pair;
the two padded heads' bytes count as overhead. Moves tpot_p95_ms."""

from benchmark import family_rooflines as rooflines


def read(run):
    return rooflines.scope_share(run, "paged_attention")
