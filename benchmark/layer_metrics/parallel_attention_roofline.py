"""Kernels (ops/paged_attention.py, scope ``paged_attention`` in the parallel
attention / Mamba-2 family): the attention heads of a layer that also runs
Mamba-2 heads, reading K and V through the block table (the kernel
``paged_attention_fwd`` on a TPU), as a share of their roofline over the
traced window. Needed: a row's live K and V once a layer, the queries in and
the output out, 4 hd a query head a causal pair (the dense and sparse
families time the same scope under names of their own). Moves
tpot_p95_ms."""

from benchmark import family_rooflines as rooflines


def read(run):
    return rooflines.scope_share(run, "paged_attention")
