"""Kernels (ops/paged_attention.py under the scope ``global_attention`` of
models/transformer.py, the windowed MoE family): the full layers' attention,
reading every earlier key of a row through a table as wide as its context,
as a share of its roofline over the traced window. Needed: a row's live K
and V once a layer, the queries in and the output out, 4 hd a query head a
causal pair. Moves tpot_p95_ms."""

from benchmark import family_rooflines as rooflines


def read(run):
    return rooflines.scope_share(run, "global_attention")
