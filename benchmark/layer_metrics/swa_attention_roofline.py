"""Kernels (ops/paged_attention.py under the scope ``swa_attention`` of
models/transformer.py, the windowed MoE family): the sliding layers'
attention, reading the WINDOW pool through a table that holds a row's live
window only, as a share of its roofline over the traced window. Needed: the
keys a row's queries can see once a layer (at most window + chunk - 1), the
queries in and the output out, 4 hd a query head a visible (query, key)
pair. Moves tpot_p95_ms."""

from benchmark import family_rooflines as rooflines


def read(run):
    return rooflines.scope_share(run, "swa_attention")
