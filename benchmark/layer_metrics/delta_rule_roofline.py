"""Kernels (ops/delta_rule.py, scope ``delta_rule``): the gated delta rule
of the linear-attention layers in both forms (one turn of the recurrence for
a row that feeds one position, the chunkwise block form for a row that feeds
more) as a share of its roofline over the traced window. Needed
(``families/linear_hybrid_decoder.py::step_needs``): a live row's float32
matrix state read and written ONCE a delta layer, ``q``, ``k``, ``v`` in and
``o`` out a fed position, a turn's 8 dk dv a head or a block's products and
triangular solve; the ``jax.numpy`` turn reads a state twice and writes it
once, a row a turn of a loop, which counts as overhead. Moves tpot_p95_ms."""

from benchmark import family_rooflines as rooflines


def read(run):
    return rooflines.scope_share(run, "delta_rule")
