"""Kernels (ops/diff_attention.py, scope ``shared_kv_attention``): the full
attention layer and the seven cross-attention layers, which all read ONE
pool, as a share of their roofline over the traced window. Needed: a row's
whole context read once a LAYER (eight times a step), the step's tokens
written once, the two softmax maps of every head pair over every causal
pair; the first form gathers every row's table to ``max_len`` once a step
and every layer reads all of it, live or not, which counts as overhead.
Moves tpot_p95_ms."""

from benchmark import family_rooflines as rooflines


def read(run):
    return rooflines.scope_share(run, "shared_kv_attention")
