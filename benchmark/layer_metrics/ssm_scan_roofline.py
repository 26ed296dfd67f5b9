"""Kernels (ops/ssm.py, scope ``ssm_scan``): the state-space layers' conv,
``W_x``, ``W_dt``, selective scan and state read and write, as a share of
their roofline over the traced window. Needed: their small weights once a
layer, every fed token's conv, projections and scan (7 operations a state
element), and a live row's float32 state read and written once a layer; the
first form runs conv and projections over the whole ``max_slots x
prefill_chunk`` grid and loops the scan over a chunk's positions with every
row in step, which counts as overhead. Moves tpot_p95_ms."""

from benchmark import family_rooflines as rooflines


def read(run):
    return rooflines.scope_share(run, "ssm_scan")
