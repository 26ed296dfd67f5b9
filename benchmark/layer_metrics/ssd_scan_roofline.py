"""Kernels (ops/ssm.py, scopes ``ssd_conv``, ``ssd_scan`` and
``ssd_gated_norm`` together): Mamba-2's conv over ``x | B | C``, its scan
(one turn of the recurrence for a row that feeds one position, the block
form for a row that feeds more, the state read and written, the ``D`` skip)
and the gated norm after it, as a share of their roofline over the traced
window. Needed (``families/parallel_hybrid_decoder.py::step_needs``): the
small weights once a layer, a live row's float32 state read and written ONCE
a layer, every fed position's conv, step, skip and norm, a turn's 4 P N a
head or a block's products; the ``jax.numpy`` form passes over every SLOT's
state and runs the conv over the whole ``max_slots x prefill_chunk`` grid,
which counts as overhead. Moves tpot_p95_ms."""

from benchmark import family_rooflines as rooflines
from benchmark import reduce

SCOPES = ("ssd_conv", "ssd_scan", "ssd_gated_norm")


def read(run):
    tr = reduce.traced(run)
    took = sum((tr or {}).get("scope_s", {}).get(s, 0.0) for s in SCOPES)
    if not took:
        return None
    least = rooflines.least_seconds(run, "ssd_scan")
    if not least:
        return None
    return 100.0 * sum(least) / took
