"""Kernels (ops/moe.py, scope ``moe_experts``): the expert layers' share of
their roofline over the traced window. Needed: each expert HIT in a step read
once, six FLOPs a weight for every (token, expert) pair, the pairs'
activations (``families/<family>.py::step_needs``, from the engine's own
per-step counters), over the scope's device time. Memory binds with eight
single-token rows (about 50 of 128 experts hit). Moves tpot_p95_ms."""

from benchmark import family_rooflines as rooflines


def read(run):
    return rooflines.scope_share(run, "moe_experts")
