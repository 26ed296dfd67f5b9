"""What the family roofline readers share: over the steps of a traced
window, the least time the chip could take for one scope of the step program
(the family's ``step_needs``: needed FLOPs over peak FLOP/s or needed bytes
over peak bytes/s, whichever is larger, a step at a time) over the device
time the trace gives that scope (``kinds/serve_family_replica.py``). Not a family file: the readers in ``layer_metrics/`` import it."""

from __future__ import annotations

from benchmark import opcount, reduce
from benchmark.kinds.serve_family_replica import load_family


def traced_steps(run):
    """(rows, counters) of every step that lies inside the traced window,
    or None where the run kept no per-step counters."""
    tr = reduce.traced(run)
    counters = run["replica"].get("step_counters")
    if not tr or counters is None:
        return None
    lo, hi = tr["window_monotonic"]
    return [(s[2], c) for s, c in zip(run["replica"]["steps"], counters)
            if s[0] >= lo and s[1] <= hi] or None


def least_seconds(run, scope: str):
    """Per traced step, the roofline's seconds for ``scope``."""
    steps = traced_steps(run)
    if not steps:
        return None
    family = load_family(run["config_file"])
    peaks = opcount.peaks_for(run["facts"]["kind"])
    return [opcount.least_seconds(
        family.step_needs(run["config_file"], rows, counters)[scope],
        peaks)["seconds"] for rows, counters in steps]


def scope_share(run, scope: str):
    """Roofline share of one named scope, in per cent. The scope's device
    time is the whole traced window's, the needs are the whole steps'
    inside it: the share reads a little low by the steps the window's edges
    cut (two of a hundred)."""
    tr = reduce.traced(run)
    least = least_seconds(run, scope)
    if not least or not tr.get("scope_s", {}).get(scope):
        return None
    return 100.0 * sum(least) / tr["scope_s"][scope]
