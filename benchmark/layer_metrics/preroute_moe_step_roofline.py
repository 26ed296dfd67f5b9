"""Kernels / step program, in ``decode_step_roofline``'s place for the
pre-routed MoE family: the least time the chip could take for the steps of
the traced window (the family's ``step_needs``: the larger of FLOPs over peak
and bytes over peak, with every weight outside the experts once, each expert
HIT once, a row's visible keys once a layer, the head if a row samples) over
the device time the step program took for them, per step as
``windowed_moe_step_roofline`` has it. It bounds every later claim in the
cell. Moves tpot_p95_ms."""

from benchmark import family_rooflines as rooflines
from benchmark import reduce


def read(run):
    tr = reduce.traced(run)
    if not tr:
        return None
    least = rooflines.least_seconds(run, "step")
    if not least or not tr["program_runs_ms"]:
        return None
    device_s = sum(tr["program_runs_ms"]) / 1e3 / len(tr["program_runs_ms"])
    return 100.0 * sum(least) / len(least) / device_s
