"""Device: share of the traced window in which no operation ran on the chip,
1 - union of device-operation intervals over the window: the host's work
between two steps, which every token gap contains. Moves tpot_p95_ms."""

from benchmark import reduce


def read(run):
    tr = reduce.traced(run)
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
