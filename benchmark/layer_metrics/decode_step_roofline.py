"""Kernels / step program: the least time the chip could take for the steps
of the traced window (per step the larger of needed FLOPs over peak FLOP/s
and needed bytes over peak bytes/s: weights once, valid keys and values
once; opcount.decode_step_needs from each step's real rows) over the device
time the step program took for them. The serve step holds no Pallas kernel
today, so this is the whole program's share. Moves tpot_p95_ms."""

from benchmark import opcount, reduce


def read(run):
    tr = reduce.traced(run)
    if not tr or not tr["program_runs_ms"]:
        return None
    peaks = opcount.peaks_for(run["facts"]["kind"])
    lo, hi = tr["window_monotonic"]
    steps = [s for s in run["replica"]["steps"] if s[0] >= lo and s[1] <= hi]
    if not steps:
        return None
    least = [opcount.least_seconds(
        opcount.decode_step_needs(run["config_file"], s[2]), peaks)
        for s in steps]
    # steps and program executions of the same window: per step, so that a
    # step cut by the window's edge on one side only does not tilt the share
    device_s = sum(tr["program_runs_ms"]) / 1e3 / len(tr["program_runs_ms"])
    return 100.0 * sum(x["seconds"] for x in least) / len(least) / device_s
