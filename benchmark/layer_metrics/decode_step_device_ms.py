"""Model step (models/transformer.py, program serve::decode_step_paged):
median device time of one execution of the step program, from the profiler
trace's module events. Moves tpot_p95_ms."""

from benchmark import reduce, stats


def read(run):
    tr = reduce.traced(run)
    if not tr or not tr["program_runs_ms"]:
        return None
    return stats.median(tr["program_runs_ms"])
