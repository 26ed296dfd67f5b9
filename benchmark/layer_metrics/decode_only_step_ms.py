"""Model step: the mean time of an engine step in which no row was fed prompt
tokens, as the engine paces it (one read of a step's ids to the next):
``engine.stats["step_s_decode_only"]`` over ``["steps_decode_only"]``
(``rtpu_serve_step_s_decode_only_total`` over
``rtpu_serve_steps_decode_only_total``), bumped in one update in ``_read`` from
the time and the rows of THE SAME step. With the lookahead this is the device's
step wherever the host's work is the shorter. Nothing to read in an engine
without the counters. Moves tpot_p95_ms."""

from benchmark import reduce


def read(run):
    end = run.get("marks", {}).get("end", {}).get("stats", {})
    if "step_s_decode_only" not in end:
        return None
    n = reduce.window_delta(run, "steps_decode_only")
    return (1e3 * reduce.window_delta(run, "step_s_decode_only") / n
            if n else None)
