"""Model step: the mean time of an engine step in which a row was fed prompt
tokens, whether its real positions fit ``STEP_BUDGET`` or took the full width:
(``engine.stats["step_s_chunk"]`` + ``["step_s_full_width"]``) over
(``["steps_chunk"]`` + ``["steps_full_width"]``), each sum bumped with its count
in ``_read`` from the time and the rows of THE SAME step. Every decoding row of
such a step waits this long for its token: where ``chunk_step_share_pct``
passes 5, ``tpot_p95_ms`` sits on it. Nothing to read in an engine without the
counters. Moves tpot_p95_ms."""

from benchmark import reduce


def read(run):
    end = run.get("marks", {}).get("end", {}).get("stats", {})
    if "step_s_chunk" not in end:
        return None
    n = sum(reduce.window_delta(run, k)
            for k in ("steps_chunk", "steps_full_width"))
    sec = sum(reduce.window_delta(run, k)
              for k in ("step_s_chunk", "step_s_full_width"))
    return 1e3 * sec / n if n else None
