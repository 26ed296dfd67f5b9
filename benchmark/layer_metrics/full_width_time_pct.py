"""Serve engine: of the seconds of the engine steps read in the window, the
share spent in steps whose real positions passed ``STEP_BUDGET`` and took the
whole grid: ``engine.stats["step_s_full_width"]`` over it and
``["step_s_chunk"]`` and ``["step_s_decode_only"]``, each bumped in ``_read``
with its count. 0 in a window without such a step. Nothing to read in an
engine without the counters. Moves tpot_p95_ms."""

from benchmark import reduce


def read(run):
    end = run.get("marks", {}).get("end", {}).get("stats", {})
    if "step_s_full_width" not in end:
        return None
    full = reduce.window_delta(run, "step_s_full_width")
    sec = full + sum(reduce.window_delta(run, k)
                     for k in ("step_s_chunk", "step_s_decode_only"))
    return 100.0 * full / sec if sec else None
