"""Model step: of the seconds of the engine steps read in the window, the
share spent in steps whose real positions passed ``STEP_BUDGET``, fit twice
it, and ran the step program's SECOND width (``2 * STEP_BUDGET`` positions,
a width the program has only on a grid wider still) and not the whole grid:
``engine.stats["step_s_second_width"]`` over ``["step_s_chunk"]`` +
``["step_s_full_width"]`` + ``["step_s_decode_only"]``, each bumped in
``_read`` with its count. The pair is counted BESIDE the step's kind (such a
step is a ``chunk`` step, or ``decode_only`` with that many decoding rows),
so the denominator is the three kinds alone. 0 in a window without such a
step (and in an engine whose grid has no second width). Nothing to read in
an engine without the counters. Moves tpot_p95_ms."""

from benchmark import reduce


def read(run):
    end = run.get("marks", {}).get("end", {}).get("stats", {})
    if "step_s_second_width" not in end:
        return None
    sec = sum(reduce.window_delta(run, k)
              for k in ("step_s_chunk", "step_s_full_width",
                        "step_s_decode_only"))
    second = reduce.window_delta(run, "step_s_second_width")
    return 100.0 * second / sec if sec else None
