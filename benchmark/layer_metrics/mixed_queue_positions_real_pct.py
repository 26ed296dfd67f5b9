"""Serve engine: of the positions the window's steps multiplied their weights
by, the share that were real: ``engine.stats["step_positions_real"]`` (a
decoding row's token, a prefilling row's chunk) over
``["step_positions_run"]`` (``STEP_BUDGET`` positions a step whose real ones
fit it, the whole ``max_slots x prefill_chunk`` grid a step whose do not), in
the windowed MoE family's cell, where long prompts prefill beside short rows
and every step is over the budget (``step_positions_real_pct`` reads the same
counters in the cells it lists). The rest is padding the matmuls and the
attention call paid for. Nothing to read in a program without the counters.
Moves tpot_p95_ms."""

from benchmark import reduce


def read(run):
    end = run.get("marks", {}).get("end", {}).get("stats", {})
    if "step_positions_run" not in end:
        return None
    ran = reduce.window_delta(run, "step_positions_run")
    return 100.0 * reduce.window_delta(run, "step_positions_real") / ran \
        if ran else None
