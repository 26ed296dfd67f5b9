"""Serve engine: of the positions the window's steps multiplied their weights
by, the share that were real: ``engine.stats["step_positions_real"]`` (what
the rows were fed: a decoding row's token, a prefilling row's chunk) over
``["step_positions_run"]`` (``STEP_BUDGET`` positions a step whose real ones
fit it, the whole ``max_slots x prefill_chunk`` grid a step whose do not, and
every step of a program without a budget), exact counts made on the host by
the rule the program applies on the device. The rest is padding the matmuls
paid for. ``steps_full_width`` over ``steps`` (the steps that took the whole
grid, on which the tail of the gap between tokens sits) has no reader.
Nothing to read in a program without the counters. Moves tpot_p95_ms."""

from benchmark import reduce


def read(run):
    if "step_positions_run" not in run["marks"]["end"]["stats"]:
        return None
    ran = reduce.window_delta(run, "step_positions_run")
    if not ran:
        return None
    return 100.0 * reduce.window_delta(run, "step_positions_real") / ran
