"""Serve engine: of the window's engine steps, the share dispatched while the
step before them was still unread: ``engine.stats["steps_dispatched_ahead"]``
over ``["steps"]`` (``rtpu_serve_steps_dispatched_ahead_total``), counted
beside ``["steps"]`` in ``step()``. On such a step the host's work (reading
the last step's token ids, emitting, admitting, building tables) ran under the
device's; a step that found nothing in flight (the first after an idle engine,
or after a migration settled the one in flight) paid for it with the chip
idle. Nothing to read in an engine without the counter. Moves tpot_p95_ms."""

from benchmark import reduce


def read(run):
    end = run.get("marks", {}).get("end", {}).get("stats", {})
    if "steps_dispatched_ahead" not in end:
        return None
    steps = reduce.window_delta(run, "steps")
    if not steps:
        return None
    return 100.0 * reduce.window_delta(run, "steps_dispatched_ahead") / steps
