"""Serve engine: of the engine steps read in the window, the share in which a
row was fed prompt tokens: (``engine.stats["steps_chunk"]`` +
``["steps_full_width"]``) over those and ``["steps_decode_only"]``, counted by
kind in ``_read``. A percentile of the gap between tokens sits on
``chunk_step_ms`` once this share passes what lies beyond the percentile.
Nothing to read in an engine without the counters. Moves tpot_p95_ms."""

from benchmark import reduce


def read(run):
    end = run.get("marks", {}).get("end", {}).get("stats", {})
    if "steps_chunk" not in end:
        return None
    chunk = sum(reduce.window_delta(run, k)
                for k in ("steps_chunk", "steps_full_width"))
    steps = chunk + reduce.window_delta(run, "steps_decode_only")
    return 100.0 * chunk / steps if steps else None
