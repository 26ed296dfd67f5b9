"""Serve engine: host milliseconds a prefix hit spends in the call that
copies its snapshot into its slot (the span ``serve::restore_state``: the
dispatch of one small device program, the device's copy of 21 MB runs behind
it in order), on the engine's own stamps: ``engine.stats["state_restore_s"]``
over ``["state_snapshots_restored"]`` in the window. Every restored request's
first token waits on it where it would wait on the prefix's chunk steps.
Nothing to read in an engine without the counters, or in a window that
restored nothing. Moves ttft_p90_ms."""

from benchmark import reduce


def read(run):
    end = run.get("marks", {}).get("end", {}).get("stats", {})
    if "state_restore_s" not in end:
        return None
    n = reduce.window_delta(run, "state_snapshots_restored")
    return 1e3 * reduce.window_delta(run, "state_restore_s") / n \
        if n else None
