"""Serve engine: how long the requests that stood at the queue's head short
of WINDOW blocks lay pending (the window pool's reservation was full:
``waited_for`` ``"window_blocks"`` on the ``serve.llm::pending`` span), on
the engine's own stamps: ``engine.stats["window_blocks_wait_s"]`` summed over
the requests admitted in the window, over ALL of them
(``["requests_admitted"]``), in ms: the part of ``ttft_pending_wait_ms`` that
a larger window pool would take away, 0 where no request waited for it.
Nothing to read in an engine without the counters. Moves ttft_p90_ms."""

from benchmark import reduce


def read(run):
    end = run.get("marks", {}).get("end", {}).get("stats", {})
    if "window_blocks_wait_s" not in end:
        return None
    n = reduce.window_delta(run, "requests_admitted")
    return 1e3 * reduce.window_delta(run, "window_blocks_wait_s") / n \
        if n else None
