"""Serve engine: how long a request lay in the engine's queue, on the engine's
own stamps: ``engine.stats["pending_wait_s"]`` over ``["requests_admitted"]``
(``rtpu_serve_pending_wait_s_total`` over ``rtpu_serve_requests_admitted_total``),
both bumped in one update where ``_sweep_and_admit`` claims slot and blocks;
the span ``serve.llm::pending`` is the same interval for one request. The mean
over the requests admitted in the window, in ms: with ``ttft_prefill_ms`` and
``handle_ttft_overhead_ms`` it is what a first token's time is made of. Nothing
to read in an engine without the counters. Moves ttft_p90_ms."""

from benchmark import reduce


def read(run):
    end = run.get("marks", {}).get("end", {}).get("stats", {})
    if "pending_wait_s" not in end:
        return None
    n = reduce.window_delta(run, "requests_admitted")
    return 1e3 * reduce.window_delta(run, "pending_wait_s") / n if n else None
