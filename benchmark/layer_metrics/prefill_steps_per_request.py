"""Serve engine: engine steps that fed a request's prompt, per request whose
first token was read in the window: ``engine.stats["prefill_steps"]`` over
``["first_tokens"]`` (``rtpu_serve_prefill_steps_total`` over
``rtpu_serve_first_tokens_total``), summed at the first token's read. A prefix
hit spares a request its steps; ``ttft_prefill_ms`` over this is what one such
step cost it. Nothing to read in an engine without the counters. Moves
ttft_p90_ms."""

from benchmark import reduce


def read(run):
    end = run.get("marks", {}).get("end", {}).get("stats", {})
    if "prefill_steps" not in end:
        return None
    n = reduce.window_delta(run, "first_tokens")
    return reduce.window_delta(run, "prefill_steps") / n if n else None
