"""Serve engine: from a request's admission (slot and blocks claimed) to the
read of its first token, on the engine's own stamps:
``engine.stats["prefill_s"]`` over ``["first_tokens"]``
(``rtpu_serve_prefill_s_total`` over ``rtpu_serve_first_tokens_total``), both
bumped in one update in ``_observe_emit``; the span ``serve.llm::prefill`` is
the same interval for one request. The mean over the first tokens read in the
window, in ms: the chunk steps of the prompt, each as long as the rows that
share it make it. Nothing to read in an engine without the counters. Moves
ttft_p90_ms."""

from benchmark import reduce


def read(run):
    end = run.get("marks", {}).get("end", {}).get("stats", {})
    if "prefill_s" not in end:
        return None
    n = reduce.window_delta(run, "first_tokens")
    return 1e3 * reduce.window_delta(run, "prefill_s") / n if n else None
