"""Serve engine: the host's work in one ``LLMEngine.step()`` call: the call's
wall time less its wait for the device (the ``serve.step::read`` stamp), mean
over the window's calls that dispatched a step:
``engine.stats["step_host_s"]`` over ``["steps"]``
(``rtpu_serve_step_host_s_total``), bumped together at the end of ``step()``.
Admission, the next step's tables, the dispatch, routing the read tokens. The
lookahead hides it under the device's step as long as it is the shorter; it is
the next wall once a step falls under it. Nothing to read in an engine without
the counter. Moves tpot_p95_ms."""

from benchmark import reduce


def read(run):
    end = run.get("marks", {}).get("end", {}).get("stats", {})
    if "step_host_s" not in end:
        return None
    n = reduce.window_delta(run, "steps")
    return 1e3 * reduce.window_delta(run, "step_host_s") / n if n else None
