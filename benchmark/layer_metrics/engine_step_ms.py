"""Serve engine (serve/llm.py, serve/kv_cache.py, serve/admission.py):
median wall time of ``engine.step()`` in the window, timed by the benchmark's
replica subclass: admission, input building, the device step, the logits
fetch, sampling and emitting. Moves tpot_p95_ms."""

from benchmark import reduce, stats


def read(run):
    steps = reduce.steps_in_window(run)
    return stats.median([(s[1] - s[0]) * 1e3 for s in steps]) if steps else None
