"""Entry points (serve/api.py, serve/handle.py): what the handle, the actor
call and the stream add to a request's first token. Median over requests of
the client's send-to-first-token time minus the replica's own
submit-to-first-emit time, both stamped by the benchmark on CLOCK_MONOTONIC
of one machine. Moves ttft_p90_ms."""

from benchmark import stats


def read(run):
    engine = run["replica"]["engine_ttft"]
    over = [(c.stamps[0] - c.sent - engine[c.key]) * 1e3
            for c in run["clients"] if c.stamps and c.key in engine]
    return stats.median(over) if over else None
