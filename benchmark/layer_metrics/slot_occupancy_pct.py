"""Serve engine: mean over the window's steps of active slots over
``max_slots``. At a fixed offered rate it is arrivals x slot time per
request / slots, so shorter steps lower it and with it the wait for a slot.
Moves ttft_p90_ms."""

from benchmark import reduce


def read(run):
    steps = reduce.steps_in_window(run)
    if not steps:
        return None
    slots = run["replica"]["max_slots"]
    return 100.0 * sum(len(s[2]) for s in steps) / (slots * len(steps))
