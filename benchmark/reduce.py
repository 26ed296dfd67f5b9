"""Small selections over one run's records that several per-layer readers
share. A reader gets ``run``: the cell, the window, the clients (driver
side), the replica's records (steps, spans, marks) and the reduced trace.
"""

from __future__ import annotations

from typing import Any, Dict, List


def steps_in_window(run: Dict[str, Any]) -> List[tuple]:
    t0, t1 = run["window"]
    return [s for s in run["replica"]["steps"] if s[0] >= t0 and s[1] <= t1]


def window_delta(run: Dict[str, Any], stat: str) -> int:
    """An engine counter's growth over the window."""
    marks = run["marks"]
    return marks["end"]["stats"][stat] - marks["start"]["stats"][stat]


def traced(run: Dict[str, Any]):
    """The reduced trace if it saw a device, else None."""
    tr = run.get("trace")
    return tr if tr and tr.get("n_devices") else None
