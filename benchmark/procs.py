"""A run leaves no process behind."""

from __future__ import annotations

import os
import time


def wait_gone(pid: int, deadline_s: float = 60.0) -> bool:
    """Wait until the process that held the chips has ended."""
    t_end = time.monotonic() + deadline_s
    while time.monotonic() < t_end:
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            return True
        time.sleep(0.05)
    return False
