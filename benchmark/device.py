"""What the process that holds the chips reports about them (needs jax:
call it only there)."""

from __future__ import annotations

import os
from typing import Any, Dict


def facts(setup: Dict[str, float]) -> Dict[str, Any]:
    """The device as jax reports it, the peak memory of the fullest chip,
    the persistent compile cache's counters and the set-up's phases."""
    import jax

    from ray_tpu.util import tpu_info

    devs = jax.devices()
    stats = [d.memory_stats() or {} for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "pid": os.getpid(),
            "memory_peak_bytes": max(
                (int(s.get("peak_bytes_in_use", 0)) for s in stats),
                default=0),
            "compile_cache": tpu_info.compile_cache_counters(),
            "setup": setup}


def compile_counts() -> Dict[str, int]:
    """Compilations per program so far, from the ``device_plane`` registry:
    the difference over the window must be nothing."""
    from ray_tpu.util import device_plane

    return {r["program"]: r.get("compiles", 0)
            for r in device_plane.registry().rows()}


def compiled_between(before: Dict[str, int],
                     after: Dict[str, int]) -> Dict[str, int]:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}
