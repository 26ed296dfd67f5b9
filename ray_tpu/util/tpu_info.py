"""TPU backend detection, per-chip peak-FLOPs table, compile-cache setup.

The chip is attached directly under jax platform ``"tpu"``. Anything that
dispatches to Pallas kernels or computes MFU goes through these helpers so
the platform test and the peak table live in one place.
"""

from __future__ import annotations

import importlib
import os
import threading

TPU_PLATFORM = "tpu"

# Public spec-sheet peak bf16 matmul FLOP/s per chip (Google Cloud TPU
# documentation, per-generation system-architecture pages), keyed by the
# ``device_kind`` string libtpu reports. A kind that is not here is an
# error: an MFU computed against a guessed peak is a wrong number with a
# device's name on it.
PEAK_FLOPS_BY_KIND = {
    "TPU v2": 45e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def is_tpu_backend() -> bool:
    import jax

    return jax.default_backend() == TPU_PLATFORM


def device_kind() -> str:
    import jax

    return jax.devices()[0].device_kind


def peak_flops_per_chip(kind: str | None = None) -> float:
    """Peak bf16 FLOP/s of one chip of ``kind`` (default: the attached
    device). Raises ``KeyError`` for a kind the table does not know."""
    kind = device_kind() if kind is None else kind
    try:
        return PEAK_FLOPS_BY_KIND[kind]
    except KeyError:
        raise KeyError(
            f"no peak-FLOPs entry for device_kind {kind!r}; add it to "
            f"ray_tpu.util.tpu_info.PEAK_FLOPS_BY_KIND with its source "
            f"(known: {sorted(PEAK_FLOPS_BY_KIND)})") from None


def hbm_usage():
    """HBM usage summed over local devices: ``{"bytes_in_use",
    "bytes_limit", "peak_bytes_in_use"}``, or None off-TPU (the CPU
    backend reports no ``memory_stats``)."""
    import jax

    if jax.default_backend() != TPU_PLATFORM:
        return None
    used = limit = peak = 0
    for d in jax.local_devices():
        ms = d.memory_stats()
        used += int(ms["bytes_in_use"])
        limit += int(ms["bytes_limit"])
        peak += int(ms.get("peak_bytes_in_use", 0))
    return {"bytes_in_use": used, "bytes_limit": limit,
            "peak_bytes_in_use": peak}


def ensure_compile_cache() -> str | None:
    """Turn on jax's persistent compilation cache for this process and
    return the directory it uses. Called by every process that owns a
    chip (train workers, ``LLMEngine``) before its first
    compile. A process pinned to the CPU (``JAX_PLATFORMS=cpu``: tests,
    pool workers) owns no chip and gets no cache: returns None without
    touching jax's backends.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it and
    nothing is set in code. Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache`` — the path is part of the cache key, so it
    never carries a pid, a time or a temp name.
    """
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        return None
    import jax

    global _counting
    if not _counting:
        _counting = True
        jax.monitoring.register_event_listener(_count_cache_event)
        # a process that owns a chip traces Pallas kernels (flash attention
        # in the train step, paged attention in the serve step), and
        # importing Pallas is about a second of pure Python: start it now,
        # beside the load of the process's first program, so that the first
        # kernel trace finds it done (an import in flight is simply waited
        # for)
        threading.Thread(target=importlib.import_module,
                         args=("jax.experimental.pallas.tpu",),
                         name="rtpu-import-pallas", daemon=True).start()
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


_counting = False
_cache_events = {"hits": 0, "misses": 0}


def _count_cache_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _cache_events["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _cache_events["misses"] += 1


def compile_cache_counters() -> dict:
    """Persistent-cache ``{"hits", "misses"}`` seen by this process since
    :func:`ensure_compile_cache` (jax's own monitoring events; programs
    that compile faster than jax's caching threshold count as neither)."""
    return dict(_cache_events)
