"""A small traffic-replay harness for the serving tier's tests: a
deterministic multi-tenant trace (a shared-prefix mixture with bursty on/off
arrivals, optionally salted with periodic LONG prompts), generated as a
stream, and a replay of it against any ``request -> token iterator`` with one
client thread a stream in flight, bounded reservoirs for the latencies and
errors classified by type. (The load generator that measures is
``benchmark/``; this one only drives tests.)"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Iterable, Iterator, List, Optional


@dataclass
class TraceConfig:
    n_requests: int = 200
    n_tenants: int = 4
    shared_prefix_tokens: int = 48     # per-tenant system prompt length
    suffix_tokens_mean: int = 12       # user-suffix length (geometric-ish)
    max_new_tokens: int = 16
    vocab: int = 256
    # bursty arrivals: ON periods at burst_rps, OFF gaps between bursts
    burst_rps: float = 50.0
    burst_len_s: float = 0.5
    gap_s: float = 0.25
    seed: int = 0
    # mixed-workload salt (ISSUE 13): every Nth request carries a LONG
    # prompt — the arrival pattern that makes colocated decode cadence
    # collapse and disaggregation win. 0 disables.
    long_every: int = 0
    long_prompt_tokens: int = 0


@dataclass
class Request:
    arrival_s: float
    tenant: int
    prompt: List[int]
    max_new: int


def iter_trace(cfg: TraceConfig) -> Iterator[Request]:
    """Deterministic multi-tenant trace, yielded one request at a time:
    tenant system prompts are fixed per seed; arrivals are an on/off
    burst process (the shape that separates load-aware routing from
    round-robin — bursts pile onto whichever replica round-robin happens
    to hit mid-burst). O(tenants) state regardless of n_requests."""
    import numpy as np

    rng = np.random.default_rng(cfg.seed)
    prefixes = [rng.integers(0, cfg.vocab, cfg.shared_prefix_tokens)
                .tolist() for _ in range(cfg.n_tenants)]
    t = 0.0
    in_burst_left = cfg.burst_len_s
    for i in range(cfg.n_requests):
        # exponential inter-arrival inside a burst; jump the gap when the
        # burst budget is spent
        dt = float(rng.exponential(1.0 / cfg.burst_rps))
        in_burst_left -= dt
        if in_burst_left <= 0:
            t += cfg.gap_s
            in_burst_left = cfg.burst_len_s
        t += dt
        tenant = int(rng.integers(cfg.n_tenants))
        if cfg.long_every and (i + 1) % cfg.long_every == 0:
            n_suffix = cfg.long_prompt_tokens
        else:
            n_suffix = 1 + int(rng.geometric(1.0 / cfg.suffix_tokens_mean))
            if cfg.long_every and cfg.long_prompt_tokens:
                # keep the mixed workload bimodal: the geometric tail
                # must not wander into long-prompt territory
                n_suffix = min(n_suffix, cfg.long_prompt_tokens - 1)
        prompt = prefixes[tenant] + rng.integers(
            0, cfg.vocab, n_suffix).tolist()
        yield Request(t, tenant, prompt, max_new=cfg.max_new_tokens)


def gen_trace(cfg: TraceConfig) -> List[Request]:
    """Materialized trace (tests / small scales)."""
    return list(iter_trace(cfg))


class _Reservoir:
    """Fixed-size uniform sample of a stream — percentile estimates for
    traces far too long to keep every latency (1M requests x 64 TPOTs
    would be half a GB as floats)."""

    def __init__(self, cap: int = 65536, seed: int = 0):
        import random

        self.cap = cap
        self.n = 0
        self.xs: List[float] = []
        self._rng = random.Random(seed)

    def add(self, x: float) -> None:
        self.n += 1
        if len(self.xs) < self.cap:
            self.xs.append(x)
        else:
            j = self._rng.randrange(self.n)
            if j < self.cap:
                self.xs[j] = x

    def percentile(self, q: float) -> float:
        from ray_tpu.serve.admission import _percentile

        return _percentile(sorted(self.xs), q)


@dataclass
class ReplayStats:
    started: int = 0
    completed: int = 0
    shed: int = 0
    deadline: int = 0
    errors: int = 0
    tokens: int = 0
    wall_s: float = 0.0
    ttft: _Reservoir = field(default_factory=_Reservoir)
    tpot: _Reservoir = field(default_factory=_Reservoir)


def classify_error(e: BaseException) -> str:
    """"shed" / "deadline" / "error" off the machine-readable
    ``error_type`` that admission errors declare and ``TaskError``
    wrappers carry across process boundaries: no matching of messages."""
    from ray_tpu.serve.admission import (DeadlineExceededError,
                                         RequestShedError)

    seen = set()
    cur: Optional[BaseException] = e
    while cur is not None and id(cur) not in seen:
        seen.add(id(cur))
        if isinstance(cur, RequestShedError):
            return "shed"
        if isinstance(cur, DeadlineExceededError):
            return "deadline"
        et = getattr(cur, "error_type", None)
        if et in ("shed", "deadline"):
            return et
        cur = getattr(cur, "cause", None) or cur.__cause__
    return "error"


def replay(stream_fn: Callable[[Request], Iterable[int]],
           trace: Iterable[Request], *, time_scale: float = 1.0,
           max_clients: int = 32) -> ReplayStats:
    """Drive the trace against ``stream_fn`` (request -> token iterator),
    honoring arrival times (``time_scale`` stretches/compresses them;
    0 = closed loop). Each in-flight request holds one client thread —
    the streaming consumption model real callers have — and at most
    ``max_clients`` are alive at once, so memory is bounded by the
    client window, never the trace length."""
    stats = ReplayStats()
    lock = threading.Lock()
    sem = threading.Semaphore(max_clients)
    t0 = time.monotonic()

    def client(req: Request) -> None:
        try:
            t_submit = time.monotonic()
            first = None
            last = t_submit
            n = 0
            try:
                for tok in stream_fn(req):
                    now = time.monotonic()
                    if first is None:
                        first = now - t_submit
                    else:
                        with lock:
                            stats.tpot.add(now - last)
                    last = now
                    n += 1
            except BaseException as e:  # noqa: BLE001 - classified below
                kind = classify_error(e)
                with lock:
                    if kind == "shed":
                        stats.shed += 1
                    elif kind == "deadline":
                        stats.deadline += 1
                    else:
                        stats.errors += 1
                    stats.tokens += n
                return
            with lock:
                stats.completed += 1
                stats.tokens += n
                if first is not None:
                    stats.ttft.add(first)
        finally:
            sem.release()

    for req in trace:
        target = t0 + req.arrival_s * time_scale
        delay = target - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        sem.acquire()
        stats.started += 1
        threading.Thread(target=client, args=(req,), daemon=True).start()
    # drain: re-acquire every client permit (each release marks one
    # client finished) — no per-thread bookkeeping, so a 1M-request
    # replay never holds 1M Thread objects
    deadline = time.monotonic() + 600
    for _ in range(max_clients):
        if not sem.acquire(timeout=max(0.1, deadline - time.monotonic())):
            break
    stats.wall_s = time.monotonic() - t0
    return stats
