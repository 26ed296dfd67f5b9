"""Traffic-replay load generator for the LLM serving tier (ISSUE 12/13).

Replays a synthetic multi-tenant trace — a shared-prefix mixture (each
tenant has a fixed system prompt; its requests append distinct user
suffixes) with bursty on/off arrivals, optionally salted with periodic
LONG prompts (the disaggregation stressor: a long prefill arriving
during steady decode) — against one of:

- an in-process :class:`~ray_tpu.serve.llm.LLMEngine`;
- an in-process colocated-vs-disaggregated engine PAIR (``--disagg``);
- a deployed multi-replica application (``--serve``), optionally
  through a multi-node cluster (``--nodes N``) and optionally split
  into prefill/decode pools (``--serve --disagg``).

The trace is GENERATED AS A STREAM (O(1) memory per in-flight request)
and the stats keep bounded reservoirs, so ``--scale full`` (>= 1M
requests — the ROADMAP's millions-of-users envelope) runs in bounded
memory; the envelope is the cluster's, not the harness's. Reports the
serving-tier scorecard:

    tokens/s (generated), TTFT p50/p99, TPOT p50/p99,
    prefix-cache hit rate, shed rate, error count,
    SLO verdict + per-pool KV-leak audit (serve modes)

Prints ONE JSON line.
"""

from __future__ import annotations

import argparse
import hashlib as _hash
import json

import os
import sys
import threading
import time
import uuid
from dataclasses import dataclass, field
from typing import (Any, Callable, Dict, Iterable, Iterator, List,
                    Optional, Tuple)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:  # runnable as `python experiments/serve_replay.py`
    sys.path.insert(0, _REPO)


# ---------------------------------------------------------------------------
# trace generation (streamed: --scale full must not materialize 1M requests)
# ---------------------------------------------------------------------------

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
    # multi-model salt (ISSUE 16): each request addresses one of
    # n_models models, drawn Zipf(zipf_alpha) — the skew that makes
    # multiplexing win (the hot model spreads over every replica while
    # dedicated deployments strand their cold engines). 0 disables.
    n_models: int = 0
    zipf_alpha: float = 1.5


@dataclass
class Request:
    arrival_s: float
    tenant: int
    prompt: List[int]
    max_new: int
    model_id: Optional[str] = None


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
    model_p = None
    if cfg.n_models > 0:
        w = np.array([1.0 / (r + 1) ** cfg.zipf_alpha
                      for r in range(cfg.n_models)])
        model_p = w / w.sum()
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
        mid = (f"m{int(rng.choice(cfg.n_models, p=model_p))}"
               if model_p is not None else None)
        yield Request(t, tenant, prompt, max_new=cfg.max_new_tokens,
                      model_id=mid)


def gen_trace(cfg: TraceConfig) -> List[Request]:
    """Materialized trace (tests / small scales)."""
    return list(iter_trace(cfg))


# ---------------------------------------------------------------------------
# replay harness (bounded memory at any request count)
# ---------------------------------------------------------------------------

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

    def summary(self) -> Dict[str, Any]:
        return {
            "requests": self.started,
            "completed": self.completed,
            "shed": self.shed,
            "deadline_exceeded": self.deadline,
            "errors": self.errors,
            "tokens": self.tokens,
            "wall_s": round(self.wall_s, 3),
            "tokens_per_s": round(self.tokens / self.wall_s, 2)
            if self.wall_s else 0.0,
            "shed_rate": round(self.shed / max(self.started, 1), 4),
            "ttft_p50_s": round(self.ttft.percentile(0.50), 4),
            "ttft_p99_s": round(self.ttft.percentile(0.99), 4),
            "tpot_p50_s": round(self.tpot.percentile(0.50), 5),
            "tpot_p99_s": round(self.tpot.percentile(0.99), 5),
        }


def classify_error(e: BaseException) -> str:
    """"shed" / "deadline" / "error" off the machine-readable
    ``error_type`` that admission errors declare and ``TaskError``
    wrappers now carry across process boundaries (ISSUE 13 satellite —
    no more str()-prefix matching)."""
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
           max_clients: int = 32,
           on_error: Optional[Callable[[Request, BaseException], str]]
           = None, max_wall_s: Optional[float] = None,
           progress_every: int = 0) -> ReplayStats:
    """Drive the trace against ``stream_fn`` (request -> token iterator),
    honoring arrival times (``time_scale`` stretches/compresses them;
    0 = closed loop). Each in-flight request holds one client thread —
    the streaming consumption model real callers have — and at most
    ``max_clients`` are alive at once, so memory is bounded by the
    client window, never the trace length. ``on_error`` overrides the
    default ``classify_error``. ``max_wall_s`` stops ADMITTING new
    requests after the budget (already-started streams drain)."""
    stats = ReplayStats()
    lock = threading.Lock()
    sem = threading.Semaphore(max_clients)
    t0 = time.monotonic()
    classify = on_error or (lambda req, e: classify_error(e))

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
                kind = classify(req, e)
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

    truncated = False
    for req in trace:
        if max_wall_s is not None \
                and time.monotonic() - t0 > max_wall_s:
            truncated = True
            break
        target = t0 + req.arrival_s * time_scale
        delay = target - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        sem.acquire()
        stats.started += 1
        threading.Thread(target=client, args=(req,), daemon=True).start()
        if progress_every and stats.started % progress_every == 0:
            print(f"# replay: {stats.started} started, "
                  f"{stats.completed} done, "
                  f"{time.monotonic() - t0:.0f}s", file=sys.stderr)
    # drain: re-acquire every client permit (each release marks one
    # client finished) — no per-thread bookkeeping, so a 1M-request
    # replay never holds 1M Thread objects
    deadline = time.monotonic() + 600
    for _ in range(max_clients):
        if not sem.acquire(timeout=max(0.1, deadline - time.monotonic())):
            break
    stats.wall_s = time.monotonic() - t0
    if truncated:
        stats.truncated = True  # type: ignore[attr-defined]
    return stats


# ---------------------------------------------------------------------------
# drivers: in-process engines (A/Bs) and deployed applications
# ---------------------------------------------------------------------------

class EngineRunner:
    """Minimal deployment-shaped wrapper over one in-process LLMEngine:
    a stepper thread plus a queue-backed token stream per request — the
    same-container A/B vehicle (no actor boot noise in the numbers)."""

    def __init__(self, engine):
        self.engine = engine
        self._stop = False
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def _loop(self):
        while not self._stop:
            if not self.engine.step():
                time.sleep(0.001)

    def stream(self, req: Request,
               deadline_s: Optional[float] = None) -> Iterable[int]:
        import queue as _q

        q: "_q.Queue[Any]" = _q.Queue()
        r = self.engine.submit(req.prompt, req.max_new, q.put_nowait,
                               deadline_s=deadline_s)
        try:
            while True:
                tok = q.get(timeout=120.0)
                if tok is None:
                    return
                if isinstance(tok, BaseException):
                    raise tok
                yield tok
        finally:
            self.engine.cancel(r)

    def close(self):
        self._stop = True
        self._thread.join(timeout=5)


def run_engine_ab(scale: str = "quick",
                  prefix_cache: bool = True, seed: int = 0,
                  model: str = "llama-debug",
                  time_scale: float = 0.0) -> Dict[str, Any]:
    """One replay against one in-process engine; returns the scorecard
    plus engine KV/prefix state. ``time_scale=0`` = closed-loop (submit
    as fast as clients free up) — the throughput-capability measurement;
    > 0 replays real arrival times."""
    from ray_tpu.serve.llm import LLMEngine

    cfg = _scale_trace(scale, seed)
    engine = LLMEngine(model, max_slots=8, max_len=256, seed=seed,
                       prefix_cache=prefix_cache,
                       block_size=16, prefill_chunk=8)
    runner = EngineRunner(engine)
    try:
        first = next(iter_trace(cfg))
        # warm the compile out of the measurement
        list(runner.stream(Request(0.0, 0, first.prompt[:8], 2)))
        stats = replay(runner.stream, iter_trace(cfg),
                       time_scale=time_scale)
    finally:
        runner.close()
    out = stats.summary()
    kv = engine.kv_state()
    if "prefix" in kv:
        p = kv["prefix"]
        lookups = max(p["hits"] + p["misses"], 1)
        out["prefix_hit_rate"] = round(p["hits"] / lookups, 4)
        out["prefix_hit_tokens"] = p["hit_tokens"]
    return out


def run_disagg_ab(scale: str = "quick", *, disagg: bool,
                  seed: int = 0,
                  model: str = "llama-debug") -> Dict[str, Any]:
    """Colocated-vs-disaggregated same-container A/B (ISSUE 13): TWO
    engines either way — colocated mode routes whole requests to the
    less-loaded engine; disagg mode dedicates one to chunked prefill
    and one to decode, shipping KV blocks over the real DeviceChannel
    path between them. Same hardware, same trace (mixed: steady short
    prompts + periodic long prompts), so the delta IS the architecture:
    long prefills stop sharing a step with in-flight decodes."""
    from ray_tpu.serve.llm import LLMDeployment

    cfg = _mixed_cfg(_scale_trace(scale, seed))
    kw = dict(_MIXED_ENGINE_KW, seed=seed)
    kw["max_len"] = _mixed_max_len(cfg, kw["block_size"])
    if disagg:
        # same TOTAL KV memory as the colocated pair (2x the per-engine
        # default), split by role: prefill holds only the transient
        # working set of in-flight prompts, decode keeps the sessions +
        # prefix cache — a decode pool sized like a colocated engine
        # would run at permanent pool pressure (every adopt evicts)
        base_blocks = kw["max_slots"] * (kw["max_len"]
                                         // kw["block_size"])
        pools = [LLMDeployment(model, role="prefill",
                               num_blocks=3 * base_blocks // 4, **kw),
                 # decode never prefills: one-block prefill_chunk keeps
                 # the chunk's dead compute out of every decode step
                 LLMDeployment(model, role="decode",
                               num_blocks=5 * base_blocks // 4,
                               **dict(kw,
                                      prefill_chunk=kw["block_size"]))]
        node = pools[0].identity()["node"]

        def stream(req: Request) -> Iterable[int]:
            rid = uuid.uuid4().hex
            desc = pools[0].prefill_export(
                req.prompt, {"req": rid, "dst": "decode0",
                             "dst_node": node})
            return pools[1].adopt_stream(req.prompt, desc, req.max_new)
    else:
        kw = dict(kw, prefill_chunk=_MIXED_COLOC_CHUNK)
        pools = [LLMDeployment(model, role="colocated", **kw),
                 LLMDeployment(model, role="colocated", **kw)]

        def stream(req: Request) -> Iterable[int]:
            states = [p.engine.kv_state() for p in pools]
            loads = [s["inflight"] + s["queued"] for s in states]
            return pools[loads.index(min(loads))](
                req.prompt, req.max_new)

    try:
        first = next(iter_trace(cfg))
        # warm every engine's compile paths out of the measurement
        for p in _mixed_warm_prompts(cfg, first.prompt * 16,
                                     kw["block_size"]):
            for _ in range(2):
                list(stream(Request(0.0, 0, list(p), 2)))
        stats = replay(stream, iter_trace(cfg), time_scale=0.0,
                       max_clients=8)
    finally:
        for p in pools:
            p.close()   # in-process: nosess rings have no sweep
    out = stats.summary()
    out["mode"] = "disagg" if disagg else "colocated"
    states = [p.engine.kv_state() for p in pools]
    out["kv_leaks"] = sum(
        s["kv_total"] - s["kv_free"] - s["prefix"]["nodes"]
        for s in states)
    out["exported"] = sum(p.engine.stats["exported"] for p in pools)
    out["adopted"] = sum(p.engine.stats["adopted"] for p in pools)
    return out


def run_multiplex_ab(scale: str = "quick", *, dedicated: bool,
                     n_models: int = 8, replicas: int = 2,
                     speculative: bool = False,
                     budget_models: int = 2, seed: int = 0,
                     model: str = "llama-debug") -> Dict[str, Any]:
    """Multi-model consolidation A/B (ISSUE 16): the SAME Zipf trace
    over ``n_models`` models, the SAME fleet-wide weight budget of
    ``replicas * budget_models`` resident model-slots, two ways of
    spending it. The DEDICATED arm does what static allocation does:
    deploys the Zipf-hottest models that fit the budget, one engine
    each, and hard-sheds every request for a model it chose not to
    host. The MULTIPLEX arm serves ALL ``n_models`` through
    ``replicas`` multiplexed deployments whose registries page weights
    in and out of the same per-replica budget on demand (LRU under
    in-flight pinning) — the swap counters in the output are the proof
    that the tail models were PAGED, not resident. Replay is
    open-loop at ~75% of fleet capacity, so a shed request is lost
    tokens at unchanged wall time, exactly what it is in production.

    Routing in the multiplex arm is sticky-home (models greedy-packed
    onto replicas by Zipf weight — steady traffic partitions the fleet
    into full batches exactly like dedicated deployments would) with
    budget-shed retries walking the other replicas and then waiting
    for an in-flight pin to drain; eager least-inflight splitting
    would fragment the hot model's batches on every request.

    ``budget_models=0`` removes the budget from BOTH arms (dedicated
    hosts all ``n_models``; the registry pages lazily but never
    evicts) — the capacity-unconstrained control."""
    from ray_tpu.serve.admission import RequestShedError
    from ray_tpu.serve.llm import LLMDeployment
    from ray_tpu.serve.multiplex import MultiplexedLLMDeployment

    cfg = _scale_trace(scale, seed)
    cfg.n_models = n_models
    cfg.zipf_alpha = 1.0
    cfg.max_new_tokens = max(cfg.max_new_tokens, 32)
    # steady open-loop arrivals at ~75% of measured fleet capacity
    # (~700 tok/s on the 2-vCPU CI box): wall time is set by the
    # ARRIVAL span, so the dedicated arm cannot convert its sheds into
    # a shorter run — lost requests are lost tokens
    cfg.n_requests = max(cfg.n_requests, 96)
    cfg.burst_rps = 16.0
    cfg.burst_len_s = 1e9        # steady Poisson, no off-gaps
    model_ids = [f"m{i}" for i in range(n_models)]
    fleet_slots = (replicas * budget_models if budget_models > 0
                   else n_models)
    kw = dict(max_slots=8, max_len=256, block_size=16, prefill_chunk=8)
    lock = threading.Lock()
    if dedicated:
        # static allocation: one single-model deployment per hosted
        # model, Zipf-hottest first, as many as the weight budget
        # seats; per-model seeds match the multiplex arm's registry
        # (identical weights per arm)
        deps = {mid: LLMDeployment(model, seed=seed + i, **kw)
                for i, mid in enumerate(model_ids[:fleet_slots])}
        pools: List[Any] = list(deps.values())

        def stream(req: Request) -> Iterable[int]:
            dep = deps.get(req.model_id)
            if dep is None:
                raise RequestShedError(
                    f"no deployment hosts {req.model_id!r} (fleet "
                    f"weight budget seats {fleet_slots} models)",
                    reason="model_budget")
            return dep(req.prompt, req.max_new)

        warm = [(dep, {}) for dep in deps.values()]
    else:
        spec = {mid: {"config": model, "seed": seed + i}
                for i, mid in enumerate(model_ids)}
        budget = None
        if budget_models > 0:
            import jax

            from ray_tpu import models as M

            c = M.get_config(model)
            one = M.params_bytes(M.init_params(jax.random.PRNGKey(0), c))
            budget = budget_models * one + 1
        pools = [MultiplexedLLMDeployment(
                     spec, budget_bytes=budget, speculative=speculative,
                     spec_accept_floor=0.0 if speculative else None,
                     seed=seed, **kw)
                 for _ in range(replicas)]
        w = [1.0 / (r + 1) ** cfg.zipf_alpha for r in range(n_models)]
        packed = [0.0] * replicas
        home: Dict[str, int] = {}
        for i, mid in enumerate(model_ids):
            j = packed.index(min(packed))
            home[mid] = j
            packed[j] += w[i]
        counts = [0] * replicas

        def _try(pick: int, req: Request):
            return pools[pick](req.prompt, req.max_new,
                               model_id=req.model_id)

        def stream(req: Request) -> Iterable[int]:
            # home first; on a model_budget shed walk the other
            # replicas; when every registry is pinned full, wait for a
            # stream to drain a pin and retry — the request queues for
            # a model-slot instead of dying
            deadline = time.monotonic() + 30.0
            while True:
                order = [home[req.model_id]] + [
                    j for j in range(replicas)
                    if j != home[req.model_id]]
                shed: Optional[BaseException] = None
                for pick in order:
                    try:
                        inner = _try(pick, req)
                        break
                    except RequestShedError as e:
                        if getattr(e, "reason", "") != "model_budget":
                            raise
                        shed = e
                else:
                    if time.monotonic() > deadline:
                        raise shed
                    time.sleep(0.025)
                    continue
                break
            with lock:
                counts[pick] += 1

            def gen() -> Iterator[int]:
                try:
                    yield from inner
                finally:
                    with lock:
                        counts[pick] -= 1

            return gen()

        warm = [(rep, {"model_id": mid})
                for rep in pools for mid in model_ids]
    try:
        first = next(iter_trace(cfg))
        # warm every (replica, model) engine's compile out of the
        # measurement — in the multiplex arm this IS the lazy
        # materialization (the registry counts the page-ins), and
        # under the budget it already runs the LRU churn the swap
        # counters report; a mid-run compile would stall every
        # in-flight decode on that replica
        for target, target_kw in warm:
            list(target(first.prompt[:8], 2, **target_kw))
            list(target(list(first.prompt), 2, **target_kw))
        stats = replay(stream, iter_trace(cfg), time_scale=1.0,
                       max_clients=32)
        # collect BEFORE close(): close tears down the lazy engines
        # and frees the registry entries the counters live on
        rep_stats = ([] if dedicated
                     else [rep.stats() for rep in pools])
    finally:
        for p in pools:
            p.close()
    out = stats.summary()
    out["mode"] = "dedicated" if dedicated else "multiplex"
    out["n_models"] = n_models
    out["fleet_model_slots"] = fleet_slots
    if dedicated:
        out["engines"] = len(pools)
        out["hosted_models"] = len(pools)
    else:
        snaps = [s["models"] for s in rep_stats]
        out["replicas"] = replicas
        out["engines"] = sum(len(s) - 1 for s in rep_stats)
        out["swaps_in"] = sum(r["swaps_in"] for s in snaps
                              for r in s.values())
        out["swaps_out"] = sum(r["swaps_out"] for s in snaps
                               for r in s.values())
        if budget_models > 0:
            out["budget_models"] = budget_models
        if speculative:
            agg = {"spec_proposed": 0, "spec_accepted": 0,
                   "spec_fallbacks": 0}
            for s in rep_stats:
                for mid, es in s.items():
                    if mid == "models":
                        continue
                    for k in agg:
                        agg[k] += es.get(k, 0)
            out.update(agg)
            out["speculative"] = True
    return out


def run_spec_ab(scale: str = "quick", *, spec: bool, seed: int = 0,
                model: str = "gpt2-debug",
                spec_k: int = 4) -> Dict[str, Any]:
    """Speculative-vs-plain same-engine A/B (ISSUE 16): one in-process
    engine, greedy decoding, same trace — the only difference is the
    drafter proposing ``spec_k`` tokens per step for one batched
    verify. Greedy spec is token-exact by construction (the parity
    tests assert it), so the delta here is pure tokens/s. The ngram
    drafter feeds on self-repetition, so acceptance (reported) is
    model- and trace-dependent; ``spec_accept_floor=0`` keeps the
    fallback out of the measurement."""
    from ray_tpu.serve.llm import LLMEngine
    from ray_tpu.serve.multiplex import SpeculativeLLMEngine

    cfg = _scale_trace(scale, seed)
    # speculative decoding is a DECODE-phase lever: the drafter feeds
    # on the sequence's own repetition, which a handful of decode steps
    # never develops. Long-decode sessions are the workload it exists
    # for — size the trace accordingly (TTFT is untouched either way).
    cfg.max_new_tokens = max(cfg.max_new_tokens, 64)
    kw = dict(max_slots=8, max_len=256, seed=seed,
              block_size=16, prefill_chunk=8)
    if spec:
        engine = SpeculativeLLMEngine(model, spec_k=spec_k,
                                      spec_accept_floor=0.0, **kw)
    else:
        engine = LLMEngine(model, **kw)
    runner = EngineRunner(engine)
    try:
        first = next(iter_trace(cfg))
        list(runner.stream(Request(0.0, 0, first.prompt[:8], 2)))
        list(runner.stream(Request(0.0, 0, list(first.prompt), 2)))
        stats = replay(runner.stream, iter_trace(cfg), time_scale=0.0,
                       max_clients=8)
    finally:
        runner.close()
    out = stats.summary()
    out["mode"] = "speculative" if spec else "plain"
    out["model"] = model
    if spec:
        s = engine.stats
        out["spec_k"] = spec_k
        out["spec_proposed"] = s.get("spec_proposed", 0)
        out["spec_accepted"] = s.get("spec_accepted", 0)
        out["spec_fallbacks"] = s.get("spec_fallbacks", 0)
        out["spec_accept_rate"] = round(
            s.get("spec_accepted", 0) / max(s.get("spec_proposed", 0),
                                            1), 4)
    return out


def run_affinity_ab(scale: str = "quick", *, replicas: int = 3,
                    seed: int = 0,
                    model: str = "llama-debug") -> Dict[str, Any]:
    """Cluster-wide prefix-affinity A/B (ISSUE 16): the same
    shared-prefix trace replayed three ways — ONE replica (the hit-rate
    ceiling: every tenant's prefix lives in the only trie), ``replicas``
    replicas routed by published prefix digests (the handle's affinity
    logic, mirrored in-process off each replica's ``load_state``), and
    ``replicas`` replicas routed at random (the scatter baseline that
    re-prefills every system prompt once per replica it lands on). The
    acceptance bar: affinity's hit rate within 0.05 of the
    single-replica ceiling."""
    import random as _random

    from ray_tpu.serve.kv_cache import prefix_key_digest
    from ray_tpu.serve.llm import LLMDeployment

    kw = dict(max_slots=4, max_len=256, block_size=16, prefill_chunk=8,
              seed=seed)
    rng = _random.Random(seed)

    def one_replay(mode: str) -> Dict[str, Any]:
        n = 1 if mode == "single" else replicas
        pools = [LLMDeployment(model, **kw) for _ in range(n)]
        lock = threading.Lock()
        counts = [0] * n
        digests: Dict[int, Dict[str, int]] = {}
        ts = [0.0]

        def _pick(req: Request) -> int:
            if n == 1:
                return 0
            if mode == "scatter":
                return rng.randrange(n)
            with lock:
                now = time.monotonic()
                if now - ts[0] > 0.05:
                    ts[0] = now
                    for j, p in enumerate(pools):
                        digests[j] = dict(
                            p.load_state().get("prefix_digest", []))
                key = prefix_key_digest(
                    list(req.prompt)[:kw["block_size"]])
                best, best_w = None, -1
                for j in range(n):
                    w = digests.get(j, {}).get(key)
                    if w is not None and int(w) > best_w:
                        best, best_w = j, int(w)
                if best is None:
                    # cold prefix — no replica has published it yet.
                    # Rendezvous-hash the key so every request of the
                    # tenant lands on the SAME replica before its
                    # digest exists; least-counts here scatters the
                    # opening burst across the fleet, planting the
                    # prefix in every trie it touches and paying the
                    # re-prefill once per replica.
                    best = max(range(n),
                               key=lambda j: _hash.sha1(
                                   f"{key}:{j}".encode()).digest())
                counts[best] += 1
                return best

        def stream(req: Request) -> Iterable[int]:
            pick = _pick(req)
            inner = pools[pick](req.prompt, req.max_new)

            def gen() -> Iterator[int]:
                try:
                    yield from inner
                finally:
                    if mode == "affinity":
                        with lock:
                            counts[pick] -= 1

            return gen()

        cfg = _scale_trace(scale, seed)
        try:
            first = next(iter_trace(cfg))
            for p in pools:
                list(p(first.prompt[:8], 2))
                list(p(list(first.prompt), 2))
            # baseline the trie counters after warm-up: the warm pass
            # runs PER REPLICA, so without the subtraction the
            # multi-replica arms are charged n-1 extra sets of warm
            # misses the single-replica ceiling never pays
            base = []
            for p in pools:
                pf = p.engine.kv_state().get("prefix", {})
                base.append((pf.get("hits", 0), pf.get("misses", 0)))
            stats = replay(stream, iter_trace(cfg), time_scale=0.0,
                           max_clients=4)
            hits = lookups = 0
            for p, (bh, bm) in zip(pools, base):
                pf = p.engine.kv_state().get("prefix", {})
                h = pf.get("hits", 0) - bh
                m = pf.get("misses", 0) - bm
                hits += h
                lookups += h + m
        finally:
            for p in pools:
                p.close()
        out = stats.summary()
        out["hit_rate"] = round(hits / max(lookups, 1), 4)
        return out

    arms = {m: one_replay(m) for m in ("single", "affinity", "scatter")}
    return {
        "mode": "affinity_ab",
        "replicas": replicas,
        "single_hit_rate": arms["single"]["hit_rate"],
        "affinity_hit_rate": arms["affinity"]["hit_rate"],
        "scatter_hit_rate": arms["scatter"]["hit_rate"],
        "affinity_within": round(arms["single"]["hit_rate"]
                                 - arms["affinity"]["hit_rate"], 4),
        "affinity_ok": (arms["single"]["hit_rate"]
                        - arms["affinity"]["hit_rate"]) <= 0.05,
        "arms": arms,
    }


#: engine shape for the mixed-workload A/Bs. prefill_chunk is the
#: colocated dilemma knob — one setting must serve prefill throughput
#: AND decode cadence. The colocated arm runs its measured-best
#: compromise (chunk 16: on CPU a chunk step costs ~linearly in chunk
#: width, so narrow chunks barely tax prefill; the swept 16/32/64/128
#: settings go 229/184/113/61 tok/s); the disagg arms dissolve the
#: dilemma per pool — prefill replicas take the wide chunk below,
#: decode replicas shrink it to one block (the compiled step carries
#: the chunk's compute whether or not anything is prefilling).
_MIXED_ENGINE_KW = dict(max_slots=8, max_len=512, block_size=16,
                        prefill_chunk=128)
_MIXED_COLOC_CHUNK = 16


def _mixed_cfg(cfg: TraceConfig) -> TraceConfig:
    """Salt a trace with the disaggregation workload: steady sessions
    emitting tokens while every 4th arrival carries a LONG prompt — the
    pattern where colocated prefill steals decode step-time, and enough
    prefill work on the wire that a dedicated prefill pool pulls its
    weight against the all-mixed baseline."""
    cfg.max_new_tokens = max(cfg.max_new_tokens, 96)
    cfg.long_every = 4
    cfg.long_prompt_tokens = 352
    return cfg


def _mixed_warm_prompts(cfg: TraceConfig, base: List[int],
                        block_size: int) -> List[List[int]]:
    """Warm prompts covering the gather/scatter jit BUCKETS real
    mixed-trace prompts hit (pow2 block counts: short mixed prompts
    land in the 4- and 8-block buckets, long ones at the top) — a
    mid-run compile would stall every in-flight decode and poison
    exactly the tail the A/Bs measure. ONE definition for every
    harness: the bucket set encodes the engine's jit-bucket contract."""
    return [base[:16], base[:4 * block_size], base[:7 * block_size],
            base[:cfg.shared_prefix_tokens + cfg.long_prompt_tokens],
            base[:16]]


def _mixed_max_len(cfg: TraceConfig, block_size: int) -> int:
    """Engine max_len that FITS the mixed trace's worst request
    (prefix + long prompt + decode budget, block-rounded): the quick
    scale fits the default 512, but medium/full prefixes (96/128) push
    the worst case past it — an undersized engine turns every long
    request into a submit-time ValueError and poisons the A/B."""
    need = (cfg.shared_prefix_tokens + cfg.long_prompt_tokens
            + cfg.max_new_tokens)
    need = ((need + block_size - 1) // block_size) * block_size
    return max(_MIXED_ENGINE_KW["max_len"], need)


def _scale_trace(scale: str, seed: int) -> TraceConfig:
    if scale == "quick":          # 2-vCPU CI tier
        return TraceConfig(n_requests=48, n_tenants=3,
                           shared_prefix_tokens=48, max_new_tokens=8,
                           burst_rps=200.0, seed=seed)
    if scale == "medium":
        return TraceConfig(n_requests=2_000, n_tenants=8,
                           shared_prefix_tokens=96, max_new_tokens=32,
                           burst_rps=500.0, seed=seed)
    # full: the millions-of-requests envelope (real hardware only)
    return TraceConfig(n_requests=1_000_000, n_tenants=64,
                       shared_prefix_tokens=128, max_new_tokens=64,
                       burst_rps=2_000.0, seed=seed)


def _boot_cluster(nodes: int):
    """Extra node daemons for --nodes (multi-node replay): returns the
    Cluster handle (caller shuts down) after registering ``nodes`` extra
    daemons beside the head."""
    from ray_tpu.cluster import Cluster

    c = Cluster()
    for _ in range(nodes):
        c.add_node(num_cpus=2)
    return c


def run_serve_replay(scale: str, replicas: int,
                     seed: int = 0, deadline_s: Optional[float] = None,
                     slo: Optional[dict] = None, nodes: int = 0,
                     disagg: bool = False,
                     slo_ttft_s: Optional[float] = None,
                     max_wall_s: Optional[float] = None,
                     mixed: bool = False,
                     max_new: Optional[int] = None,
                     max_clients: int = 32) -> Dict[str, Any]:
    """Deploy a multi-replica application and replay through the real
    routing path (load-aware picker, admission, streaming). ``disagg``
    splits the replicas into a prefill pool and a decode pool and
    routes through the transfer-aware DisaggHandle; ``nodes`` boots
    that many extra node daemons first (multi-node envelope); ``mixed``
    salts the trace with periodic long prompts (the disaggregation A/B
    workload); ``max_new`` overrides the trace's per-request decode
    length (the envelope knob that fits a 1M-request run onto a
    CPU-only box — TTFT, the declared SLO, is decode-length
    independent). The output carries an SLO verdict (p99 TTFT vs
    ``slo_ttft_s``) and a zero-leak KV audit across every replica of
    every pool."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve import LLMDeployment

    cluster = None
    if nodes > 0:
        cluster = _boot_cluster(nodes)
        ray_tpu.init(address=cluster.address,
                     cluster_authkey=cluster.authkey, num_cpus=2)
    else:
        ray_tpu.init(ignore_reinit_error=True)
    if mixed:
        engine_kw = dict(_MIXED_ENGINE_KW, seed=seed)
        mixed_cfg = _mixed_cfg(_scale_trace(scale, seed))
        if max_new is not None:    # the override lands on the trace
            mixed_cfg.max_new_tokens = max_new  # — size for it too
        engine_kw["max_len"] = _mixed_max_len(
            mixed_cfg, engine_kw["block_size"])
        if not disagg:
            engine_kw["prefill_chunk"] = _MIXED_COLOC_CHUNK
    else:
        engine_kw = dict(max_slots=8, max_len=256, seed=seed,
                         block_size=16, prefill_chunk=8)
    try:
        if disagg:
            # same TOTAL KV memory as a colocated deployment of the
            # same replica count, split by role (see run_disagg_ab)
            base_blocks = engine_kw["max_slots"] * (
                engine_kw["max_len"] // engine_kw["block_size"])
            prefill_kw = {"num_blocks": 3 * base_blocks // 4}
            # the decode pool never prefills, but the compiled step
            # carries the prefill_chunk-wide prefill slice either
            # way — shrink it to one block so decode-only steps
            # stop paying the chunk's dead compute
            decode_kw = {"num_blocks": 5 * base_blocks // 4,
                         "prefill_chunk": engine_kw["block_size"]}
            if scale == "full":
                # the 1M envelope: per-request work is dominated by
                # per-STEP and per-MESSAGE overhead, not FLOPs.
                # prefill pool: 64 tenants x 8 prefix blocks = 512
                # blocks of trie + the in-flight working set — an
                # undersized pool thrashes the trie and every prompt
                # re-prefills its 128-token system prompt (measured:
                # hit rate 0.42 -> 0.97, and prefill-step time is THE
                # full-scale bottleneck). decode pool: adoption always
                # claims fresh blocks, so a decode-side trie is pure
                # eviction overhead — disable it. stream_batch turns
                # lagging consumers' N token messages into 1 (TTFT —
                # the declared SLO — is untouched).
                engine_kw["prefill_chunk"] = 32
                engine_kw["stream_batch"] = 8
                prefill_kw["num_blocks"] = 5 * base_blocks
                decode_kw.update(num_blocks=3 * base_blocks,
                                 max_slots=16, prefix_cache=False)
            handle = serve.deploy_disagg(
                "llama-debug", name="llm_replay",
                prefill_replicas=max(1, replicas // 2),
                decode_replicas=max(1, replicas - replicas // 2),
                slo=slo,
                prefill_engine_kwargs=prefill_kw,
                decode_engine_kwargs=decode_kw,
                **engine_kw)

            def stream(req: Request):
                return handle.stream(req.prompt, req.max_new,
                                     deadline_s=deadline_s)

            warm_stream = stream
        else:
            app = serve.deployment(
                LLMDeployment, num_replicas=replicas,
                ray_actor_options={"max_concurrency": 16, "num_cpus": 0},
            ).bind("llama-debug", slo=slo, **engine_kw)
            sh = serve.run(app, name="llm_replay").options(stream=True)

            def stream(req: Request):
                for tok in sh.remote(req.prompt, req.max_new,
                                     deadline_s=deadline_s):
                    # stream_batch replicas deliver token chunks (lists)
                    if isinstance(tok, list):
                        yield from tok
                    else:
                        yield tok

            warm_stream = stream

        trace_cfg = _scale_trace(scale, seed)
        if mixed:
            trace_cfg = _mixed_cfg(trace_cfg)
        if max_new is not None:
            trace_cfg.max_new_tokens = max_new
        first = next(iter_trace(trace_cfg))
        warm_prompts = [first.prompt[:8], list(first.prompt)]
        if mixed:
            warm_prompts += _mixed_warm_prompts(
                trace_cfg, first.prompt * 16, engine_kw["block_size"])
        for wp in warm_prompts:
            for _ in range(replicas * 2):  # warm every replica's compile
                list(warm_stream(Request(0.0, 0, list(wp), 2)))
        stats = replay(stream, iter_trace(trace_cfg), time_scale=0.0,
                       max_wall_s=max_wall_s, max_clients=max_clients,
                       progress_every=10_000 if scale != "quick" else 0)
        out = stats.summary()
        out["replicas"] = replicas
        out["disagg"] = disagg
        out["nodes"] = 1 + nodes
        if engine_kw.get("stream_batch", 1) > 1:
            out["stream_batch"] = engine_kw["stream_batch"]
        if getattr(stats, "truncated", False):
            out["truncated"] = True

        # per-pool KV/prefix state + ZERO-LEAK audit, enumerating the
        # replicas directly (a ROUTED probe can land on one replica
        # twice and double-count its hits)
        if disagg:
            states = handle.kv_states()
        else:
            h = serve.get_deployment_handle("LLMDeployment")
            h._refresh(force=True)
            states = {"colocated": [
                ray_tpu.get(r.handle_request.remote("kv_state", (), {}),
                            timeout=60) for r in h._replicas]}
        hits = lookups = leaks = 0
        for pool in states.values():
            for s in pool:
                hits += s.get("prefix", {}).get("hits", 0)
                lookups += (s.get("prefix", {}).get("hits", 0)
                            + s.get("prefix", {}).get("misses", 0))
                leaks += (s["kv_total"] - s["kv_free"]
                          - s.get("prefix", {}).get("nodes", 0))
        out["prefix_hit_rate"] = round(hits / max(lookups, 1), 4)
        out["kv_leaks"] = leaks
        if slo_ttft_s is not None:
            out["slo"] = {
                "declared_ttft_p99_s": slo_ttft_s,
                "measured_ttft_p99_s": out["ttft_p99_s"],
                "ok": out["ttft_p99_s"] <= slo_ttft_s,
            }
        if disagg:
            handle.shutdown()
        else:
            serve.delete("LLMDeployment")
        return out
    finally:
        try:
            serve.shutdown()
            ray_tpu.shutdown()
        except Exception:
            pass
        if cluster is not None:
            cluster.shutdown()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--scale", default="quick",
                   choices=("quick", "medium", "full"))
    p.add_argument("--serve", action="store_true",
                   help="drive a deployed multi-replica app (default: "
                        "in-process engine A/B)")
    p.add_argument("--replicas", type=int, default=2)
    p.add_argument("--disagg", action="store_true",
                   help="disaggregated prefill/decode pools (with "
                        "--serve: deployed pools; alone: in-process "
                        "two-engine A/B)")
    p.add_argument("--colocated", action="store_true",
                   help="with --disagg (in-process): run the colocated "
                        "baseline arm instead")
    p.add_argument("--multi-model", action="store_true",
                   help="multi-model Zipf trace through multiplexed "
                        "replicas (in-process A/B; ISSUE 16)")
    p.add_argument("--dedicated", action="store_true",
                   help="with --multi-model: run the N dedicated "
                        "single-model deployments baseline arm instead")
    p.add_argument("--n-models", type=int, default=8,
                   help="distinct models in the multi-model trace")
    p.add_argument("--budget-models", type=int, default=2,
                   help="with --multi-model: resident model-slots per "
                        "replica — the fleet weight budget BOTH arms "
                        "spend (0 = unbounded)")
    p.add_argument("--spec", action="store_true",
                   help="speculative-decoding engine A/B (in-process; "
                        "ISSUE 16); with --multi-model: speculative "
                        "multiplexed replicas")
    p.add_argument("--plain", action="store_true",
                   help="with --spec: run the plain-decoding baseline "
                        "arm instead")
    p.add_argument("--affinity", action="store_true",
                   help="prefix-affinity routing A/B over --replicas "
                        "replicas (in-process; ISSUE 16)")
    p.add_argument("--nodes", type=int, default=0,
                   help="extra node daemons to boot (multi-node replay)")
    p.add_argument("--slo-ttft-s", type=float, default=None,
                   help="declared p99 TTFT SLO; the output carries the "
                        "verdict")
    p.add_argument("--max-wall-s", type=float, default=None,
                   help="stop admitting new requests after this budget")
    p.add_argument("--mixed", action="store_true",
                   help="salt the trace with periodic long prompts "
                        "(the disaggregation A/B workload)")
    p.add_argument("--max-new", type=int, default=None,
                   help="override per-request decode length (the "
                        "envelope knob for CPU-only full-scale runs)")
    p.add_argument("--max-clients", type=int, default=32,
                   help="max concurrently in-flight requests")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    if args.serve:
        out = run_serve_replay(args.scale, args.replicas, seed=args.seed,
                               nodes=args.nodes, disagg=args.disagg,
                               slo_ttft_s=args.slo_ttft_s,
                               max_wall_s=args.max_wall_s,
                               mixed=args.mixed, max_new=args.max_new,
                               max_clients=args.max_clients)
    elif args.multi_model:
        out = run_multiplex_ab(args.scale, dedicated=args.dedicated,
                               n_models=args.n_models,
                               replicas=args.replicas,
                               speculative=args.spec,
                               budget_models=args.budget_models,
                               seed=args.seed)
    elif args.spec:
        out = run_spec_ab(args.scale, spec=not args.plain,
                          seed=args.seed)
    elif args.affinity:
        out = run_affinity_ab(args.scale, replicas=args.replicas,
                              seed=args.seed)
    elif args.disagg:
        out = run_disagg_ab(args.scale, disagg=not args.colocated,
                            seed=args.seed)
    else:
        out = run_engine_ab(args.scale, seed=args.seed)
    print(json.dumps({"metric": "serve_replay", **out}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
