"""A serve cell, driver side: deploy the replica through ``serve.run``, offer
the cell's traffic in an open loop through the handle, and take the
end-to-end numbers on the client's clock. This process never imports jax;
the replica (``serve_replica.BenchLLM``) holds the chip.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, List

from benchmark import check, device, procs, stats, traffic, weights
from benchmark.kinds.serve_replica import BenchLLM, now, request_key

#: toy sizes for ``--rehearse-cpu``: control flow only, never a result line
REHEARSE = {
    "config_file": weights.TOY_WIDTHS,
    "engine": {"max_slots": 4, "max_len": 256, "block_size": 8,
               "num_blocks": 160, "prefill_chunk": 8},
    "shrink_lengths": 16,
}


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


class Client:
    """One request as its sender sees it."""

    def __init__(self, req: traffic.Request):
        self.req = req
        self.key = request_key(req.prompt)
        self.sent = None
        self.stamps: List[float] = []
        self.error = None
        self.finished = False

    def run(self, handle, state) -> None:
        self.sent = now()
        try:
            for _ in handle.options(stream=True).remote(
                    self.req.prompt, self.req.max_new):
                self.stamps.append(now())
            self.finished = True
        except Exception as e:  # a thread: the run counts and reports it
            if not state["window_over"]:
                self.error = repr(e)


def _call(handle, method: str, *args, timeout_s: float = 1200.0):
    return handle.options(method_name=method).remote(*args).result(
        timeout_s=timeout_s)


def _stream_tokens(handle, prompt, max_new) -> List[int]:
    return [int(t) for t in
            handle.options(stream=True).remote(prompt, max_new)]


def _shrink(spec: Dict[str, Any], by: int) -> Dict[str, Any]:
    return {k: (max(1, int(v) // by) if k in ("min", "max", "median", "value")
                else v) for k, v in spec.items()}


def rehearsal_cell(cell: Dict[str, Any]) -> Dict[str, Any]:
    cell = dict(cell)
    cell["config_file"] = {**cell["config_file"], **REHEARSE["config_file"]}
    cell["config_file"]["engine"] = {**cell["config_file"]["engine"],
                                     **REHEARSE["engine"]}
    by = REHEARSE["shrink_lengths"]
    mix = dict(cell["traffic_file"])
    for k in ("history_tokens", "turn_tokens", "output_tokens"):
        mix[k] = _shrink(mix[k], by)
    mix["shared_prefix_tokens"] = mix.get("shared_prefix_tokens", 0) // by
    cell["traffic_file"] = mix
    cell["check"] = {**cell["check"], "ref_len": 256, "new_tokens": 4}
    cell["self_agreement"] = {"prompt_tokens": 40, "new_tokens": 6}
    return cell


def offer(handle, requests: List[traffic.Request], seconds: float,
          state: Dict[str, Any]):
    """The open loop: each request is sent when it is due, whatever became
    of the ones before it. Returns the clients and the window."""
    clients = [Client(r) for r in requests]
    threads = []
    t0 = now()
    for c in clients:
        wait = t0 + c.req.due_s - now()
        if wait > 0:
            time.sleep(wait)
        th = threading.Thread(target=c.run, args=(handle, state), daemon=True)
        th.start()
        threads.append(th)
    wait = t0 + seconds - now()
    if wait > 0:
        time.sleep(wait)
    return clients, threads, (t0, t0 + seconds)


def end_to_end(clients: List[Client], window, seconds: float) -> Dict[str, Any]:
    """The serve cells' end-to-end numbers, all on the client's clock. TTFT
    runs from the instant a request was DUE; a request without a first token
    counts as missing and is timed up to when the run gave up on it."""
    t0, t1 = window
    ttft, gaps, tokens, missing = [], [], 0, 0
    for c in clients:
        due = t0 + c.req.due_s
        if c.stamps:
            ttft.append((c.stamps[0] - due) * 1e3)
        else:
            missing += 1
            ttft.append((now() - due) * 1e3)
        tokens += sum(t0 <= s <= t1 for s in c.stamps)
        gaps += [(b - a) * 1e3 for a, b in zip(c.stamps, c.stamps[1:])
                 if b <= t1]
    late = [(c.sent - (t0 + c.req.due_s)) * 1e3 for c in clients
            if c.sent is not None]
    return {"ttft_p90_ms": stats.percentile(ttft, 90),
            "tpot_p95_ms": stats.percentile(gaps, 95) if gaps else None,
            "serve_tok_s": tokens / seconds,
            "ttft_p50_ms": stats.percentile(ttft, 50),
            "tpot_p50_ms": stats.percentile(gaps, 50) if gaps else None,
            "n_requests": len(clients), "n_gaps": len(gaps),
            "missing_first_token": missing,
            "send_lateness_p95_ms": stats.percentile(late, 95)}


def self_agreement(handle, cell, seed: int, vocab: int) -> Dict[str, Any]:
    """One prompt served twice through the handle, cold and then with its
    prefix cached: the engine must agree with itself token for token."""
    spec = cell["self_agreement"]
    rng = traffic.rng_for(seed, 2)
    prompt = rng.integers(0, vocab, spec["prompt_tokens"]).tolist()
    before = _call(handle, "bench_mark", "agree_0")["stats"]
    cold = _stream_tokens(handle, prompt, spec["new_tokens"])
    mid = _call(handle, "bench_mark", "agree_1")["stats"]
    warm = _stream_tokens(handle, prompt, spec["new_tokens"])
    after = _call(handle, "bench_mark", "agree_2")["stats"]
    return {"equal": cold == warm, "tokens": len(cold),
            "cold_hit_tokens": mid["prefix_hit_tokens"]
            - before["prefix_hit_tokens"],
            "warm_hit_tokens": after["prefix_hit_tokens"]
            - mid["prefix_hit_tokens"]}


def pick_samples(requests: List[traffic.Request], cell, seed: int):
    """The seeded sample of this run's own requests that the logits check
    serves again: the shortest, the longest and seeded others."""
    n, new = cell["check"]["requests"], cell["check"]["new_tokens"]
    by_len = sorted(requests, key=lambda r: len(r.prompt))
    rng = traffic.rng_for(seed, 3)
    middle = [by_len[i] for i in rng.permutation(len(by_len) - 2)[: n - 2] + 1]
    return [(r.prompt, new) for r in [by_len[0], by_len[-1]] + middle]


def run(ctx) -> Dict[str, Any]:
    args, cell = ctx.args, ctx.cell
    rehearse = args.rehearse_cpu
    if rehearse:
        cell = rehearsal_cell(cell)
    cf = cell["config_file"]
    vocab = cf["vocab_size"]

    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init()
    state = {"window_over": False}
    try:
        opts = {"max_concurrency": 512, "num_cpus": 0}
        if not rehearse:
            have = ray_tpu.cluster_resources().get("TPU", 0)
            if have < cell["chips"]:
                raise SystemExit(
                    f"this host has {have} TPU chip(s), the cell needs "
                    f"{cell['chips']}: no chip, no run")
            opts["resources"] = {"TPU": float(cell["chips"])}
        replica_cell = {k: cell[k] for k in
                        ("name", "config_file", "check", "step_program")}
        app = serve.deployment(BenchLLM, name="BenchLLM",
                               ray_actor_options=opts).bind(
            replica_cell, args.seed)
        handle = serve.run(app, name="bench")
        facts = _call(handle, "bench_facts")
        log(f"replica up after {now() - ctx.t_start:.1f}s: {facts['kind']} x"
            f"{facts['count']} ({facts['platform']}), weights "
            f"{facts['param_bytes'] / 1e9:.2f} GB {facts['param_dtypes']}, "
            f"KV pool {facts['kv_pool_bytes'] / 1e9:.2f} GB, set-up "
            f"{facts['setup']}, compile cache {facts['compile_cache']}")
        on = (facts["platform"], facts["count"])
        if on != ("tpu", cell["chips"]) and not (rehearse and on[0] == "cpu"):
            raise SystemExit(f"the replica runs on {on[0]} x{on[1]}, the "
                             f"cell needs tpu x{cell['chips']}")

        mix = cell["traffic_file"]
        rates = [float(r) for r in args.sweep.split(",")] if args.sweep \
            else [float(cell["rate_rps"])]
        sweep = []
        for i, rate in enumerate(rates):
            # a sweep gives every rate tokens of its own: the schedule is
            # the same at every rate, and a prompt sent twice would be
            # served from the prefix cache the second time
            token_seed = args.seed + i
            # set-up the traffic needs: each tenant's prefix served once, so
            # the trie holds them before the first request of the window
            warm = [threading.Thread(
                target=_stream_tokens, args=(handle, p, 1), daemon=True)
                for p in traffic.warm_prompts(mix, token_seed, vocab)]
            for th in warm:
                th.start()
            for th in warm:
                th.join(timeout=600)
            requests = traffic.generate(mix, rate, args.seconds, token_seed,
                                        vocab)
            state["window_over"] = False
            start = _call(handle, "bench_mark", "window_start")
            setup_s = now() - ctx.t_start
            trace_thread = None
            if args.trace and not args.sweep:
                trace_thread = _Tracer(handle, cell, ctx, args.seconds)
                trace_thread.start()
            clients, threads, window = offer(handle, requests, args.seconds,
                                             state)
            end = _call(handle, "bench_mark", "window_end")
            # every request due in the window gets its first token or the
            # run gives up on it; the rest of each answer is not waited for
            give_up = now() + float(cell.get("first_token_grace_s", 30.0))
            while now() < give_up and any(
                    not c.stamps and c.error is None for c in clients):
                time.sleep(0.01)
            e2e = end_to_end(clients, window, args.seconds)
            state["window_over"] = True
            cancelled = _call(handle, "bench_cancel_inflight")
            for th in threads:
                th.join(timeout=60)
            e2e["cancelled_at_end"] = cancelled
            e2e["setup_s"] = setup_s
            e2e["rate_rps"] = rate
            log(f"rate {rate} req/s: {e2e}")
            sweep.append(e2e)
        trace = trace_thread.join_result() if trace_thread else None

        failed = sum(1 for c in clients if c.error is not None or not c.stamps)
        wrong_len = sum(1 for c in clients
                        if c.finished and len(c.stamps) != c.req.max_new)
        compiled = device.compiled_between(start["compiles"],
                                           end["compiles"])
        agree = self_agreement(handle, cell, args.seed, vocab)
        logits = _call(handle, "bench_check",
                       pick_samples(requests, cell, args.seed))
        collected = _call(handle, "bench_collect")
        kv = collected["kv_state"]
        facts = _call(handle, "bench_facts")
    finally:
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()
    if not procs.wait_gone(facts["pid"]):
        raise SystemExit(f"replica process {facts['pid']} did not end")

    numbers = {
        "logit_rel_err_pooled": logits["logit_rel_err_pooled"],
        "tie_gap_max": logits["tie_gap_max"],
        "short_answers": logits["short_answers"] + wrong_len,
        "self_disagreement": 0 if agree["equal"] else 1,
        "self_agreement_missed_prefix": 0 if agree["warm_hit_tokens"] > 0 else 1,
        "kv_blocks_leaked": kv["kv_total"] - kv["kv_free"]
        - kv["prefix"]["nodes"],
        "compiles_in_window": sum(compiled.values()),
        "failed_requests": failed,
    }
    verdicts = check.verdict(numbers, cell["limits"])
    for v in verdicts:
        log(f"check {v['name']}: {v['value']} (limit {v['limit']}) "
            f"{'ok' if v['ok'] else 'NOT OK'}")
    log(f"logits check detail: {logits}; self-agreement {agree}; "
        f"compiled in window {compiled}")
    e2e = sweep[-1]
    log(f"samples: {e2e['n_requests']} requests, {e2e['n_gaps']} token gaps; "
        f"send lateness p95 {e2e['send_lateness_p95_ms']:.3f} ms")
    return {
        "correct": all(v["ok"] for v in verdicts),
        "attempted": len(clients), "failed": failed,
        "values": e2e, "facts": facts, "trace": trace,
        "run": {"cell": cell, "config_file": cf, "window": window,
                "seconds": args.seconds, "clients": clients,
                "replica": collected, "trace": trace, "facts": facts,
                "marks": {"start": start, "end": end}},
    }


class _Tracer(threading.Thread):
    """Trace a few seconds in the middle of the window, from the replica's
    own process (only the process that holds the chip can trace it)."""

    def __init__(self, handle, cell, ctx, seconds: float):
        super().__init__(daemon=True)
        self.handle, self.ctx = handle, ctx
        self.length = min(float(cell.get("trace_seconds", 6.0)), seconds / 2)
        self.delay = (seconds - self.length) / 2
        self.result = None
        self.error = None

    def run(self) -> None:
        try:
            time.sleep(self.delay)
            _call(self.handle, "bench_trace_start",
                  os.path.join(self.ctx.out_dir, "trace"))
            time.sleep(self.length)
            self.result = _call(self.handle, "bench_trace_stop")
        except Exception as e:  # reported by join_result
            self.error = e

    def join_result(self):
        self.join(timeout=300)
        if self.error is not None:
            raise RuntimeError(f"tracing failed: {self.error!r}")
        return self.result
