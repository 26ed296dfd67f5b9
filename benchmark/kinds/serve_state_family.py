"""A serve cell of a model family whose program has scopes, kernels and
per-step counters of its own, and whose answers last as long as the window,
driver side.

Beyond the four names of ``families/README.md`` (``transformer_config``,
``build_params``, ``TOY_WIDTHS``, ``step_needs``) a family file gives this
kind three more: ``SCOPES`` (the program's ``jax.named_scope``s whose device
time a traced run reports under ``trace["scope_s"]``), ``KERNELS``
(operations that reach the compiled program without their scope, by
instruction-name prefix -> scope) and ``STEP_COUNTERS`` (engine counters kept
per step under ``replica["step_counters"]``): the names
``family_rooflines.scope_share`` reads, so a roofline reader stays three
lines. ``kinds/serve_family_replica.py`` holds those three lists itself, for
its one family; here ``StateFamilyLLM`` (``serve_state_family_replica.py``)
is deployed in ``FamilyLLM``'s place.

**The window opens on a running engine.** The dense cells' requests live a
few seconds, so a window that starts with an empty engine is in its steady
state after a tenth of its length. Here a request lives as long as the
window (a thousand tokens at twenty a second): from an empty start the slots
only fill, no context passes half of ``max_len``, three requests in four are
cut off by the window's end, and ``serve_tok_s`` is ``1 / gap`` under another
name: it follows the host's speed and every stop of the machine one for one
(PERF.md sections 6 and 7; the driver refused that form for its spread). So
the open loop starts ``pre_roll.seconds`` BEFORE the window: a second draw
of the same mix at the cell's rate (``pre_roll.traffic_seed``, chosen by the
mix's own rule over that span) is offered through the handle at its instants,
as set-up; the window then opens, without a pause, on the slots, pools and
states those requests hold, and offers the cell's own schedule
(``traffic.generate(mix, rate, seconds, ...)``, as every cell). What is
counted: ``ttft`` over the requests DUE in the window; tokens that reached
clients inside the window and gaps that end inside it from every request,
carried in or new (PERF.md section 2's definitions). The rate is a share of
the knee found under the same pre-roll (``--sweep``).

``run`` is ``kinds/serve_family.py::run`` with the pre-roll between the
traffic's own set-up and the window and the counting above. That flow is
written again here (deploy, window, give-up rule, checks, verdicts: the
order and every call are the family kind's) because ``serve_family.run`` is
one function with no seam between set-up and window; ``offer``,
``end_to_end``, ``self_agreement``, ``pick_samples``, the tracer, the
rehearsal's cell and the replica's probes are imported. The ``benchmark``
issue that merges the kinds makes the pre-roll a step of the one flow. This
process never imports jax.

``StallWatch`` names, as commentary, the instants at which the whole machine
stood still inside the window (a thread that sleeps a few milliseconds and
notes when it overslept by tens; the replica and a third process stand still
at the same instants, PERF.md section 7): it lets a reader tell a run the
machine slowed from one the program did.
"""

from __future__ import annotations

import copy
import threading
import time
from typing import Any, Dict, List

from benchmark import check, device, procs, traffic
from benchmark.kinds import serve, serve_family
from benchmark.kinds.serve import Client, _Tracer, _call, _stream_tokens, log
from benchmark.kinds.serve_family_replica import load_family, now
from benchmark.kinds.serve_state_family_replica import StateFamilyLLM

#: ``--rehearse-cpu``: the pre-roll cut like the lengths, control flow only
REHEARSE_PRE_ROLL_S = 2.0


class StallWatch(threading.Thread):
    """Oversleeps of this process, on the window's clock: (start, seconds)."""

    NAP_S, OVER_S = 0.005, 0.05

    def __init__(self):
        super().__init__(daemon=True, name="bench-stall-watch")
        self.stalls: List[tuple] = []
        self.over = threading.Event()

    def run(self) -> None:
        last = time.monotonic()
        while not self.over.is_set():
            time.sleep(self.NAP_S)
            t = time.monotonic()
            if t - last - self.NAP_S > self.OVER_S:
                self.stalls.append((last, t - last))
            last = t

    def inside(self, window) -> List[tuple]:
        return [(t, d) for t, d in self.stalls if window[0] <= t <= window[1]]


def pre_roll_requests(cell, rate: float, token_seed: int,
                      vocab: int) -> List[traffic.Request]:
    """The requests due in the ``pre_roll.seconds`` before the window: a
    draw of the cell's mix of its own, with tokens of its own, at offsets
    below zero."""
    spec = cell["pre_roll"]
    mix = {**cell["traffic_file"], "traffic_seed": spec["traffic_seed"]}
    reqs = traffic.generate(mix, rate, float(spec["seconds"]),
                            token_seed + 1_000_003, vocab)
    for r in reqs:
        r.due_s -= float(spec["seconds"])
    return reqs


def offer_pre_roll(handle, requests: List[traffic.Request], seconds: float,
                   state):
    """``serve.offer`` for the ``seconds`` before the window: each request
    sent when it is due (offsets below zero), none waited for; returns when
    the window is to open."""
    clients, threads = [Client(r) for r in requests], []
    t0 = now() + seconds
    for c in clients:
        wait = t0 + c.req.due_s - now()
        if wait > 0:
            time.sleep(wait)
        th = threading.Thread(target=c.run, args=(handle, state), daemon=True)
        th.start()
        threads.append(th)
    wait = t0 - now()
    if wait > 0:
        time.sleep(wait)
    return clients, threads


def carried_in(clients: List[Client], t0: float) -> List[Client]:
    """The pre-roll's clients as the window sees them: their last stamp
    before the window (the start of the first gap that ends inside it) and
    every stamp after."""
    out = []
    for c in clients:
        before = [s for s in c.stamps if s < t0]
        view = copy.copy(c)
        view.stamps = before[-1:] + [s for s in c.stamps if s >= t0]
        out.append(view)
    return out


def end_to_end(clients, carried, window, seconds: float) -> Dict[str, Any]:
    """``serve.end_to_end``: first tokens of the requests due in the window,
    tokens and gaps of all that streamed inside it."""
    e2e = serve.end_to_end(clients, window, seconds)
    every = serve.end_to_end(clients + carried_in(carried, window[0]),
                             window, seconds)
    for k in ("tpot_p95_ms", "tpot_p50_ms", "serve_tok_s", "n_gaps"):
        e2e[k] = every[k]
    e2e["n_carried_in"] = sum(
        1 for c in carried if any(s >= window[0] for s in c.stamps))
    return e2e


def rehearsal_cell(cell: Dict[str, Any]) -> Dict[str, Any]:
    cell = serve_family.rehearsal_cell(cell)
    cell["pre_roll"] = {**cell["pre_roll"], "seconds": REHEARSE_PRE_ROLL_S}
    return cell


def run(ctx) -> Dict[str, Any]:
    watch = StallWatch()
    watch.start()
    try:
        out = _run(ctx)
    finally:
        watch.over.set()
    t0 = out["run"]["window"][0]
    stalls = watch.inside(out["run"]["window"])
    out["run"]["machine_stalls"] = stalls
    log(f"the machine stood still {len(stalls)} times inside the window, "
        f"{sum(d for _, d in stalls) * 1e3:.0f} ms in all: "
        f"{[(round(t - t0, 2), round(d * 1e3)) for t, d in stalls]} "
        f"(s into the window, ms)")
    return out


def _run(ctx) -> Dict[str, Any]:
    args, cell = ctx.args, ctx.cell
    rehearse = args.rehearse_cpu
    if rehearse:
        cell = rehearsal_cell(cell)
    cf = cell["config_file"]
    vocab = cf["vocab_size"]

    import ray_tpu
    from ray_tpu import serve as rt_serve

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
        app = rt_serve.deployment(StateFamilyLLM, name="BenchLLM",
                                  ray_actor_options=opts).bind(
            replica_cell, args.seed)
        handle = rt_serve.run(app, name="bench")
        facts = _call(handle, "bench_facts")
        log(f"replica up after {now() - ctx.t_start:.1f}s: {facts['kind']} x"
            f"{facts['count']} ({facts['platform']}), weights "
            f"{facts['param_bytes'] / 1e9:.2f} GB {facts['param_dtypes']}, "
            f"cache pools {facts['kv_pool_bytes'] / 1e9:.2f} GB, set-up "
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
            token_seed = args.seed + i    # see kinds/serve.py
            warm = [threading.Thread(
                target=_stream_tokens, args=(handle, p, 1), daemon=True)
                for p in traffic.warm_prompts(mix, token_seed, vocab)]
            for th in warm:
                th.start()
            for th in warm:
                th.join(timeout=1200)
            requests = traffic.generate(mix, rate, args.seconds, token_seed,
                                        vocab)
            state["window_over"] = False
            carried, carried_threads = offer_pre_roll(
                handle, pre_roll_requests(cell, rate, token_seed, vocab),
                float(cell["pre_roll"]["seconds"]), state)
            start = _call(handle, "bench_mark", "window_start")
            setup_s = now() - ctx.t_start
            trace_thread = None
            if args.trace and not args.sweep:
                trace_thread = _Tracer(handle, cell, ctx, args.seconds)
                trace_thread.start()
            clients, threads, window = serve.offer(handle, requests,
                                                   args.seconds, state)
            end = _call(handle, "bench_mark", "window_end")
            give_up = now() + float(cell.get("first_token_grace_s", 30.0))
            while now() < give_up and any(
                    not c.stamps and c.error is None
                    for c in clients + carried):
                time.sleep(0.01)
            e2e = end_to_end(clients, carried, window, args.seconds)
            state["window_over"] = True
            cancelled = _call(handle, "bench_cancel_inflight")
            for th in threads + carried_threads:
                th.join(timeout=60)
            e2e["cancelled_at_end"] = cancelled
            e2e["setup_s"] = setup_s
            e2e["rate_rps"] = rate
            sent = sum(len(c.req.prompt) for c in clients
                       if c.sent is not None)
            hits = (end["stats"]["prefix_hit_tokens"]
                    - start["stats"]["prefix_hit_tokens"])
            e2e["prefix_hit_token_pct"] = 100.0 * hits / max(sent, 1)
            log(f"rate {rate} req/s: {e2e}; pre-roll of "
                f"{cell['pre_roll']['seconds']} s offered {len(carried)} "
                f"requests, {e2e['n_carried_in']} of them streamed inside "
                f"the window")
            sweep.append(e2e)
        trace = trace_thread.join_result() if trace_thread else None

        everyone = clients + carried
        failed = sum(1 for c in everyone
                     if c.error is not None or not c.stamps)
        wrong_len = sum(1 for c in everyone
                        if c.finished and len(c.stamps) != c.req.max_new)
        compiled = device.compiled_between(start["compiles"],
                                           end["compiles"])
        agree = serve.self_agreement(handle, cell, args.seed, vocab)
        logits = _call(handle, "bench_check",
                       serve.pick_samples(requests, cell, args.seed))
        collected = _call(handle, "bench_collect")
        kv = collected["kv_state"]
        facts = _call(handle, "bench_facts")
    finally:
        try:
            rt_serve.shutdown()
        finally:
            ray_tpu.shutdown()
    if not procs.wait_gone(facts["pid"]):
        raise SystemExit(f"replica process {facts['pid']} did not end")

    numbers = {
        "logit_rel_err_pooled": logits["logit_rel_err_pooled"],
        "tie_gap_max": logits["tie_gap_max"],
        "short_answers": logits["short_answers"] + wrong_len,
        "self_disagreement": 0 if agree["equal"] else 1,
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
    counters = load_family(cf).STEP_COUNTERS
    log(f"samples: {e2e['n_requests']} requests due in the window, "
        f"{e2e['n_carried_in']} carried in, {e2e['n_gaps']} token gaps; "
        f"send lateness p95 {e2e['send_lateness_p95_ms']:.3f} ms; prefix "
        f"hits {e2e['prefix_hit_token_pct']:.2f} % of prompt tokens; engine "
        f"counters over the window "
        f"{ {k: end['stats'].get(k, 0) - start['stats'].get(k, 0) for k in counters} }")
    if trace and trace.get("scope_s"):
        log(f"device seconds by scope in the traced window: "
            f"{trace['scope_s']} ({trace['scope_instructions']} instructions "
            f"placed)")
    return {
        "correct": all(v["ok"] for v in verdicts),
        "attempted": len(everyone), "failed": failed,
        "values": e2e, "facts": facts, "trace": trace,
        "run": {"cell": cell, "config_file": cf, "window": window,
                "seconds": args.seconds, "clients": clients,
                "carried": carried, "replica": collected, "trace": trace,
                "facts": facts, "marks": {"start": start, "end": end}},
    }
