"""A serve cell of a model FAMILY, driver side: the ``serve``
kind with everything that depends on the architecture looked up by the
configuration's ``reference`` name, in ``benchmark/families/<family>.py``
(``transformer_config``, ``build_params``, ``TOY_WIDTHS``, ``step_needs``;
``families/README.md``). ``serve.py`` binds the dense decoder's weights and
operation counts; this kind binds none, so the next architecture is data
plus its family's files.

The window, the clocks and the check are the code the dense cells run:
``offer``, ``end_to_end``, ``self_agreement``, ``pick_samples`` and the
tracer come from ``kinds/serve.py``; the replica (``serve_family_replica
.FamilyLLM``, a ``BenchLLM``) holds the chip. This process never imports
jax.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict

from benchmark import check, device, procs, traffic
from benchmark.kinds import serve
from benchmark.kinds.serve import _Tracer, _call, _stream_tokens, log
from benchmark.kinds.serve_family_replica import (STEP_COUNTERS, FamilyLLM,
                                                  load_family, now)

#: ``--rehearse-cpu``: toy engine and lengths cut so that the shared
#: document still runs past the toy ``topk``
REHEARSE = {
    "engine": {"max_slots": 4, "max_len": 512, "block_size": 8,
               "num_blocks": 320, "prefill_chunk": 8},
    "shrink_lengths": 128,
}


# -- driver side --------------------------------------------------------------

def rehearsal_cell(cell: Dict[str, Any]) -> Dict[str, Any]:
    cell = dict(cell)
    family = load_family(cell["config_file"])
    cell["config_file"] = {**cell["config_file"], **family.TOY_WIDTHS}
    cell["config_file"]["engine"] = {**cell["config_file"]["engine"],
                                     **REHEARSE["engine"]}
    by = REHEARSE["shrink_lengths"]
    mix = dict(cell["traffic_file"])
    for k in ("history_tokens", "turn_tokens", "output_tokens"):
        mix[k] = serve._shrink(mix[k], by)
    mix["shared_prefix_tokens"] = mix.get("shared_prefix_tokens", 0) // by
    cell["traffic_file"] = mix
    cell["check"] = {**cell["check"], "ref_len": 512, "new_tokens": 4}
    cell["self_agreement"] = {"prompt_tokens": 100, "new_tokens": 6}
    return cell


def run(ctx) -> Dict[str, Any]:
    """``kinds/serve.py``'s run with ``FamilyLLM`` deployed: the same
    set-up, window, give-up rule, checks and verdicts."""
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
        app = rt_serve.deployment(FamilyLLM, name="BenchLLM",
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
            if i:
                log(f"rate {rate}: {_call(handle, 'bench_forget_prefixes')} "
                    f"blocks of the last rate's prefixes dropped")
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
                    not c.stamps and c.error is None for c in clients):
                time.sleep(0.01)
            e2e = serve.end_to_end(clients, window, args.seconds)
            state["window_over"] = True
            cancelled = _call(handle, "bench_cancel_inflight")
            for th in threads:
                th.join(timeout=60)
            e2e["cancelled_at_end"] = cancelled
            e2e["setup_s"] = setup_s
            e2e["rate_rps"] = rate
            sent = sum(len(c.req.prompt) for c in clients
                       if c.sent is not None)
            hits = (end["stats"]["prefix_hit_tokens"]
                    - start["stats"]["prefix_hit_tokens"])
            e2e["prefix_hit_token_pct"] = 100.0 * hits / max(sent, 1)
            log(f"rate {rate} req/s: {e2e}")
            sweep.append(e2e)
        trace = trace_thread.join_result() if trace_thread else None

        failed = sum(1 for c in clients if c.error is not None or not c.stamps)
        wrong_len = sum(1 for c in clients
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
        f"send lateness p95 {e2e['send_lateness_p95_ms']:.3f} ms; prefix "
        f"hits {e2e['prefix_hit_token_pct']:.2f} % of prompt tokens; engine "
        f"counters over the window "
        f"{ {k: end['stats'].get(k, 0) - start['stats'].get(k, 0) for k in STEP_COUNTERS} }")
    if trace and trace.get("scope_s"):
        log(f"device seconds by scope in the traced window: "
            f"{trace['scope_s']} ({trace['scope_instructions']} instructions "
            f"placed)")
    return {
        "correct": all(v["ok"] for v in verdicts),
        "attempted": len(clients), "failed": failed,
        "values": e2e, "facts": facts, "trace": trace,
        "run": {"cell": cell, "config_file": cf, "window": window,
                "seconds": args.seconds, "clients": clients,
                "replica": collected, "trace": trace, "facts": facts,
                "marks": {"start": start, "end": end}},
    }
