"""The readings the ``logit_rel_err_pooled`` limit of a cell of the windowed
MoE family is set from, THROUGH the harness's own comparison
(``check.logits_against_reference`` then ``check.verdict`` against the
cell's limits), on the cell's own sample of requests, in one process that
holds the chip (``tools/calibrate_parallel_hybrid.py`` is the pattern; a
``benchmark`` PR folds the family tools into one):

    python3 -m benchmark.tools.calibrate_windowed_moe --workload <cell>
        --seed <n> [--controls int8,fp8_pool,no_gate,...] [--shares]

Per seed: the cell's replica class (``StateFamilyLLM``: the family's seeded
weights, the configuration's engine, its warm-up), the sample the cell's own
check picks from the window's requests (``serve.pick_samples``: the shortest,
the longest, which is of the long class and lies past the window, and seeded
others), served together by the engine with the logits of every sampled
position captured, as ``bench_check`` does. Printed, one JSON line a reading,
each with the verdict on ``logit_rel_err_pooled`` and ``tie_gap_max`` beside
it: ``sound`` (the engine against the plain reference), and each control in
the engine's place. ``int8`` is the contract's control, by the harness's own
``control=True``. ``fp8_pool`` is the ENGINE itself with both KV pools
rounded to float8 (e4m3) after every step (``BenchEngine.kv_round`` with
``tools/calibrate.py``'s rounder): a lower precision of what the two pools
hold. ``window_table_shifted`` is the ENGINE itself with every row's window
table handed to the step one block off (entry ``j`` holds entry ``j + 1``'s
block): the table and the numbering the program reads it in (``first_block``)
disagree by a block, what an off-by-one in ``LLMEngine._move_window`` would
give, at the timed sizes. ``sound`` is also printed a sample
(``sound_by_sample``: the sample's own pooled error, then its positions'
median, 90th and 95th percentile and largest, and how many of them read over
0.03: the tail that a pooled reading stands on), so that a reading carried by
the one long request shows as such. Every other name of the
reference's ``CONTROLS`` (mathematics left out or moved) goes the same way
as in the pattern: the reference's ``weights=<name>`` pass over the engine's
own token sequences, handed to ``logits_against_reference`` where the
engine's logits go. ``--shares`` adds, layer by layer on the longest sample,
the root mean square norms of the residual and of what the attention branch
and the MLP or expert branch add to it over the decoded positions: what the
seeded weights' scales were chosen by. One seed a process: two models do not
fit the chip.
"""

from __future__ import annotations

import argparse
import json

import numpy as np

from benchmark.tools.calibrate_parallel_hybrid import (LOGIT_LIMITS,
                                                       in_the_engines_place,
                                                       log)


def branch_norms(ref, params, cf, padded, rows):
    """Per layer (residual in, attention branch, MLP branch) over ``rows``:
    the layer run whole by the reference, and again with the MLP's post-norm
    gain at zero (its branch then adds nothing), so the two branches come
    apart without a second copy of the layer's mathematics."""
    import jax
    import jax.numpy as jnp

    hp = ref.hyper(cf, "as_given")
    norm = lambda a: float(jnp.sqrt(jnp.mean(jnp.sum(a[rows] ** 2, -1))))
    scale = float(cf["hidden_size"]) ** 0.5
    x = ref._embed(params["embed"], jnp.asarray(padded, jnp.int32), scale)
    out = []
    for seg, index, window, rotate in ref.layer_plan(cf):
        layers = params["layers"][seg]
        muted = {**layers, "post_mlp_norm":
                 jnp.zeros_like(layers["post_mlp_norm"])}
        run = lambda tree: ref._layer_at(jnp.array(x), tree, index, hp,
                                         seg == "dense", window, rotate)
        after_attn, after = run(muted), run(layers)
        out.append([norm(x), norm(after_attn - x), norm(after - after_attn)])
        x = after
        jax.block_until_ready(x)
    return out


def shifted_window_tables(engine):
    """``engine._step_fn`` with the window table's columns one block off."""
    inner, at = engine._step_fn, engine._full_width

    def step(params, cache, tokens, tables, *rest):
        tables = np.asarray(tables)
        return inner(params, cache, tokens, np.concatenate(
            [tables[:, :at], tables[:, at + 1:], tables[:, -1:]], axis=1),
            *rest)

    return step


def position_tail(check, params, sample, pairs, cf, ref_len):
    """(prompt tokens, pooled error, the positions' median, p90, p95 and
    largest, positions over 0.03) of one sample."""
    prompt, _ = sample
    toks = [t for t, _ in pairs]
    out = check.compare_logits(
        np.stack([l for _, l in pairs]),
        check.reference_logits(params, prompt, toks, cf, ref_len), toks)
    rel = out["rel_err"]
    return (len(prompt), round(float(np.sqrt(out["err_sq"] / out["ref_sq"])),
                               5),
            *(round(float(np.percentile(rel, q)), 5) for q in (50, 90, 95)),
            round(float(rel.max()), 5), int((rel > 0.03).sum()))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--controls", default="int8")
    ap.add_argument("--shares", action="store_true")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from benchmark import check, manifest, traffic
    from benchmark.kinds import serve, serve_state_family
    from benchmark.kinds.serve_state_family_replica import StateFamilyLLM
    from benchmark.tools.calibrate import _kv_rounder

    man = manifest.load_manifest()
    cell = manifest.load_cell(man, args.workload)
    if args.rehearse_cpu:
        cell = serve_state_family.rehearsal_cell(cell)
    cf, mix = cell["config_file"], cell["traffic_file"]
    ref = manifest.load_module(manifest.reference_path(cf["reference"]))
    ref_len = int(cell["check"]["ref_len"])
    limits = {k: cell["limits"][k] for k in LOGIT_LIMITS}
    controls = [c for c in args.controls.split(",") if c]
    unknown = set(controls) - set(ref.CONTROLS) - {"fp8_pool",
                                                   "window_table_shifted"}
    if unknown:
        ap.error(f"the reference has no control {sorted(unknown)}")

    llm = StateFamilyLLM(cell, args.seed)
    try:
        facts = llm.bench_facts()
        log(f"seed {args.seed}: {facts['kind']} x{facts['count']} "
            f"({facts['platform']}); set-up {facts['setup']}; attention "
            f"{llm.engine.stats['attn_impl']}; weights "
            f"{facts['param_bytes']} B, pools {facts['kv_pool_bytes']} B")
        requests = traffic.generate(mix, cell["rate_rps"], man["run_seconds"],
                                    args.seed, cf["vocab_size"])
        samples = serve.pick_samples(requests, cell, args.seed)
        rows = llm.serve_captured(samples)

        def record(name, numbers):
            verdicts = check.verdict(numbers, limits)
            print(json.dumps({name: numbers, "seed": args.seed,
                              "prompts": [len(p) for p, _ in samples],
                              "verdict": verdicts,
                              "correct": all(v["ok"] for v in verdicts)}),
                  flush=True)

        record("sound", check.logits_against_reference(
            llm.params, samples, rows, cf, ref_len))
        log("sound_by_sample", [
            position_tail(check, llm.params, s, r, cf, ref_len)
            for s, r in zip(samples, rows)])
        for name in controls:
            if name == "int8":
                numbers = check.logits_against_reference(
                    llm.params, samples, rows, cf, ref_len, control=True)
            elif name == "fp8_pool":
                llm.engine.kv_round = _kv_rounder()
                try:
                    numbers = check.logits_against_reference(
                        llm.params, samples, llm.serve_captured(samples), cf,
                        ref_len)
                finally:
                    llm.engine.kv_round = None
            elif name == "window_table_shifted":
                step = llm.engine._step_fn
                llm.engine._step_fn = shifted_window_tables(llm.engine)
                try:
                    numbers = check.logits_against_reference(
                        llm.params, samples, llm.serve_captured(samples), cf,
                        ref_len)
                finally:
                    llm.engine._step_fn = step
            else:
                numbers = check.logits_against_reference(
                    llm.params, samples,
                    in_the_engines_place(llm.params, samples, rows, cf,
                                         ref_len, name), cf, ref_len)
            record(name, numbers)
        if args.shares:
            prompt, pairs = max(
                ((p, r) for (p, _), r in zip(samples, rows)),
                key=lambda pr: len(pr[0]))
            seq = list(prompt) + [t for t, _ in pairs][:-1]
            padded = np.zeros(ref_len, np.int32)
            padded[:len(seq)] = seq
            decoded = np.arange(len(prompt), len(seq))
            for i, (h, a, m) in enumerate(branch_norms(
                    ref, llm.params, cf, padded, decoded)):
                log(f"layer {i}: |residual in| {h:.3f}, |attention branch| "
                    f"{a:.3f}, |MLP or expert branch| {m:.3f}")
        log(f"memory: {jax.devices()[0].memory_stats()}")
    finally:
        llm.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
