"""The readings the limits of a cell of the linear hybrid family are set
from, THROUGH the harness's own comparison (``check.logits_against_reference``
then ``check.verdict`` against the cell's limits), on the cell's own sample
of requests, in one process that holds the chip (``tools/calibrate.py`` does
it for the dense cells; its ``BenchLLM`` makes a dense decoder's weights, so
this family needs a tool of its own until a ``benchmark`` PR folds them into
one):

    python3 -m benchmark.tools.calibrate_linear_hybrid --workload <cell>
        --seed <n> [--controls int8,bf16_state,pool_bf16,...] [--shares]

Per seed: the cell's replica class (``SnapshotFamilyLLM``: the family's seeded
weights, the configuration's engine, its warm-up), the sample the cell's own
check picks from the window's requests (``serve.pick_samples``), served
together by the engine with the logits of every sampled position captured,
as ``bench_check`` does: first COLD (``sound_cold``: an empty trie, every
prompt prefilled from a zero state, a snapshot taken at its last block
boundary), then again (``sound_warm``: every prompt lands on its own
snapshot, restores it and prefills what lies past it), both against the
plain reference, and what the two serves' logits differ by
(``snapshot_logit_drift``: nothing, on sound code). Printed, one JSON line a reading, each with the verdict on
``logit_rel_err_pooled`` and ``tie_gap_max`` beside it and how many of the
samples restored a snapshot.

Controls, each in the engine's place: a name of the reference's ``VARIANTS``
is the reference's ``weights=<name>`` pass over the engine's own token
sequences, its logits and its state (``reference_state_rel``) where the
engine's would stand (``int8`` by the harness's own ``control=True``, logits
only); the three that
only the ENGINE can show run the cold-then-warm pair again on an emptied
trie and record the warm serve:

- ``pool_bf16``: the state pool (matrix state and conv inputs) rounded to
  bfloat16 after every step, where the configuration states float32;
- ``snapshot_bf16``: every snapshot rounded to bfloat16 as it is taken;
- ``snapshot_late``: a hit starts one position BEFORE its snapshot's depth,
  so the state it restores holds that position already and the step feeds
  it again: what a snapshot taken one position late gives.

``--shares`` adds, layer by layer on the longest sample, what the seeded
weights' scales were chosen by: the residual's root mean square, and for a
delta layer the quantiles of ``alpha`` and the share of ``beta`` past 1
over (token, head) pairs. One seed a process: two models do not fit the
chip.
"""

from __future__ import annotations

import argparse
import json
import os

import numpy as np

#: the limits a set of logits can be held to
LOGIT_LIMITS = ("logit_rel_err_pooled", "tie_gap_max")
#: the controls only the engine can show
ENGINE_CONTROLS = ("pool_bf16", "snapshot_bf16", "snapshot_late")


def log(*parts) -> None:
    print("[calibrate]", *parts, flush=True)


def gate_shares(ref, params, cf, padded, rows):
    """Per layer over ``rows``: the residual's RMS going in, and for a delta
    layer alpha's 5 / 50 / 95 % quantiles and the share of beta past 1, by
    the reference's own functions."""
    import jax
    import jax.numpy as jnp

    hp = ref.hyper(cf, "as_given")
    h = dict(hp)["delta_heads"]
    blocks = params["layers"]["periods"]
    periods, per = blocks["delta"]["w_qkv"].shape[:2]
    rms = lambda a: float(jnp.sqrt(jnp.mean(a[rows] ** 2)))
    idx = lambda *i: tuple(jnp.asarray(j, jnp.int32) for j in i)
    x = ref._embed(params["embed"], jnp.asarray(padded, jnp.int32))
    out = []
    for p in range(periods):
        for j in range(per):
            leaf = lambda name: ref._leaf(blocks["delta"], name, idx(p, j),
                                          "as_given")
            with jax.default_matmul_precision("highest"):
                ab = x[rows] @ leaf("w_ab")
            alpha = np.asarray(jnp.exp(-jnp.exp(leaf("A_log")) * jax.nn.softplus(
                ab[:, :h] + leaf("dt_bias"))))
            beta = np.asarray(2.0 * jax.nn.sigmoid(ab[:, h:]))
            out.append(("delta", rms(x), np.quantile(alpha, [.05, .5, .95])
                        .round(4).tolist(), float((beta > 1).mean())))
            y = ref._delta(x, blocks["delta"], idx(p, j), hp)[0]
            x = ref._close(x, y, blocks["delta"], idx(p, j), hp)
        out.append(("full", rms(x), None, None))
        y = ref._attention(x, blocks["attn"], idx(p), hp)
        x = ref._close(x, y, blocks["attn"], idx(p), hp)
    return out


def in_the_engines_place(params, samples, rows, cf, ref_len, variant):
    """``rows`` with each position's logits replaced by the reference's
    ``weights=variant`` pass over the same sequence: the engine's tokens
    stay, so the comparison runs over the positions it ran over."""
    from benchmark import check

    out = []
    for (prompt, _), pairs in zip(samples, rows):
        toks = [t for t, _ in pairs]
        got = np.asarray(check.reference_logits(
            params, prompt, toks, cf, ref_len, weights=variant))
        out.append(list(zip(toks, got)))
    return out


def reference_state_rel(ref, params, samples, rows, cf, ref_len, variant):
    """``state_rel_err`` with the reference's ``weights=variant`` pass in the
    engine's place: its state after each sample's fed tokens against the
    honest pass's, ``[samples, layers, H]``."""
    norm = lambda a: np.sqrt(np.square(a).sum((-2, -1)))
    out = []
    for (prompt, _), pairs in zip(samples, rows):
        seq = list(prompt) + [t for t, _ in pairs][:-1]
        padded = np.zeros(ref_len, np.int32)
        padded[:len(seq)] = seq
        want, got = (np.asarray(ref.state_at(params, padded, len(seq), cf,
                                             weights=w)[0])
                     for w in ("as_given", variant))
        out.append(norm(got - want) / norm(want))
    return np.stack(out)


def _bf16_tree():
    """A jitted rounding of every leaf of a tree of float32 arrays to bf16
    values (``reduce_precision``: the TPU's compiler drops a narrowing
    conversion that is widened again at once), in place."""
    import jax
    from jax import lax

    return jax.jit(lambda tree: jax.tree.map(
        lambda a: lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7),
        tree), donate_argnums=(0,))


def engine_control(llm, name):
    """Put the control ``name`` into the engine; returns the undo."""
    eng = llm.engine
    if name == "pool_bf16":
        low = _bf16_tree()
        leaves = eng._layout.state_leaves

        def rounded(cache):
            return {**cache, **low({k: cache[k] for k in leaves})}

        eng.kv_round = rounded
        return lambda: setattr(eng, "kv_round", None)
    if name == "snapshot_bf16":
        low, take = _bf16_tree(), eng._snapshot_fn
        eng._snapshot_fn = lambda *a: low(take(*a))
        return lambda: setattr(eng, "_snapshot_fn", take)
    if name == "snapshot_late":
        match = eng.prefix.match_snapshot

        def early(tokens):
            blocks, matched, snap = match(tokens)
            return blocks, matched - (1 if snap is not None else 0), snap

        eng.prefix.match_snapshot = early
        return lambda: setattr(eng.prefix, "match_snapshot", match)
    raise ValueError(name)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--controls", default="int8")
    ap.add_argument("--shares", action="store_true")
    ap.add_argument("--dump", default="",
                    help="directory for every reading's state errors by "
                         "(sample, layer, head), one .npy a reading")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import check, manifest, traffic
    from benchmark.kinds import serve, serve_state_family
    from benchmark.kinds.serve_snapshot_family_replica import (
        SnapshotFamilyLLM, cold_then_warm, logit_drift, state_rel_err,
        state_summary)

    man = manifest.load_manifest()
    cell = manifest.load_cell(man, args.workload)
    if args.rehearse_cpu:
        cell = serve_state_family.rehearsal_cell(cell)
    cf, mix = cell["config_file"], cell["traffic_file"]
    ref = manifest.load_module(manifest.reference_path(cf["reference"]))
    ref_len = int(cell["check"]["ref_len"])
    limits = {k: cell["limits"][k] for k in LOGIT_LIMITS}
    controls = [c for c in args.controls.split(",") if c]
    unknown = set(controls) - set(ref.VARIANTS) - set(ENGINE_CONTROLS)
    if unknown:
        ap.error(f"no such control: {sorted(unknown)}")

    llm = SnapshotFamilyLLM(cell, args.seed)
    try:
        eng = llm.engine
        facts = llm.bench_facts()
        log(f"seed {args.seed}: {facts['kind']} x{facts['count']} "
            f"({facts['platform']}); set-up {facts['setup']}; attention "
            f"{eng.stats['attn_impl']}; snapshot pool "
            f"{eng.prefix.snapshots}; memory peak "
            f"{facts.get('memory_peak_bytes')}")
        requests = traffic.generate(mix, cell["rate_rps"], man["run_seconds"],
                                    args.seed, cf["vocab_size"])
        samples = serve.pick_samples(requests, cell, args.seed)

        def record(name, numbers, **more):
            # the logits' limits, and of the kind's own what the reading has
            own = {k: v for k, v in cell["snapshot_limits"].items()
                   if k in more}
            verdicts = check.verdict(numbers, limits) \
                + check.verdict(more, own)
            print(json.dumps({name: numbers, "seed": args.seed,
                              "prompts": [len(p) for p, _ in samples],
                              **more, "verdict": verdicts,
                              "correct": all(v["ok"] for v in verdicts)}),
                  flush=True)


        against = lambda rows: check.logits_against_reference(
            llm.params, samples, rows, cf, ref_len)

        def state(name, rows, reqs):
            """The slots' state after the serve ``rows`` against the
            reference's: by layer, the least, median and largest head."""
            by_head = state_rel_err(llm, samples, rows, reqs)
            if args.dump:
                os.makedirs(args.dump, exist_ok=True)
                np.save(os.path.join(
                    args.dump, f"state_{name}_{args.seed}.npy"), by_head)
            return state_summary(by_head)

        (cold, n_cold, _), (warm, n_warm, reqs) = cold_then_warm(llm, samples)
        carried = state("sound_warm", warm, reqs)
        record("sound_cold", against(cold), restored=n_cold)
        record("sound_warm", against(warm), restored=n_warm, **carried)
        log(f"snapshot_logit_drift, sound: {logit_drift(cold, warm)}")
        for name in controls:
            if name in ENGINE_CONTROLS:
                undo = engine_control(llm, name)
                try:
                    (first, _, _), (rows, n, reqs) = cold_then_warm(
                        llm, samples)
                    carried = state(name, rows, reqs)
                finally:
                    undo()
                record(name, against(rows), restored=n, **carried,
                       snapshot_logit_drift=logit_drift(first, rows))
            elif name == "int8":
                record(name, check.logits_against_reference(
                    llm.params, samples, cold, cf, ref_len, control=True))
            else:
                record(name, against(in_the_engines_place(
                    llm.params, samples, cold, cf, ref_len, name)),
                    **state_summary(reference_state_rel(
                        ref, llm.params, samples, cold, cf, ref_len, name)))
        if args.shares:
            prompt, pairs = max(
                ((p, r) for (p, _), r in zip(samples, cold)),
                key=lambda pr: len(pr[0]))
            seq = list(prompt) + [t for t, _ in pairs][:-1]
            padded = np.zeros(ref_len, np.int32)
            padded[:len(seq)] = seq
            decoded = np.arange(len(prompt), len(seq))
            for i, (kind, x, alpha, beta) in enumerate(gate_shares(
                    ref, llm.params, cf, padded, decoded)):
                log(f"layer {i} ({kind}): residual RMS in {x:.3f}"
                    + (f", alpha 5/50/95 % {alpha}, beta past 1 for "
                       f"{beta:.3f} of (token, head) pairs" if alpha else ""))
    finally:
        llm.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
