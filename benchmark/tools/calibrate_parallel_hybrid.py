"""The readings the ``logit_rel_err_pooled`` limit of a cell of the parallel
attention / Mamba-2 family is set from, THROUGH the harness's own comparison
(``check.logits_against_reference`` then ``check.verdict`` against the
cell's limits), on the cell's own sample of requests, in one process that
holds the chip (``tools/calibrate.py`` does it for the dense cells; its
``BenchLLM`` makes a dense decoder's weights, so this family needs a tool of
its own until a ``benchmark`` PR folds the three into one):

    python3 -m benchmark.tools.calibrate_parallel_hybrid --workload <cell>
        --seed <n> [--controls int8,bf16_state,...] [--shares]

Per seed: the cell's replica class (``StateFamilyLLM``: the family's seeded
weights, the configuration's engine, its warm-up), the sample the cell's own
check picks from the window's requests (``serve.pick_samples``), served
together by the engine with the logits of every sampled position captured,
as ``bench_check`` does. Printed, one JSON line a reading, each with the
verdict on ``logit_rel_err_pooled`` and ``tie_gap_max`` beside it:
``sound`` (the engine against the plain reference), and each control in the
engine's place. ``int8`` is the contract's control, by the harness's own
``control=True``. Every other name of the reference's ``VARIANTS`` (a lower
precision of the state: ``bf16_state``, ``bf16_scan``; mathematics left
out) goes the same way: the reference's ``weights=<name>`` pass over the
engine's own token sequences, handed to ``logits_against_reference`` where
the engine's logits go. ``--shares`` adds, layer by layer on the longest
sample, the root mean square norms of the residual and of what the
attention branch, the Mamba-2 branch and the MLP add to it over the decoded
positions: what the seeded weights' scales were chosen by. One seed a
process: two models do not fit the chip.
"""

from __future__ import annotations

import argparse
import json

import numpy as np

#: the limits a set of logits can be held to
LOGIT_LIMITS = ("logit_rel_err_pooled", "tie_gap_max")


def log(*parts) -> None:
    print("[calibrate]", *parts, flush=True)


def branch_norms(ref, params, cf, padded, rows):
    """Per layer (residual in, attention out, Mamba-2 out, MLP out) over
    ``rows``, by the reference's own functions."""
    import jax.numpy as jnp

    hp = ref.hyper(cf, "as_given")
    layers = params["layers"]
    norm = lambda a: float(jnp.sqrt(jnp.mean(jnp.sum(a[rows] ** 2, -1))))
    x = ref._embed(params["embed"], jnp.asarray(padded, jnp.int32),
                   float(cf["embedding_multiplier"]))
    out = []
    for i in range(layers["wq"].shape[0]):
        idx = jnp.asarray(i, jnp.int32)
        att = ref._attention(x, layers, idx, hp)
        ssm = ref._mamba2(x, layers, idx, hp)
        after = ref._mlp(x + att + ssm, layers, idx, hp)
        out.append([norm(x), norm(att), norm(ssm),
                    norm(after - (x + att + ssm))])
        x = after
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--controls", default="int8")
    ap.add_argument("--shares", action="store_true")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import check, manifest, traffic
    from benchmark.kinds import serve, serve_state_family
    from benchmark.kinds.serve_state_family_replica import StateFamilyLLM

    man = manifest.load_manifest()
    cell = manifest.load_cell(man, args.workload)
    if args.rehearse_cpu:
        cell = serve_state_family.rehearsal_cell(cell)
    cf, mix = cell["config_file"], cell["traffic_file"]
    ref = manifest.load_module(manifest.reference_path(cf["reference"]))
    ref_len = int(cell["check"]["ref_len"])
    limits = {k: cell["limits"][k] for k in LOGIT_LIMITS}
    controls = [c for c in args.controls.split(",") if c]
    unknown = set(controls) - set(ref.VARIANTS)
    if unknown:
        ap.error(f"the reference has no variant {sorted(unknown)}")

    llm = StateFamilyLLM(cell, args.seed)
    try:
        facts = llm.bench_facts()
        log(f"seed {args.seed}: {facts['kind']} x{facts['count']} "
            f"({facts['platform']}); set-up {facts['setup']}; attention "
            f"{llm.engine.stats['attn_impl']}")
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
        for name in controls:
            if name == "int8":
                numbers = check.logits_against_reference(
                    llm.params, samples, rows, cf, ref_len, control=True)
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
            for i, (h, a, s, m) in enumerate(branch_norms(
                    ref, llm.params, cf, padded, decoded)):
                log(f"layer {i}: |residual in| {h:.3f}, |attention out| "
                    f"{a:.3f}, |Mamba-2 out| {s:.3f}, |MLP out| {m:.3f}")
    finally:
        llm.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
