"""Read the numbers every limit of the logits check is set from, in one
process that holds the chip (no ray_tpu runtime, one set-up):

    python3 -m benchmark.tools.calibrate --workload <cell> --seeds 1,2,3 \
        [--control 1] [--engine-control kv,int8]

For each seed: new seeded weights in the same engine, the cell's own sample
of requests served by the engine with its logits captured, and the plain
reference over the same sequences. ``--control 1`` adds the control of the
contract: the reference over int8 weights in the engine's place.
``--engine-control`` adds the same downgrade, and the next one a deployment
would reach for, read THROUGH the program: the engine itself serving
(``kv``) a KV pool rounded to fp8 (e4m3) after every step, (``int8``)
weights rounded to int8 per output channel as the control rounds them, and
then both; each on tokens of its own so that no KV block of an earlier
serve is hit.
Prints one JSON line per reading and a summary; PERF.md section 2 records
them beside the limits.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys

from benchmark import check, manifest, traffic, weights
from benchmark.kinds import serve

#: token seeds of the engine controls, apart from the run's own
_TOKEN_SEED_STEP = 1000003


def _kv_rounder():
    """Round a KV pool to fp8 (e4m3: 4 exponent and 3 mantissa bits), in
    place. By ``reduce_precision``, not a pair of ``astype``: on the TPU the
    compiler drops a narrowing conversion that is widened again at once (my
    chip run, PR 24: the pair read the same as no rounding at all). Values
    under 2**-6 flush to zero where e4m3 keeps subnormals."""
    import jax

    def to_fp8(c):
        return jax.tree.map(
            lambda x: jax.lax.reduce_precision(x, exponent_bits=4,
                                               mantissa_bits=3), c)

    return jax.jit(to_fp8, donate_argnums=0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--engine-control", default="")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    man = manifest.load_manifest()
    cell = manifest.load_cell(man, args.workload)
    if args.rehearse_cpu:
        cell = serve.rehearsal_cell(cell)
    cf, mix = cell["config_file"], cell["traffic_file"]
    vocab, ref_len = cf["vocab_size"], cell["check"]["ref_len"]
    seeds = [int(s) for s in args.seeds.split(",")]

    from benchmark.kinds.serve_replica import BenchLLM

    llm = BenchLLM(cell, seeds[0])
    eng = llm.engine
    facts = llm.bench_facts()
    print(f"[calibrate] {facts['kind']} x{facts['count']} "
          f"({facts['platform']}); set-up {facts['setup']}", flush=True)
    reference = manifest.load_module(manifest.reference_path(cf["reference"]))
    readings = {}
    engine_controls = set(filter(None, args.engine_control.split(",")))
    if engine_controls - {"kv", "int8"}:
        ap.error("--engine-control takes kv, int8 or both")
    rounder = _kv_rounder() if "kv" in engine_controls else None

    def install(params):
        llm.params = params
        eng.set_params(params)
        gc.collect()

    def serve_sample(token_seed):
        """The cell's sample of requests with tokens from ``token_seed``,
        served by the engine as it stands now."""
        requests = traffic.generate(mix, cell["rate_rps"], man["run_seconds"],
                                    token_seed, vocab)
        samples = serve.pick_samples(requests, cell, token_seed)
        warm = traffic.warm_prompts(mix, token_seed, vocab)
        if warm:
            llm._serve_local([(p, 1) for p in warm])
        hits0 = eng.stats["prefix_hit_tokens"]
        rows = llm.serve_captured(samples)
        return samples, rows, eng.stats["prefix_hit_tokens"] - hits0

    def record(name, seed, out, hits=None):
        out["seed"] = seed
        if hits is not None:
            out["prefix_hit_tokens"] = hits
        readings.setdefault(name, []).append(out)
        print(json.dumps({name: out}), flush=True)

    try:
        for i, seed in enumerate(seeds):
            if i:
                install(None)
                install(weights.make_params(llm.tconfig, seed))
            samples, rows, hits = serve_sample(seed)
            record("sound", seed, check.logits_against_reference(
                llm.params, samples, rows, cf, ref_len), hits)
            if args.control:
                record("control", seed, check.logits_against_reference(
                    llm.params, samples, rows, cf, ref_len, control=True))
            if "kv" in engine_controls:
                # bf16 weights, fp8 KV pool: compared while they are here
                eng.kv_round = rounder
                served = serve_sample(seed + _TOKEN_SEED_STEP)
                eng.kv_round = None
                record("engine_fp8_kv", seed, check.logits_against_reference(
                    llm.params, served[0], served[1], cf, ref_len), served[2])
            if "int8" not in engine_controls:
                continue
            # the weights are given up for their int8 rounding, then made
            # again from the seed for the reference
            params, llm.params = llm.params, None
            eng.set_params(None)
            install(reference.rounded_weights(params, cf))
            del params
            served = {"engine_int8_weights":
                      serve_sample(seed + 2 * _TOKEN_SEED_STEP)}
            if rounder is not None:
                eng.kv_round = rounder
                served["engine_int8_weights_fp8_kv"] = serve_sample(
                    seed + 3 * _TOKEN_SEED_STEP)
                eng.kv_round = None
            install(None)
            install(weights.make_params(llm.tconfig, seed))
            for name, (s, r, h) in served.items():
                record(name, seed, check.logits_against_reference(
                    llm.params, s, r, cf, ref_len), h)
    finally:
        llm.close()
    summary = {"workload": args.workload, "seeds": len(seeds)}
    for name, outs in readings.items():
        pooled = [o["logit_rel_err_pooled"] for o in outs]
        summary[name] = {"pooled_min": min(pooled), "pooled_max": max(pooled),
                         "tie_gap_max": max(o["tie_gap_max"] for o in outs)}
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
