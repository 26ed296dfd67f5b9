"""The readings a ``serve_family`` cell's ``logit_rel_err_pooled`` limit is set
from, in one process on the chip (``tools/calibrate.py`` does it for the dense
cells through the replica; this goes through the engine directly, so that a
seed costs two minutes and not four):

    python3 -m benchmark.tools.calibrate_family --workload <cell> --seed <n>
        [--tails 300,3600] [--controls int8,recent_keys] [--shares]

Per seed: the family's seeded weights, the engine with the configuration's
settings, one shared document served, then per ``tail`` a request of document
+ ``tail`` unshared tokens decoded for ``check.new_tokens`` tokens with the
logits of every sampled position kept. Printed: the engine against the plain
reference (the sound reading), and each control in the engine's place: the
reference's own ``weights=<control>`` pass against its honest pass on the
same sequence (``int8``: the contract's control; ``recent_keys``: the indexer
left out). ``--shares`` adds, layer by layer on the last sequence, the norms
of the residual, the attention branch and the expert branch over the unshared
positions: what the seeded weights' scales were chosen by. One seed a
process: two models do not fit the chip.
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def log(*parts) -> None:
    print("[calibrate]", *parts, flush=True)


def branch_norms(ref, params, cf, padded, rows):
    """Root mean square norms of (residual in, attention out, experts out)
    over ``rows``, per layer, by the reference's own functions."""
    import jax
    import jax.numpy as jnp

    hp = dict(ref.hyper(cf))
    eps = hp["rms_norm_eps"]

    @jax.jit
    def parts(h, layers, index):
        lp = {k: jax.lax.dynamic_index_in_dim(w, index, 0, False)
              for k, w in layers.items()}
        lp = {k: w if k in ref._EXPERT else w.astype(jnp.float32)
              for k, w in lp.items()}
        with jax.default_matmul_precision("highest"):
            a = ref._attention(ref._rms_norm(h, lp["attn_norm"], eps), lp,
                               hp, "indexer")
            m = ref._experts(ref._rms_norm(h + a, lp["mlp_norm"], eps), lp,
                             hp, "as_given")
        norm = lambda x: jnp.sqrt(jnp.mean(jnp.sum(x[rows] ** 2, -1)))
        return h + a + m, norm(h), norm(a), norm(m)

    h = ref._embed(params["embed"], jnp.asarray(padded))
    out = []
    for i in range(cf["num_hidden_layers"]):
        h, *norms = parts(h, params["layers"], i)
        out.append([float(x) for x in norms])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--tails", default="300,3600")
    ap.add_argument("--controls", default="int8")
    ap.add_argument("--shares", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from benchmark import check, manifest, weights
    from benchmark.kinds.serve_family_replica import load_family
    from ray_tpu.serve.llm import LLMEngine
    from ray_tpu.util.tpu_info import ensure_compile_cache

    cell = manifest.load_cell(manifest.load_manifest(), args.workload)
    cf, family = cell["config_file"], load_family(cell["config_file"])
    ref = manifest.load_module(manifest.reference_path(cf["reference"]))
    ref_len, new = cell["check"]["ref_len"], cell["check"]["new_tokens"]
    ensure_compile_cache()
    t0 = time.monotonic()
    tconfig = family.transformer_config(cf)
    params = jax.jit(lambda k: family.build_params(tconfig, k))(
        weights.prng_key(args.seed))
    eng_cf = cf["engine"]
    eng = LLMEngine(
        tconfig, params, seed=args.seed, paged=True,
        max_slots=eng_cf["max_slots"], max_len=eng_cf["max_len"],
        block_size=eng_cf["block_size"], num_blocks=eng_cf["num_blocks"],
        prefill_chunk=eng_cf["prefill_chunk"])
    captured, sample = [], eng._sample

    def capture(row):
        captured.append(row.copy())
        return sample(row)

    def serve(prompt, n):
        out = []
        eng.submit(prompt, n, lambda item: out.append(item)
                   if isinstance(item, int) else None)
        while eng.step():
            pass
        return out

    rng = np.random.default_rng(args.seed)
    vocab = cf["vocab_size"]
    doc = rng.integers(
        0, vocab, cell["traffic_file"]["shared_prefix_tokens"]).tolist()
    serve(doc + [1], 1)
    log(f"seed {args.seed}: weights, engine and one document in "
        f"{time.monotonic() - t0:.1f} s on {jax.devices()[0].device_kind}")
    controls = [c for c in args.controls.split(",") if c]
    sums = {name: [0.0, 0.0] for name in ["engine"] + controls}

    def add(name, got, honest):
        err = float(np.sum((got - honest) ** 2))
        sums[name][0] += err
        sums[name][1] += float(np.sum(honest ** 2))
        return np.sqrt(err / float(np.sum(honest ** 2)))

    for tail in (int(t) for t in args.tails.split(",")):
        prompt = doc + rng.integers(0, vocab, tail).tolist()
        captured.clear()
        eng._sample = capture
        toks = serve(prompt, new)
        eng._sample = sample
        honest = np.asarray(check.reference_logits(
            params, prompt, toks, cf, ref_len))
        got = np.stack(captured)
        line = [f"engine {add('engine', got, honest):.5f} (arg-max equal "
                f"{(got.argmax(1) == honest.argmax(1)).mean():.3f})"]
        for name in controls:
            control = np.asarray(check.reference_logits(
                params, prompt, toks, cf, ref_len, weights=name))
            line.append(f"{name} {add(name, control, honest):.5f}")
        log(f"seed {args.seed} tail {tail}:", ", ".join(line))
    log(f"seed {args.seed} pooled over {len(args.tails.split(','))} x {new} "
        f"positions:", {k: round(float(np.sqrt(a / b)), 5)
                        for k, (a, b) in sums.items()})
    if args.shares:
        seq = prompt + toks[:-1]
        padded = np.zeros(ref_len, np.int32)
        padded[:len(seq)] = seq
        rows = np.arange(len(doc), len(seq))
        for i, (h, a, m) in enumerate(branch_norms(ref, params, cf, padded,
                                                   rows)):
            log(f"layer {i}: |residual in| {h:.3f}, |attention out| {a:.3f},"
                f" |experts out| {m:.3f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
