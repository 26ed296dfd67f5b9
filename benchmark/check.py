"""How ``correct`` is decided: the numbers compared and the comparison.

The engine's logits at every generated position against the plain float32
reference over the whole sequence (prompt + the tokens the engine generated,
so prefill through the chunks and decode through the cache are both under
the comparison), as relative error ||engine - ref||2 / ||ref||2 over the
vocabulary; and the near-tie rule for the sampled token. Greedy-token
equality with a second program is NOT demanded: with seeded weights the
logits are nearly flat and the largest changes on rounding (PERF.md,
finding 7 of PR 21, and PR 23's refusal).

The limits are data (``workloads/<cell>.json``, key ``limits``), set from
chip readings of honest code and of the int8 control; PERF.md section 2
gives both readings for each.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np


def _load_reference(config_file: Dict[str, Any]):
    from benchmark import manifest

    return manifest.load_module(
        manifest.reference_path(config_file["reference"]))


def compare_logits(got, ref, tokens) -> Dict[str, Any]:
    """Per position: relative L2 error over the vocabulary, and how far the
    reference's logit of the chosen token lies under the reference's
    largest (0 when the token is the reference's arg-max)."""
    import jax.numpy as jnp

    got, ref = jnp.asarray(got, jnp.float32), jnp.asarray(ref, jnp.float32)
    rel = jnp.linalg.norm(got - ref, axis=-1) / jnp.linalg.norm(ref, axis=-1)
    chosen = jnp.take_along_axis(
        ref, jnp.asarray(tokens, jnp.int32)[:, None], axis=-1)[:, 0]
    gap = jnp.max(ref, axis=-1) - chosen
    # the reading a sampling fault would give: the neighbouring token
    wrong = jnp.take_along_axis(
        ref, ((jnp.asarray(tokens, jnp.int32) + 1) % ref.shape[-1])[:, None],
        axis=-1)[:, 0]
    return {"rel_err": np.asarray(rel), "tie_gap": np.asarray(gap),
            "wrong_token_gap": np.asarray(jnp.max(ref, axis=-1) - wrong),
            "err_sq": float(jnp.sum(jnp.square(got - ref))),
            "ref_sq": float(jnp.sum(jnp.square(ref))),
            "argmax_equal": np.asarray(jnp.argmax(ref, -1) == jnp.asarray(
                tokens, jnp.int32))}


def reference_logits(params, prompt, generated, config_file, ref_len,
                     weights="as_given"):
    """Reference logits at the positions that produced ``generated``: the
    sequence is prompt + generated[:-1], padded at the end to ``ref_len``
    (causal: padding after a position cannot reach it) so that one compiled
    program serves every sample of a cell."""
    ref = _load_reference(config_file)
    seq = list(prompt) + list(generated[:-1])
    if len(seq) > ref_len:
        raise ValueError(f"sequence of {len(seq)} exceeds ref_len {ref_len}")
    rows = np.arange(len(prompt) - 1, len(seq))
    padded = np.zeros(ref_len, np.int32)
    padded[:len(seq)] = seq
    return ref.logits_at(params, padded, rows, config_file, weights=weights)


def logits_against_reference(params, samples, rows, config_file,
                             ref_len: int, control: bool = False
                             ) -> Dict[str, Any]:
    """``rows[i]`` holds the (token, engine logits) pairs the engine gave
    for ``samples[i] = (prompt, max_new)``.

    ``control=True`` puts the control in the engine's place: the reference
    itself over int8 weights, at the same positions of the same sequences
    (teacher-forced on the same tokens, as the engine is on its own), its
    token the arg-max of its own logits."""
    rel, gap, equal, wrong, short, err2, ref2 = [], [], [], [], 0, 0.0, 0.0
    for (prompt, max_new), pairs in zip(samples, rows):
        toks = [t for t, _ in pairs]
        short += len(toks) != max_new
        ref = reference_logits(params, prompt, toks, config_file, ref_len)
        if control:
            got = reference_logits(params, prompt, toks, config_file,
                                   ref_len, weights="int8")
            chosen = np.asarray(got).argmax(-1)
        else:
            got, chosen = np.stack([l for _, l in pairs]), toks
        out = compare_logits(got, ref, chosen)
        err2 += out["err_sq"]
        ref2 += out["ref_sq"]
        rel.append(out["rel_err"])
        gap.append(out["tie_gap"])
        equal.append(out["argmax_equal"])
        wrong.append(out["wrong_token_gap"])
    rel, gap, equal, wrong = map(np.concatenate, (rel, gap, equal, wrong))
    return {"positions": int(rel.size), "samples": len(samples),
            "short_answers": int(short),
            "logit_rel_err_pooled": float(np.sqrt(err2 / ref2)),
            "logit_rel_err_max": float(rel.max()),
            "logit_rel_err_median": float(np.median(rel)),
            "logit_rel_err_min": float(rel.min()),
            "tie_gap_max": float(gap.max()),
            "wrong_token_gap_median": float(np.median(wrong)),
            "argmax_equal_share": float(equal.mean())}


def verdict(numbers: Dict[str, float],
            limits: Dict[str, float]) -> List[Dict[str, Any]]:
    """Each number compared beside its limit; a limit is an upper end."""
    return [{"name": k, "value": numbers[k], "limit": lim,
             "ok": bool(numbers[k] <= lim)} for k, lim in limits.items()]
