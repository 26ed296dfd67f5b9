"""What runs inside the replica of a ``serve_snapshot_family`` cell:
``StateFamilyLLM`` (``serve_state_family_replica.py``) whose logits check
serves its sample TWICE on an emptied trie, cold and then through each
prompt's own snapshot, and keeps what the two serves differ by. Importing
this module imports no jax.

A snapshot is a copy, and a row that restores one feeds its prompt's tail in
the chunk the cold serve fed it in (the cold serve ends a chunk on the
boundary the copy is taken at), so the warm serve's logits are the cold
serve's TO THE BIT (my chip runs, PR 50: 0.0 apart over 256 positions on
every seed). ``snapshot_logit_drift`` is the largest difference of any logit
between the two; a snapshot held in bfloat16, where the configuration states
float32, moves it (the calibration tool's ``snapshot_bf16``), while the
comparison with the plain reference cannot see one rounding of the state
(0.0494 against the sound 0.0496). What is compared with the reference is the
WARM serve: prefill past a restored snapshot, then decode; and after it the
state its requests left in their slots (``state_rel_err``), which is what a
state POOL rounded to bfloat16 moves where the logits pass it (0.053 against
the sound 0.049 under a limit of 0.062).
"""

from __future__ import annotations

from typing import Any, Dict, List

from benchmark.kinds.serve_state_family_replica import StateFamilyLLM


def cold_then_warm(llm, samples: List[tuple]):
    """The samples served on an emptied trie and then again: ``(rows,
    requests that restored a snapshot, the engine's requests)`` of each
    serve."""
    eng = llm.engine
    llm.bench_forget_prefixes()
    out = []
    for _ in range(2):
        before = eng.stats["state_snapshots_restored"]
        requests, submit = [], eng.submit
        eng.submit = lambda *a, **kw: (requests.append(submit(*a, **kw))
                                       or requests[-1])
        try:
            rows = llm.serve_captured(samples)
        finally:
            del eng.submit          # the instance's: the method is back
        out.append((rows, eng.stats["state_snapshots_restored"] - before,
                    requests))
    return out


def state_rel_err(llm, samples: List[tuple], rows, requests):
    """``[samples, layers, H]``: by delta layer and head, how far the matrix
    state a request's slot holds once it is served lies from the plain
    reference's after the same tokens (the prompt and all but the last token
    of the answer: what the request was fed), ``||engine - ref|| / ||ref||``.
    (The conv's last inputs are not compared: the step zeroes them for a slot
    whose request has left, a row at position 0, so they can be read only of
    the request that retires last; they are bf16 values held in float32, and
    a wrong one is a wrong token's projection, which the logits see.)"""
    import numpy as np

    from benchmark import check

    cf, eng = llm.config_file, llm.engine
    ref = check._load_reference(cf)
    norm = lambda a, axes: np.sqrt(np.square(a).sum(axes))
    states = []
    for (prompt, _), pairs, req in zip(samples, rows, requests):
        seq = list(prompt) + [t for t, _ in pairs][:-1]
        padded = np.zeros(int(llm.cell["check"]["ref_len"]), np.int32)
        padded[:len(seq)] = seq
        want = np.asarray(ref.state_at(llm.params, padded, len(seq), cf)[0])
        got = np.asarray(llm.family.slot_state(eng._cache, req.slot,
                                               eng.config)[0])
        states.append(norm(got - want, (-2, -1)) / norm(want, (-2, -1)))
    return np.stack(states)


def logit_drift(cold, warm) -> float:
    """The largest difference of any logit between two serves of one sample
    (a token that differs: infinity)."""
    import numpy as np

    worst = 0.0
    for a, b in zip(cold, warm):
        if [t for t, _ in a] != [t for t, _ in b]:
            return float("inf")
        worst = max([worst] + [float(np.abs(x[1] - y[1]).max())
                               for x, y in zip(a, b)])
    return worst


def state_summary(by_head) -> Dict[str, Any]:
    """``state_rel_err``'s array as the numbers a run keeps: the one the cell
    holds to a limit, ``state_rel_err_first_layer`` (the WORST head of the
    FIRST delta layer over the samples), and by layer the least, median and
    largest head.

    Why the first layer: its input is the embedding, the same on both sides,
    so its state differs from the reference's by the bf16 projections alone
    (0.0022-0.0040 in every head on every seed, my chip runs, PR 50), and a
    state pool held in bfloat16 where the configuration states float32 shows
    (0.0030-0.0125: a head whose decay is slow gathers a rounding a step
    over the 64 decode turns). From the second layer on the residual
    carries the bf16 activations' own error (0.007-0.014, then 0.05-0.2 in
    the ninth) and a rounding of the state is lost in it. Why the worst
    head: the sound heads read alike, and the rounding lands on the slow
    ones."""
    import numpy as np

    layers = np.moveaxis(by_head, 1, 0).reshape(by_head.shape[1], -1)
    return {"state_rel_err_first_layer": float(by_head[:, 0].max()),
            "state_by_layer_min_median_max": np.stack(
                [layers.min(1), np.median(layers, 1), layers.max(1)],
                1).round(5).tolist()}


class SnapshotFamilyLLM(StateFamilyLLM):
    """One replica of a ``serve_snapshot_family`` cell."""

    snapshot_check: Dict[str, Any] = {}

    def bench_check(self, samples: List[tuple],
                    control: bool = False) -> Dict[str, Any]:
        from benchmark import check

        (cold, _, _), (warm, restored, requests) = cold_then_warm(
            self, samples)
        self.snapshot_check = {
            "snapshot_logit_drift": logit_drift(cold, warm),
            **state_summary(state_rel_err(self, samples, warm, requests)),
            "restored": restored, "samples": len(samples)}
        return check.logits_against_reference(
            self.params, samples, warm, self.config_file,
            int(self.cell["check"]["ref_len"]), control=control)

    def bench_collect(self) -> Dict[str, Any]:
        out = super().bench_collect()
        out["snapshot_check"] = self.snapshot_check
        return out
