"""The logits check at a debug size on the CPU: the paged engine (chunked
prefill, decode through the cache, a prefix hit) against the plain
reference, and the int8 control put in the engine's place. The control's
chip runs at the cells' own sizes are in PERF.md; this keeps the comparison
itself under test at a size a test run can hold. Starts no ray_tpu runtime."""

import numpy as np
import pytest

from benchmark import check, manifest, traffic
from benchmark.kinds import serve

SEEDS = [11, 2**31 + 7, 123456789]


@pytest.fixture(scope="module", params=["mistral-7b.chat-steady",
                                         "qwen2-7b.agent-prefix"])
def replica(request):
    from benchmark.kinds.serve_replica import BenchLLM

    cell = serve.rehearsal_cell(
        manifest.load_cell(manifest.load_manifest(), request.param))
    llm = BenchLLM(cell, SEEDS[0])
    yield cell, llm
    llm.close()


def _samples(cell, seed):
    cf, mix = cell["config_file"], cell["traffic_file"]
    reqs = traffic.generate(mix, 2.0, 10, seed, cf["vocab_size"])
    return serve.pick_samples(reqs, cell, seed), \
        traffic.warm_prompts(mix, seed, cf["vocab_size"])


def _check(cell, llm, seed, control=False):
    samples, warm = _samples(cell, seed)
    if warm:
        llm._serve_local([(p, 1) for p in warm])
    rows = llm.serve_captured(samples)
    return check.logits_against_reference(
        llm.params, samples, rows, cell["config_file"],
        cell["check"]["ref_len"], control=control)


def test_engine_matches_reference_and_control_does_not(replica):
    cell, llm = replica
    hits0 = llm.engine.stats["prefix_hit_tokens"]
    sound = _check(cell, llm, SEEDS[0])
    control = _check(cell, llm, SEEDS[0], control=True)
    assert sound["short_answers"] == 0 and sound["positions"] == 16
    # bf16 activations against float32 "highest": about a percent, and the
    # int8 control is clearly worse on the same sequences
    assert sound["logit_rel_err_pooled"] < 0.03
    assert control["logit_rel_err_pooled"] > 1.5 * sound["logit_rel_err_pooled"]
    if cell["traffic_file"]["tenants"]:
        assert llm.engine.stats["prefix_hit_tokens"] > hits0


def test_a_broken_layer_fails_the_check(replica):
    """What the check is for: leave part of the mathematics out (here the
    engine is given another window than the reference) and it fails."""
    cell, llm = replica
    if not cell["config_file"]["use_sliding_window"]:
        pytest.skip("this family runs without a window")
    wrong = dict(cell["config_file"], use_sliding_window=False)
    samples, _ = _samples(cell, SEEDS[1])
    rows = llm.serve_captured(samples)
    got = check.logits_against_reference(llm.params, samples, rows, wrong,
                                         cell["check"]["ref_len"])
    ok = check.logits_against_reference(llm.params, samples, rows,
                                        cell["config_file"],
                                        cell["check"]["ref_len"])
    assert got["logit_rel_err_pooled"] > 5 * ok["logit_rel_err_pooled"]


def test_compare_logits_near_tie_rule():
    ref = np.array([[0.0, 1.0, 0.9], [2.0, 0.0, 0.0]], np.float32)
    out = check.compare_logits(ref + 0.01, ref, [2, 0])
    assert out["tie_gap"] == pytest.approx([0.1, 0.0], abs=1e-6)
    assert list(out["argmax_equal"]) == [False, True]
    assert out["rel_err"][1] == pytest.approx(0.01 * 3 ** 0.5 / 2.0, rel=1e-4)


def test_verdict_prints_each_number_beside_its_limit():
    v = check.verdict({"a": 0.5, "b": 1}, {"a": 1.0, "b": 0})
    assert [(x["name"], x["ok"]) for x in v] == [("a", True), ("b", False)]
