"""The trace reduction on a small recorded trace of the chip (three
executions of the paged step program) and on hand-made events."""

import gzip
import json
import os

import pytest

from benchmark import trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "recorded_trace.json.gz")


@pytest.fixture(scope="module")
def reduced():
    with gzip.open(DATA, "rt") as f:
        rec = json.load(f)
    return trace_reduce.reduce(
        rec["trace"], host_spans=[tuple(s) for s in rec["host_spans"]],
        anchor_ns=rec["anchor_ns"], window=tuple(rec["window"]),
        program="_raw_step_paged")


def test_recorded_trace_busy_time_and_program_runs(reduced):
    assert reduced["n_devices"] == 1
    assert reduced["window_s"] == pytest.approx(0.293988953)
    # three runs of 91.84 ms, and nothing else, ran in the window
    assert reduced["program_runs_ms"] == pytest.approx(
        [91.841621, 91.839628, 91.845962])
    assert reduced["busy_s"] == pytest.approx(0.275526136)
    assert reduced["busy_s"] == pytest.approx(
        sum(reduced["program_runs_ms"]) / 1e3, rel=1e-3)


def test_recorded_trace_operations_count_nested_time_once(reduced):
    # the layers run inside a `while`: self times add up to the busy time
    # only if the while's own duration is not counted again
    ops = reduced["device_ops"]
    assert len(ops) == 10
    assert ops[0][0] == "%fusion.177 fusion f32[16,32]"
    assert ops[0][1] == pytest.approx(0.042892944)
    assert sum(s for _, s in ops) < reduced["busy_s"]
    assert all(len(name) <= 120 for name, _ in ops)


def test_recorded_trace_idle_gaps_name_what_the_host_did(reduced):
    gaps = reduced["idle_gaps"]
    # the longest gap runs from the window's start, inside one step's
    # sample_emit, through admit, build_inputs and dispatch into the next
    # fetch_logits: no span covers half of it, so none names it; the next
    # lies inside one fetch_logits
    assert gaps[0] == ["outside_any_span", pytest.approx(0.006941841)]
    assert gaps[1] == ["fetch_logits", pytest.approx(0.006520496)]
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(reduced["idle_by_host_state_s"].values()) == pytest.approx(idle)


def _trace(ops, modules=()):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": list(modules)},
            {"name": "XLA Ops", "events": list(ops)}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [["bench::anchor", 1000.0, 10.0]]}]}]}


def test_hand_made_events():
    # anchor: trace 1000 ns = monotonic 5_000_001_000 ns, so offset -5e9
    ops = [["%while.1 = while(...)", 2000.0, 6000.0],
           ["%a = f32[8]{0} fusion(x)", 2500.0, 1000.0],
           ["%b = f32[8]{0} copy(y)", 4000.0, 3000.0],
           ["%c = f32[8]{0} copy(z)", 9000.0, 500.0]]
    mods = [["jit_step(1)", 2000.0, 6000.0], ["jit_other(2)", 9000.0, 500.0]]
    spans = [("fetch", 5.000008, 5.0000089), ("build", 5.0000095, 5.0000105)]
    out = trace_reduce.reduce(
        _trace(ops, mods), host_spans=spans, anchor_ns=5_000_001_000,
        window=(5.000001, 5.000011), program="jit_step")
    assert out["window_s"] == pytest.approx(10e-6)
    assert out["busy_s"] == pytest.approx(6.5e-6)
    assert out["program_runs_ms"] == pytest.approx([0.006])
    times = dict(out["device_ops"])
    assert times["%while.1 while"] == pytest.approx(2e-6)   # 6000-1000-3000
    assert times["%b copy f32[8]"] == pytest.approx(3e-6)
    # idle: 1000-2000 (no span), 8000-9000 (fetch covers 900 of it),
    # 9500-11000 (build covers 1000)
    assert out["idle_gaps"][0] == ["build", pytest.approx(1.5e-6)]
    assert {g[0] for g in out["idle_gaps"]} == {
        "build", "fetch", "outside_any_span"}


def test_a_span_names_a_gap_only_if_it_covers_more_than_half_of_it():
    # one step, then 1.2 s in which no request is in flight, then a step:
    # the fetch_logits of the first step ends 3 us into the gap and would
    # have named all of it; the build_inputs of the next step covers its
    # last 40 us. The second gap, 6 ms, lies inside one fetch_logits.
    ops = [["%a = f32[8]{0} fusion(x)", 1_000_000.0, 20_000_000.0],
           ["%b = f32[8]{0} fusion(x)", 1_221_000_000.0, 20_000_000.0],
           ["%c = f32[8]{0} fusion(x)", 1_247_000_000.0, 20_000_000.0]]
    spans = [("fetch_logits", 0.002, 0.021003),
             ("sample_emit", 0.021003, 0.0211),
             ("build_inputs", 1.22096, 1.2212),
             ("fetch_logits", 1.2213, 1.26)]
    out = trace_reduce.reduce(
        _trace(ops), host_spans=spans, anchor_ns=1000,
        window=(0.001, 1.267), program="jit_step")
    assert out["idle_gaps"][0] == ["outside_any_span", pytest.approx(1.2)]
    assert out["idle_gaps"][1] == ["fetch_logits", pytest.approx(0.006)]
    assert out["idle_by_host_state_s"]["outside_any_span"] == \
        pytest.approx(1.2)
    assert out["busy_s"] == pytest.approx(0.06)     # the union is untouched


def test_a_trace_without_the_anchor_is_refused():
    t = _trace([])
    t["planes"][1]["lines"][0]["events"] = []
    with pytest.raises(ValueError):
        trace_reduce.reduce(t, host_spans=[], anchor_ns=0, window=(0, 1),
                            program="x")
