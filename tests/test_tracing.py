"""Trace plane (ISSUE 7): span ring, cross-process collection into the
head TraceStore, mid-session arming, critical-path analysis, Perfetto
export, and tpu_watch single-instance hygiene.

The multi-NODE collection path (heartbeat -> GCS trace store) is covered
in test_cluster.py; the serve request chain in test_serve.py.
"""

import json
import os
import time

import pytest

import ray_tpu
from ray_tpu.util import state, tracing, trace_store


def _cleanup_tracing():
    os.environ.pop("RTPU_TRACING", None)
    os.environ.pop("RTPU_TRACE_FILE", None)
    tracing._reset_for_tests()


@pytest.fixture
def clean_tracing():
    _cleanup_tracing()
    yield
    _cleanup_tracing()


def _wait_for(pred, timeout=45.0, interval=0.25):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = pred()
        if v:
            return v
        time.sleep(interval)
    return pred()


# ---------------------------------------------------------------------------
# recording plane (no runtime needed)
# ---------------------------------------------------------------------------


def test_disabled_span_is_noop(clean_tracing):
    with tracing.span("demo.test::off") as tp:
        assert tp is None
    assert tracing.manual_span("demo.test::off") is None
    tracing.record_span("demo.test::off", 1, 2)
    assert tracing.ring_stats()["len"] == 0


def test_ring_bounds_and_drop_counter(clean_tracing, monkeypatch):
    monkeypatch.setenv("RTPU_TRACING", "1")
    monkeypatch.setenv("RTPU_TRACE_RING", "16")
    tracing._reset_for_tests()
    end = time.time_ns()
    for i in range(40):
        tracing.record_span("demo.test::fill", end - 1000, end, {"i": i})
    st = tracing.ring_stats()
    assert st["len"] == 16
    assert st["dropped"] == 24
    batch = tracing.drain_ring()
    assert len(batch) == 16
    # drained exactly once: the ring is empty now
    assert tracing.ring_stats()["len"] == 0
    # newest survive a bounded ring
    assert batch[-1]["attributes"]["i"] == 39


def test_span_nesting_and_manual_parentage(clean_tracing, monkeypatch):
    monkeypatch.setenv("RTPU_TRACING", "1")
    tracing._reset_for_tests()
    with tracing.span("demo.test::outer") as outer_tp:
        assert outer_tp is not None
        with tracing.span("demo.test::inner") as inner_tp:
            pass
        ms = tracing.manual_span("demo.test::manual")
        ms.finish()
    spans = {s["name"]: s for s in tracing.drain_ring()}
    outer = spans["demo.test::outer"]
    inner = spans["demo.test::inner"]
    manual = spans["demo.test::manual"]
    assert inner["trace_id"] == outer["trace_id"]
    assert inner["parent_span_id"] == outer["span_id"]
    # manual span started while outer was active: same trace
    assert manual["trace_id"] == outer["trace_id"]
    assert manual["parent_span_id"] == outer["span_id"]
    assert outer_tp == f"00-{outer['trace_id']}-{outer['span_id']}-01"


def test_trace_store_since_cursor(clean_tracing):
    ts = trace_store.TraceStore(cap=100)
    ts.ingest([{"name": f"s{i}"} for i in range(5)], {"node_id": "n1"})
    batch, start = ts.since(0)
    assert start == 0 and len(batch) == 5
    assert all(s["node_id"] == "n1" for s in batch)
    # nothing new past the acked cursor
    batch2, start2 = ts.since(start + len(batch))
    assert batch2 == [] and start2 == 5
    ts.ingest([{"name": "s5"}])
    batch3, start3 = ts.since(5)
    assert [s["name"] for s in batch3] == ["s5"] and start3 == 5


def test_critical_path_for_trace_sums_exactly():
    ms = 1_000_000  # ns per ms
    spans = [
        {"name": "serve.handle::request", "trace_id": "t", "span_id": "a",
         "parent_span_id": None, "start_time_unix_nano": 0,
         "end_time_unix_nano": 100 * ms, "attributes": {}},
        {"name": "serve.handle::route", "trace_id": "t", "span_id": "b",
         "parent_span_id": "a", "start_time_unix_nano": 5 * ms,
         "end_time_unix_nano": 20 * ms, "attributes": {}},
        {"name": "execute::handle_request", "trace_id": "t",
         "span_id": "c", "parent_span_id": "b",
         "start_time_unix_nano": 40 * ms, "end_time_unix_nano": 90 * ms,
         "attributes": {}, "worker_id": "w1"},
    ]
    res = trace_store.critical_path_for_trace(spans)
    assert res["end_to_end_ms"] == pytest.approx(100.0)
    segs = res["segments"]
    total = sum(seg["ms"] for seg in segs.values())
    assert total == pytest.approx(100.0, abs=1e-6)
    # deepest-span attribution: route 15ms, execute 50ms, and the
    # queue/transit holes (5+20+10 = 35ms) are the root's SELF time
    exe = next(v for k, v in segs.items() if k.startswith("execute::"))
    assert exe["ms"] == pytest.approx(50.0)
    root = next(v for k, v in segs.items()
                if k.startswith("serve.handle::request"))
    assert root["ms"] == pytest.approx(35.0)
    assert res["dominant"].startswith("execute::")

    # without a covering root, the hole becomes an explicit gap segment
    res2 = trace_store.critical_path_for_trace(spans[1:])
    assert any(k.startswith("gap:") for k in res2["segments"])
    total2 = sum(seg["ms"] for seg in res2["segments"].values())
    assert total2 == pytest.approx(res2["end_to_end_ms"], abs=1e-6)


def test_critical_path_for_tasks_uses_submit_spans():
    ring = [{"task_id": b"\x01" * 16, "name": "f", "type": "task",
             "status": "ok", "ts": 0.0,
             "phases": {"queue": 0.001, "lease": 0.001, "execute": 0.002,
                        "store_result": 0.001, "total": 0.01}}]
    spans = [{"name": "submit::f",
              "attributes": {"task_id": (b"\x01" * 16).hex()},
              "start_time_unix_nano": 0,
              "end_time_unix_nano": 3_000_000}]
    res = trace_store.critical_path_for_tasks(ring, spans)
    assert res["tasks"] == 1
    segs = res["segments"]
    assert segs["driver_submit"]["mean_ms"] == pytest.approx(3.0)
    # transit = total - attributed = 10 - (1+1+2+1) - 3 = 2ms
    assert segs["transit"]["mean_ms"] == pytest.approx(2.0)
    out = trace_store.format_breakdown(res)
    assert "driver_submit" in out and "critical path" in out


# ---------------------------------------------------------------------------
# collection through a live runtime (workers push over the pipe)
# ---------------------------------------------------------------------------


@pytest.fixture
def traced_rt(clean_tracing, monkeypatch):
    monkeypatch.setenv("RTPU_TRACING", "1")
    tracing._reset_for_tests()
    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


def test_worker_spans_reach_driver_store(traced_rt, tmp_path):
    @ray_tpu.remote
    def traced(x):
        return x + 1

    assert ray_tpu.get(traced.remote(1), timeout=60) == 2

    def seen():
        # keep the pipeline busy so worker pushes fire promptly
        ray_tpu.get(traced.remote(0), timeout=60)
        spans = state.list_spans()
        ex = [s for s in spans if s["name"] == "execute::traced"
              and s.get("worker_id")]
        sub = [s for s in spans if s["name"] == "submit::traced"]
        return ex and sub and (ex, sub)

    got = _wait_for(seen)
    assert got, "worker execute spans never reached the driver TraceStore"
    ex, sub = got
    # driver submit span and worker execute span join one trace
    by_task = {s["attributes"].get("task_id"): s for s in sub}
    joined = [e for e in ex
              if e["attributes"].get("task_id") in by_task
              and e["trace_id"] ==
              by_task[e["attributes"]["task_id"]]["trace_id"]]
    assert joined, "execute spans did not share the submit span's trace"
    # origin labels ride the collection hop
    assert ex[0]["component"] == "worker"

    # unified Perfetto export: loads as JSON, has per-process rows and
    # real slices
    doc = state.export_perfetto(str(tmp_path / "t.json"))
    loaded = json.loads((tmp_path / "t.json").read_text())
    assert loaded == doc
    evs = doc["traceEvents"]
    assert any(e.get("ph") == "M" and e.get("name") == "process_name"
               for e in evs)
    assert any(e.get("ph") == "X" and "::" in str(e.get("name"))
               for e in evs)

    # aggregate critical path over the flight ring: execute attributed,
    # driver submit CPU visible from trace data
    res = state.summarize_critical_path()
    assert res["tasks"] > 0
    assert "execute" in res["segments"]
    assert "driver_submit" in res["segments"]


def test_enable_tracing_mid_session_reaches_live_workers(clean_tracing):
    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    try:
        @ray_tpu.remote
        def warm(x):
            return x

        # worker exists BEFORE arming — it must learn over the pipe
        assert ray_tpu.get(warm.remote(1), timeout=60) == 1
        assert state.list_spans() == []
        tracing.enable_tracing()

        def seen():
            ray_tpu.get(warm.remote(0), timeout=60)
            return [s for s in state.list_spans()
                    if s["name"] == "execute::warm"]

        assert _wait_for(seen), \
            "pre-armed worker never recorded after enable_tracing()"
        tracing.disable_tracing()
        tracing.drain_ring()
        before = len(state.list_spans())
        ray_tpu.get(warm.remote(2), timeout=60)
        time.sleep(0.5)
        # disarm reached the driver at least: no new driver submit spans
        new = [s for s in state.list_spans()[before:]
               if s["name"] == "submit::warm"]
        assert not new
    finally:
        ray_tpu.shutdown()
