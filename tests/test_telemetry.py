"""Telemetry plane: metric registration semantics, Prometheus exposition
correctness, metrics federation, the task-lifecycle flight recorder, and
train step telemetry (ISSUE 3)."""

import json
import re
import time

import pytest

import ray_tpu


# ---------------------------------------------------------------------------
# metric registry semantics (satellite: silent name-collision fix)
# ---------------------------------------------------------------------------


def test_metric_reregistration_merges_samples():
    """Re-creating a metric with an existing name must NOT orphan the
    previously recorded samples — both instances share one store."""
    from ray_tpu.util.metrics import Counter, clear_registry, prometheus_text

    clear_registry()
    c1 = Counter("reg_merge_total", "first registration")
    c1.inc(2)
    c2 = Counter("reg_merge_total", "second registration")
    c2.inc(3)
    # both instances observe the merged value
    assert dict(c1._samples()) == dict(c2._samples())
    text = prometheus_text()
    assert "reg_merge_total 5.0" in text
    # later increments through the FIRST instance still land too
    c1.inc(1)
    assert "reg_merge_total 6.0" in prometheus_text()
    clear_registry()


def test_metric_type_mismatch_raises():
    from ray_tpu.util.metrics import Counter, Gauge, clear_registry

    clear_registry()
    Counter("reg_clash_total", "a counter")
    with pytest.raises(ValueError, match="already registered"):
        Gauge("reg_clash_total", "now a gauge?")
    clear_registry()


def test_histogram_boundary_mismatch_raises():
    from ray_tpu.util.metrics import Histogram, clear_registry

    clear_registry()
    Histogram("reg_hist", "h", boundaries=[1, 10])
    with pytest.raises(ValueError, match="boundaries"):
        Histogram("reg_hist", "h", boundaries=[2, 20])
    # identical boundaries merge fine
    h2 = Histogram("reg_hist", "h", boundaries=[1, 10])
    h2.observe(5)
    clear_registry()


# ---------------------------------------------------------------------------
# Prometheus exposition correctness (satellite)
# ---------------------------------------------------------------------------


def test_prometheus_histogram_cumulative_buckets():
    from ray_tpu.util.metrics import Histogram, clear_registry, prometheus_text

    clear_registry()
    h = Histogram("expo_hist", "latency", boundaries=[0.1, 1, 10])
    for v in (0.05, 0.5, 0.5, 5, 50, 500):
        h.observe(v)
    text = prometheus_text()
    lines = [line for line in text.splitlines()
             if line.startswith("expo_hist")]
    # cumulative le buckets, +Inf == count, exact sum
    assert 'expo_hist_bucket{le="0.1"} 1' in lines
    assert 'expo_hist_bucket{le="1"} 3' in lines
    assert 'expo_hist_bucket{le="10"} 4' in lines
    assert 'expo_hist_bucket{le="+Inf"} 6' in lines
    assert "expo_hist_count 6" in lines
    assert "expo_hist_sum 556.05" in lines
    # buckets are monotonically non-decreasing in exposition order
    cums = [float(line.rsplit(" ", 1)[1]) for line in lines
            if line.startswith("expo_hist_bucket")]
    assert cums == sorted(cums)
    clear_registry()


def test_prometheus_label_escaping():
    from ray_tpu.util.metrics import Counter, clear_registry, prometheus_text

    clear_registry()
    c = Counter("expo_esc_total", "escapes", tag_keys=("path",))
    nasty = 'he said "hi"\\there\nnewline'
    c.inc(1, tags={"path": nasty})
    text = prometheus_text()
    assert ('expo_esc_total{path="he said \\"hi\\"\\\\there\\nnewline"} 1.0'
            in text)
    # literal newline must never appear inside a label value
    for line in text.splitlines():
        if line.startswith("expo_esc_total{"):
            assert "\n" not in line
    clear_registry()


def test_prometheus_single_type_header_with_federation():
    """Local + remote samples of the same metric group under ONE
    HELP/TYPE header (the text format forbids repeating it)."""
    from ray_tpu.util.metrics import (Counter, FederationStore,
                                      clear_registry, prometheus_text,
                                      registry_records)

    clear_registry()
    c = Counter("fed_shared_total", "d")
    c.inc(1)
    store = FederationStore()
    store.ingest("w1", {"worker_id": "aaaa", "node_id": "n1",
                        "component": "worker"}, registry_records())
    text = prometheus_text(extra=store.export())
    assert text.count("# TYPE fed_shared_total counter") == 1
    assert "fed_shared_total 1.0" in text
    assert ('fed_shared_total{component="worker",node_id="n1",'
            'worker_id="aaaa"} 1.0') in text
    clear_registry()


# ---------------------------------------------------------------------------
# task-lifecycle flight recorder + single-node worker federation
# ---------------------------------------------------------------------------


@pytest.fixture
def rt_telemetry(monkeypatch):
    monkeypatch.setenv("RTPU_METRICS_PUSH_INTERVAL_S", "0.2")
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield ray_tpu
    ray_tpu.shutdown()


def test_flight_recorder_phases_and_summary(rt_telemetry):
    import numpy as np

    @ray_tpu.remote
    def work(xs):
        time.sleep(0.02)
        return len(xs)

    # big enough to take the store-segment path (inline args would skip
    # the arg_fetch phase)
    ref = ray_tpu.put(np.zeros(500_000))
    assert ray_tpu.get([work.remote(ref) for _ in range(6)],
                       timeout=60) == [500_000] * 6

    from ray_tpu.core.runtime import _get_runtime
    from ray_tpu.util.state import list_task_events, summarize_tasks

    ring = list_task_events()
    recs = [r for r in ring if r["name"] == "work"]
    assert len(recs) >= 6
    for rec in recs:
        ph = rec["phases"]
        # every lifecycle phase is present and sane
        for key in ("queue", "lease", "arg_fetch", "execute",
                    "store_result", "total"):
            assert key in ph, ph
            assert ph[key] >= 0
        assert ph["execute"] >= 0.015  # the sleep is visible
        assert ph["total"] >= ph["execute"]
        assert rec["status"] == "ok"
        assert rec["worker_id"]

    summary = summarize_tasks()
    phases = summary["work"]["phases"]
    assert phases["execute"]["count"] >= 6
    assert phases["execute"]["p50_ms"] >= 15
    assert phases["execute"]["p99_ms"] >= phases["execute"]["p50_ms"]
    assert phases["queue"]["p50_ms"] >= 0

    # built-in phase histograms feed /metrics
    from ray_tpu.util.metrics import prometheus_text

    text = prometheus_text()
    assert 'rtpu_task_phase_seconds_bucket' in text
    assert 'phase="execute"' in text
    assert "rtpu_tasks_finished_total" in text

    # the driver's ring is bounded
    assert _get_runtime().task_ring.maxlen is not None


def test_timeline_contains_nested_lifecycle_slices(rt_telemetry, tmp_path):
    @ray_tpu.remote
    def traced():
        time.sleep(0.01)
        return 1

    assert ray_tpu.get([traced.remote() for _ in range(3)],
                       timeout=60) == [1, 1, 1]

    out = tmp_path / "trace.json"
    events = ray_tpu.timeline(str(out))
    # loadable Chrome-trace JSON: an array of complete ("X") events with
    # microsecond timestamps and durations
    loaded = json.loads(out.read_text())
    assert isinstance(loaded, list) and loaded
    tasks = [e for e in loaded if e["name"] == "traced" and e["ph"] == "X"]
    assert len(tasks) >= 3
    for e in tasks:
        assert set(e) >= {"name", "ph", "ts", "dur", "pid", "tid"}
    nested = [e for e in loaded if e.get("cat") == "task_phase"
              and e["name"].startswith("traced:")]
    assert {e["name"] for e in nested} >= {"traced:execute"}
    # each nested slice nests INSIDE its task slice on the same lane
    for e in nested:
        parent = next(p for p in tasks if p["tid"] == e["tid"]
                      and p["ts"] <= e["ts"] + 1
                      and e["ts"] + e["dur"] <= p["ts"] + p["dur"] + 1000)
        assert parent["ph"] == "X"
    assert events == loaded


def test_worker_metrics_federate_to_driver(rt_telemetry):
    """Samples recorded INSIDE worker processes (built-ins + user metrics
    created in tasks) appear on the driver's exposition with worker_id/
    node_id/component labels."""

    @ray_tpu.remote
    def busy(i):
        from ray_tpu.util.metrics import Counter

        Counter("user_task_metric_total", "created inside a task").inc()
        time.sleep(0.05)
        return i

    assert ray_tpu.get([busy.remote(i) for i in range(8)],
                       timeout=60) == list(range(8))

    from conftest import poll_until
    from ray_tpu.dashboard import start_dashboard, stop_dashboard

    dash = start_dashboard(port=0)
    import urllib.request

    url = f"http://127.0.0.1:{dash.port}/metrics"
    try:
        def scrape():
            txt = urllib.request.urlopen(url, timeout=5).read().decode()
            wids = set(re.findall(
                r'rtpu_worker_tasks_total\{[^}]*worker_id="(\w+)"', txt))
            return txt if (len(wids) >= 2
                           and "user_task_metric_total{" in txt) else None

        txt = poll_until(scrape, timeout=30,
                         desc=">=2 worker origins on /metrics")
    finally:
        stop_dashboard()
    assert 'component="worker"' in txt
    assert re.search(r'rtpu_worker_tasks_total\{[^}]*node_id="\w+"', txt)
    # worker exec-time histogram federated too
    assert "rtpu_worker_task_exec_seconds_bucket{" in txt


# ---------------------------------------------------------------------------
# job submission REST (ISSUE 4 satellite, reference job_head.py role)
# ---------------------------------------------------------------------------


def test_job_rest_submit_status_logs_stop(rt_telemetry):
    import json
    import urllib.request

    from conftest import poll_until
    from ray_tpu.dashboard import start_dashboard, stop_dashboard

    dash = start_dashboard(port=0)
    base = f"http://127.0.0.1:{dash.port}"
    try:
        def post(path, body=None):
            def once():
                req = urllib.request.Request(
                    base + path,
                    data=json.dumps(body or {}).encode(),
                    headers={"Content-Type": "application/json"},
                    method="POST")
                return json.loads(
                    urllib.request.urlopen(req, timeout=15).read())
            return poll_until(once, timeout=30, desc=f"POST {path}")

        def get(path):
            def once():
                return json.loads(urllib.request.urlopen(
                    base + path, timeout=15).read())
            return poll_until(once, timeout=30, desc=f"GET {path}")

        # submit -> terminal SUCCEEDED -> logs round-trip
        job_id = post("/api/jobs", {
            "entrypoint": "echo rest-job-output"})["result"]["job_id"]

        def done():
            info = get(f"/api/jobs/{job_id}")["result"]
            return info if info["status"] in ("SUCCEEDED", "FAILED",
                                              "STOPPED") else None

        info = poll_until(done, timeout=90, desc="job terminal")
        assert info["status"] == "SUCCEEDED"
        logs = get(f"/api/jobs/{job_id}/logs")["result"]["logs"]
        assert "rest-job-output" in logs
        assert any(j["job_id"] == job_id
                   for j in get("/api/jobs")["result"])

        # a long-running job stops via the REST stop route
        jid2 = post("/api/jobs",
                    {"entrypoint": "sleep 60"})["result"]["job_id"]

        def running():
            info = get(f"/api/jobs/{jid2}")["result"]
            return info["status"] == "RUNNING" or None

        poll_until(running, timeout=90, desc="job running")
        assert post(f"/api/jobs/{jid2}/stop")["result"]["stopped"]

        def stopped():
            return get(f"/api/jobs/{jid2}")["result"][
                "status"] == "STOPPED" or None

        poll_until(stopped, timeout=90, desc="job stopped")

        # unknown job ids are 404s, and a metrics scrape on the SAME
        # threaded server works while job routes are in use
        try:
            urllib.request.urlopen(base + "/api/jobs/nope", timeout=15)
            assert False, "expected 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404
        txt = urllib.request.urlopen(base + "/metrics",
                                     timeout=15).read().decode()
        assert "rtpu_scheduler_ready_queue_depth" in txt
    finally:
        stop_dashboard()


# ---------------------------------------------------------------------------
# train step telemetry
# ---------------------------------------------------------------------------


def test_step_telemetry_records_metrics():
    from ray_tpu.train.telemetry import StepTelemetry
    from ray_tpu.util.metrics import clear_registry, prometheus_text

    clear_registry()
    t = StepTelemetry()
    t.record_step(0.1, tokens=1000, loss=2.5)
    t.record_step(0.2, tokens=1000, mfu=0.31)
    t.record_compile(3.0)
    snap = t.snapshot()
    assert snap["steps"] == 2
    assert snap["tokens_per_s"] == 5000.0
    assert snap["mfu"] == 0.31
    assert snap["compiles"] == 1
    text = prometheus_text()
    assert "rtpu_train_step_seconds_count 2" in text
    assert "rtpu_train_tokens_per_s 5000.0" in text
    assert "rtpu_train_mfu 0.31" in text
    assert "rtpu_train_compile_total 1.0" in text
    assert "rtpu_train_loss 2.5" in text
    clear_registry()


def test_step_telemetry_on_report_interval():
    from ray_tpu.train.telemetry import StepTelemetry

    t = StepTelemetry()
    t.on_report({"loss": 1.0})          # first report: arms the clock
    time.sleep(0.05)
    t.on_report({"loss": 0.5, "tokens_per_s": 100.0})
    snap = t.snapshot()
    assert snap["steps"] == 1
    assert snap["step_time_s"] >= 0.04
    assert snap["loss"] == 0.5
    assert snap["tokens_per_s"] > 0


def test_train_loop_helper_records_compile_event():
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.train import TrainLoopHelper
    from ray_tpu.train.telemetry import get_step_telemetry
    from ray_tpu.parallel import MeshConfig

    helper = TrainLoopHelper.create(
        lambda: {"w": jnp.ones((4, 4))},
        {"w": (None, None)},
        lambda p, b: ((b["x"] @ p["w"]).sum() ** 2, {}),
        optax.sgd(1e-2),
        mesh_config=MeshConfig(dp=1, fsdp=-1, tp=1, sp=1),
    )
    before = get_step_telemetry().snapshot().get("compiles", 0)
    batch = {"x": jnp.ones((8, 4))}
    helper.run_steps(batch, 2)   # fresh scanned program -> compile event
    helper.run_steps(batch, 2)   # cached -> no new event
    after = get_step_telemetry().snapshot().get("compiles", 0)
    assert after == before + 1
