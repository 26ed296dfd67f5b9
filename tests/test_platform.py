"""Platform services: state API, metrics, dashboard HTTP, job submission, CLI."""

import json
import time

import pytest

import ray_tpu


@pytest.fixture
def rt_plat():
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


def test_state_api_lists(rt_plat):
    from ray_tpu.util import state

    @ray_tpu.remote
    class A:
        def ping(self):
            return 1

    a = A.options(name="state_test_actor").remote()
    ray_tpu.get(a.ping.remote())

    actors = state.list_actors()
    assert any(rec["name"] == "state_test_actor" for rec in actors)
    assert state.summarize_actors().get("ALIVE", 0) >= 1

    @ray_tpu.remote
    def work():
        return 2

    ray_tpu.get([work.remote() for _ in range(3)])
    tasks = state.list_tasks()
    assert len(tasks) >= 3
    summary = state.summarize_tasks()
    assert sum(v.get("FINISHED", 0) for v in summary.values()) >= 3

    ref = ray_tpu.put(123)
    objs = state.list_objects()
    assert any(o["object_id"] == ref.id.hex() for o in objs)

    workers = state.list_workers()
    assert len(workers) >= 1

    filtered = state.list_actors(filters=[("name", "=", "state_test_actor")])
    assert len(filtered) == 1


def test_metrics_prometheus_text():
    from ray_tpu.util.metrics import (Counter, Gauge, Histogram,
                                      clear_registry, prometheus_text)

    clear_registry()
    c = Counter("rtpu_test_total", "test counter", tag_keys=("kind",))
    c.inc(2, tags={"kind": "a"})
    c.inc(3, tags={"kind": "b"})
    g = Gauge("rtpu_test_gauge", "test gauge")
    g.set(7.5)
    h = Histogram("rtpu_test_hist", "test hist", boundaries=[1, 10])
    h.observe(0.5)
    h.observe(5)
    h.observe(50)

    text = prometheus_text()
    assert 'rtpu_test_total{kind="a"} 2.0' in text
    assert "rtpu_test_gauge 7.5" in text
    assert "rtpu_test_hist_count 3" in text
    assert "rtpu_test_hist_sum 55.5" in text
    clear_registry()


def test_dashboard_endpoints(rt_plat):
    import http.client

    from ray_tpu.dashboard import Dashboard

    dash = Dashboard(port=0).start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", dash.port, timeout=10)
        conn.request("GET", "/api/summary/objects")
        resp = conn.getresponse()
        assert resp.status == 200
        data = json.loads(resp.read())["result"]
        assert "total" in data

        conn = http.client.HTTPConnection("127.0.0.1", dash.port, timeout=10)
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        assert resp.status == 200

        # "/" serves the single-page UI (reference dashboard client role)
        conn = http.client.HTTPConnection("127.0.0.1", dash.port, timeout=10)
        conn.request("GET", "/")
        resp = conn.getresponse()
        assert resp.status == 200
        body = resp.read().decode()
        assert "<html" in body and "/api/nodes" in body
        # UI views: drill-down panel, timeline swimlanes, metric sparklines
        assert "detail" in body and "timeline" in body and "spark" in body

        # /api/timeline returns the driver's Chrome-trace events (the
        # fixture ran tasks, so X spans exist)
        @ray_tpu.remote
        def one():
            return 1

        ray_tpu.get(one.remote())
        conn = http.client.HTTPConnection("127.0.0.1", dash.port, timeout=10)
        conn.request("GET", "/api/timeline")
        resp = conn.getresponse()
        assert resp.status == 200
        events = json.loads(resp.read())["result"]
        assert isinstance(events, list)
        assert any(e.get("ph") == "X" and e.get("dur", 0) > 0
                   for e in events)
    finally:
        dash.stop()


def test_job_submission_lifecycle(rt_plat, tmp_path):
    from ray_tpu.job_submission import JobStatus, JobSubmissionClient

    client = JobSubmissionClient()
    script = tmp_path / "job.py"
    script.write_text("print('hello from job'); print(6*7)\n")
    job_id = client.submit_job(entrypoint=f"python {script}")
    status = client.wait_until_finished(job_id, timeout=60)
    assert status == JobStatus.SUCCEEDED
    logs = client.get_job_logs(job_id)
    assert "hello from job" in logs and "42" in logs
    infos = client.list_jobs()
    assert any(i.job_id == job_id for i in infos)


def test_job_failure_recorded(rt_plat, tmp_path):
    from ray_tpu.job_submission import JobStatus, JobSubmissionClient

    client = JobSubmissionClient()
    job_id = client.submit_job(entrypoint="python -c 'import sys; sys.exit(3)'")
    status = client.wait_until_finished(job_id, timeout=60)
    assert status == JobStatus.FAILED
    assert client.get_job_info(job_id).return_code == 3


def test_job_stop_kills_entrypoint(rt_plat):
    from ray_tpu.job_submission import JobStatus, JobSubmissionClient

    client = JobSubmissionClient()
    job_id = client.submit_job(entrypoint="sleep 600")
    # wait for the subprocess pgid to publish
    deadline = time.time() + 60
    while time.time() < deadline:
        info = client.get_job_info(job_id)
        if info.pgid:
            break
        time.sleep(0.1)
    assert info.pgid, "job never started"
    assert client.stop_job(job_id)
    assert client.get_job_status(job_id) == JobStatus.STOPPED
    # the entrypoint process group is gone
    import os, signal

    deadline = time.time() + 10
    gone = False
    while time.time() < deadline:
        try:
            os.killpg(info.pgid, 0)
            time.sleep(0.1)
        except ProcessLookupError:
            gone = True
            break
    assert gone, "entrypoint subprocess survived stop_job"


def test_cli_status_and_clean():
    from ray_tpu.scripts import main

    assert main(["status"]) == 0


def test_cli_has_no_bench_command(capsys):
    """The benchmark is ``python3 -m benchmark.run``; ``bench.py`` and the
    command that ran it are gone."""
    from ray_tpu.scripts import main

    with pytest.raises(SystemExit) as e:
        main(["bench"])
    assert e.value.code == 2
    assert "invalid choice: 'bench'" in capsys.readouterr().err


def test_cli_stack_dumps_worker_stacks(rt_plat):
    """ray_tpu stack (reference `ray stack`): SIGUSR1 + faulthandler dumps
    every worker thread's python stack into the session log."""
    import io
    import time
    from contextlib import redirect_stdout

    import ray_tpu
    from ray_tpu.scripts import main as cli_main

    @ray_tpu.remote
    def warm():
        return None

    ray_tpu.get([warm.remote() for _ in range(2)])  # workers fully booted

    @ray_tpu.remote
    def sleeper():
        time.sleep(6)
        return 1

    refs = [sleeper.remote() for _ in range(2)]
    time.sleep(1.0)  # sleepers running
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = cli_main(["stack"])
    out = buf.getvalue()
    assert rc == 0
    assert "signaled" in out
    assert "Current thread" in out  # a real stack dump was captured
    assert "sleeper" in out or "time.sleep" in out or "execute" in out
    ray_tpu.get(refs, timeout=30)


def test_tracing_spans_propagate_to_workers(tmp_path):
    """W3C-propagated task spans (reference tracing_helper role): driver
    submit spans and worker execute spans share one trace id across the
    process boundary; actor calls traced too."""
    import ray_tpu
    from ray_tpu.util import tracing

    trace_file = str(tmp_path / "traces.jsonl")
    tracing.enable_tracing(trace_file)
    try:
        ray_tpu.init(num_cpus=2, ignore_reinit_error=True)

        @ray_tpu.remote
        def traced_task(x):
            return x + 1

        assert ray_tpu.get(traced_task.remote(1), timeout=60) == 2

        @ray_tpu.remote
        class TracedActor:
            def m(self):
                return "ok"

        a = TracedActor.remote()
        assert ray_tpu.get(a.m.remote(), timeout=60) == "ok"

        deadline = time.time() + 30
        spans = []
        while time.time() < deadline:
            spans = tracing.read_trace_file(trace_file)
            if (any(s["name"] == "execute::traced_task" for s in spans)
                    and any(s["name"] == "execute::m" for s in spans)):
                break
            time.sleep(0.3)
        submit = next(s for s in spans if s["name"] == "submit::traced_task")
        execute = next(s for s in spans
                       if s["name"] == "execute::traced_task")
        assert execute["trace_id"] == submit["trace_id"]
        assert execute["parent_span_id"] == submit["span_id"]
        assert execute["attributes"]["process.pid"] != \
            submit["attributes"]["process.pid"]
        assert any(s["name"] == "submit::m" for s in spans)

        # nested submissions join the ENCLOSING task's trace
        @ray_tpu.remote
        def inner(x):
            return x * 10

        @ray_tpu.remote
        def outer():
            return ray_tpu.get(inner.remote(4))

        assert ray_tpu.get(outer.remote(), timeout=60) == 40
        deadline = time.time() + 30
        while time.time() < deadline:
            spans = tracing.read_trace_file(trace_file)
            # outer's span ends AFTER inner's and is written by another
            # worker: wait for all three, not for the first to land
            if {"execute::outer", "submit::inner", "execute::inner"} <= {
                    s["name"] for s in spans}:
                break
            time.sleep(0.3)
        outer_exec = next(s for s in spans if s["name"] == "execute::outer")
        inner_sub = next(s for s in spans if s["name"] == "submit::inner")
        inner_exec = next(s for s in spans if s["name"] == "execute::inner")
        assert inner_sub["trace_id"] == outer_exec["trace_id"]
        assert inner_sub["parent_span_id"] == outer_exec["span_id"]
        assert inner_exec["trace_id"] == outer_exec["trace_id"]
    finally:
        import os as _os

        _os.environ.pop("RTPU_TRACING", None)
        _os.environ.pop("RTPU_TRACE_FILE", None)
        tracing._state["enabled"] = None
        tracing._state["fd"] = None
        ray_tpu.shutdown()


def test_node_host_stats_reported(rt_plat):
    """Per-node host utilization (reference dashboard reporter module):
    nodes() carries a psutil sample; keys stay stable for the UI."""
    nodes = ray_tpu.nodes()
    stats = nodes[0].get("stats") or {}
    assert {"cpu_percent", "mem_used", "mem_total",
            "num_cpus"} <= set(stats)
    assert stats["mem_total"] > stats["mem_used"] > 0
