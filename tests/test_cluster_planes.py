"""The federated planes across nodes: task events, metrics, traces,
profiles, logs, the object directory dump and device reports at the head
(moved from test_cluster.py letter for letter)."""

import time

import numpy as np
import pytest

import ray_tpu

from conftest import _init, _wait_nodes, poll_until

def test_task_events_ship_to_gcs_cluster_wide(cluster):
    """Task events from EVERY node land in the GCS store: the state API
    lists tasks that ran on peer daemons too (reference TaskEventBuffer ->
    GcsTaskManager pipeline; VERDICT missing #8)."""
    cluster.add_node(num_cpus=2, resources={"peer": 2})
    _init(cluster)
    _wait_nodes(2)

    @ray_tpu.remote(resources={"peer": 1})
    def remote_side():
        return 1

    @ray_tpu.remote(num_cpus=1)
    def local_side():
        return 2

    assert ray_tpu.get([remote_side.remote() for _ in range(3)]
                       + [local_side.remote()], timeout=60) == [1, 1, 1, 2]

    from conftest import poll_until
    from ray_tpu.util.state import list_tasks, summarize_tasks

    def _names():  # events flush on the heartbeat; polls retry transient
        names = {}
        for t in list_tasks():
            names.setdefault(t["name"], set()).add(t["node"])
        ok = (len(names.get("remote_side", ())) >= 1
              and len(names.get("local_side", ())) >= 1)
        return names if ok else None

    names = poll_until(_names, timeout=20, interval=0.5,
                       desc="task events from both nodes in the GCS")
    assert "remote_side" in names and "local_side" in names
    # the two task kinds executed on DIFFERENT nodes
    assert names["remote_side"] != names["local_side"]
    assert summarize_tasks()["remote_side"]["FINISHED"] >= 3


def test_metrics_federation_across_nodes(cluster, monkeypatch):
    """ISSUE 3 acceptance: the head /metrics endpoint exposes samples
    originating from >= 2 distinct worker processes AND >= 2 cluster
    nodes, each carrying node_id/worker_id labels — scraped live over
    HTTP. The full pipeline: worker registries push deltas over the
    control pipe; node registries (plus their workers') ride the GCS
    heartbeat; the head pulls peers' at scrape time."""
    import re
    import urllib.request

    from conftest import poll_until

    monkeypatch.setenv("RTPU_METRICS_PUSH_INTERVAL_S", "0.2")
    cluster.add_node(num_cpus=2, resources={"peer": 2})
    _init(cluster)
    _wait_nodes(2)

    @ray_tpu.remote(resources={"peer": 1})
    def remote_side(i):
        time.sleep(0.2)
        return i

    @ray_tpu.remote(num_cpus=1)
    def local_side(i):
        time.sleep(0.2)
        return i

    # concurrency forces >= 2 workers on the head AND on the daemon
    out = ray_tpu.get([remote_side.remote(i) for i in range(4)]
                      + [local_side.remote(i) for i in range(4)],
                      timeout=60)
    assert sorted(out) == sorted(list(range(4)) * 2)

    from ray_tpu.dashboard import start_dashboard, stop_dashboard

    dash = start_dashboard(port=0)
    url = f"http://127.0.0.1:{dash.port}/metrics"
    try:
        def scrape():
            txt = urllib.request.urlopen(url, timeout=5).read().decode()
            wids, nids = set(), set()
            for m in re.finditer(r'rtpu_worker_tasks_total\{([^}]*)\}',
                                 txt):
                tags = dict(re.findall(r'(\w+)="([^"]*)"', m.group(1)))
                if tags.get("component") != "worker":
                    continue
                wids.add(tags.get("worker_id"))
                nids.add(tags.get("node_id"))
            wids.discard(None)
            nids.discard(None)
            return txt if (len(wids) >= 2 and len(nids) >= 2) else None

        # worker pushes (0.2s) -> daemon heartbeat metrics (~2s) -> GCS
        # -> head scrape; generous margin for the 2-vCPU box
        txt = poll_until(scrape, timeout=60, interval=0.5,
                         desc=">=2 workers and >=2 nodes on head /metrics")
    finally:
        stop_dashboard()

    # node-level (raylet/driver) registries federate too, with node ids
    assert re.search(r'component="raylet"', txt)
    # and phase histograms from the daemon's own flight recorder arrive
    # labeled with its node id
    assert re.search(
        r'rtpu_task_phase_seconds_count\{[^}]*node_id="\w+"', txt)


def test_core_runtime_metrics_from_all_layers_on_head(cluster,
                                                      monkeypatch):
    """ISSUE 4 acceptance: the head /metrics shows BUILT-IN core-runtime
    metrics from >= 2 nodes (scheduler + object store from the head,
    unlabeled, AND from the daemon, node_id-labeled) plus the GCS
    server's own instrumentation (component="gcs"): per-method RPC
    counters/latency, heartbeat-gap histogram, table sizes."""
    import re
    import urllib.request

    from conftest import poll_until

    monkeypatch.setenv("RTPU_METRICS_PUSH_INTERVAL_S", "0.2")
    cluster.add_node(num_cpus=2, resources={"peer": 2})
    _init(cluster)
    _wait_nodes(2)

    @ray_tpu.remote(resources={"peer": 1})
    def remote_side(i):
        return np.zeros(50_000), i  # big enough to hit the store

    @ray_tpu.remote(num_cpus=1)
    def local_side(i):
        return np.zeros(50_000), i

    out = ray_tpu.get([remote_side.remote(i) for i in range(3)]
                      + [local_side.remote(i) for i in range(3)],
                      timeout=60)
    assert sorted(x[1] for x in out) == [0, 0, 1, 1, 2, 2]

    from ray_tpu.dashboard import start_dashboard, stop_dashboard

    dash = start_dashboard(port=0)
    url = f"http://127.0.0.1:{dash.port}/metrics"
    try:
        def scrape():
            txt = urllib.request.urlopen(url, timeout=5).read().decode()
            ok = (
                # scheduler: head (unlabeled) + daemon (node-labeled)
                re.search(r"^rtpu_scheduler_tasks_dispatched_total \d",
                          txt, re.M)
                and re.search(r'rtpu_scheduler_tasks_dispatched_total\{'
                              r'[^}]*node_id="\w+"', txt)
                # object store: both origins again
                and re.search(r"^rtpu_object_store_bytes_used \d",
                              txt, re.M)
                and re.search(r'rtpu_object_store_bytes_used\{'
                              r'[^}]*node_id="\w+"', txt)
                # GCS process instrumentation arrives via metrics_get
                and re.search(r'rtpu_gcs_rpc_total\{[^}]*'
                              r'component="gcs"[^}]*'
                              r'method="node_heartbeat"', txt)
                and re.search(r'rtpu_gcs_heartbeat_gap_seconds_count\{'
                              r'[^}]*component="gcs"', txt)
                and re.search(r'rtpu_gcs_table_size\{[^}]*'
                              r'table="objects"', txt)
            )
            return txt if ok else None

        # worker pushes (0.2s) -> daemon heartbeat (~2s) -> GCS -> head
        txt = poll_until(scrape, timeout=60, interval=0.5,
                         desc="scheduler/store/GCS built-ins on head "
                              "/metrics")
    finally:
        stop_dashboard()

    # spillback decisions surfaced with a reason label
    assert re.search(
        r'rtpu_cluster_tasks_forwarded_total\{[^}]*reason="\w+"', txt)
    # the GCS's state-lock contention accounting federates too
    assert re.search(r'rtpu_lock_acquisitions\{[^}]*component="gcs"'
                     r'[^}]*lock="gcs.state"', txt) or \
        re.search(r'rtpu_lock_acquisitions\{[^}]*lock="gcs.state"', txt)


def test_memory_dump_lists_cluster_objects(cluster):
    """`ray_tpu memory` / GCS obj_list: directory dump with pin counts
    (reference `ray memory` refcount-dump role)."""
    _init(cluster)
    refs = [ray_tpu.put(np.ones(1 << 15)) for _ in range(3)]
    from ray_tpu.cluster.rpc import RpcClient

    cli = RpcClient(cluster.address, cluster.authkey.encode())

    def _big_rows():
        # a put tells the directory with a cast: the rows arrive after
        # put() has returned
        rows = cli.call("obj_list", 100, timeout=10)
        big = [r for r in rows if (r["size"] or 0) >= (1 << 15) * 8]
        return big if len(big) >= 3 else None

    try:
        big = poll_until(_big_rows, timeout=15,
                         desc="3 put objects in the GCS directory")
    finally:
        cli.close()
    assert all(r["pins"] >= 1 and r["status"] == "READY" for r in big)
    del refs


def test_task_events_dedup_on_cursor_rewind(cluster):
    """A node that re-registers rewinds its event cursor to 0 and reships
    history; the GCS drops events below its per-node high-water mark
    (advisor r3: duplicated task events in the state API)."""
    from ray_tpu.cluster.rpc import RpcClient

    cli = RpcClient(cluster.address, cluster.authkey.encode())
    try:
        nid = b"\x01" * 16
        evs = [{"name": f"t{i}", "ts": i} for i in range(5)]
        assert cli.call("task_events", nid, evs, 0, timeout=10)
        # cursor rewind after re-register: same 5 events again from seq 0,
        # plus 2 genuinely new ones
        evs2 = evs + [{"name": "t5", "ts": 5}, {"name": "t6", "ts": 6}]
        assert cli.call("task_events", nid, evs2, 0, timeout=10)
        got = [e for e in cli.call("task_events_get", 100, timeout=10)
               if e["node"] == nid.hex()[:8]]
        names = [e["name"] for e in got]
        assert names == [f"t{i}" for i in range(7)], names
    finally:
        cli.close()


def test_trace_spans_cross_processes_and_nodes(cluster):
    """ISSUE 7: one trace id spans >= 3 processes (driver submit ->
    worker execute -> nested submit -> second worker) and >= 2 nodes,
    collected over worker pipe pushes + GCS-heartbeat shipping. Tracing
    is armed MID-SESSION, so the daemon (booted un-armed) must learn via
    the KV/pubsub push and relay to its workers (satellite fix)."""
    from ray_tpu.util import state, tracing

    cluster.add_node(num_cpus=2, resources={"side": 2})
    _init(cluster)
    tracing.enable_tracing()
    try:
        @ray_tpu.remote(resources={"side": 1})
        def traced_inner(x):
            return x + 1

        @ray_tpu.remote(resources={"side": 1})
        def traced_outer():
            return ray_tpu.get(traced_inner.remote(1), timeout=60)

        assert ray_tpu.get(traced_outer.remote(), timeout=90) == 2

        def full_trace():
            # fresh work keeps worker pushes + heartbeats flowing
            try:
                ray_tpu.get(traced_outer.remote(), timeout=90)
                spans = state.list_spans(limit=100_000)
            except ConnectionError:
                return None
            outers = [s for s in spans
                      if s["name"] == "execute::traced_outer"]
            for o in reversed(outers):
                trace = [s for s in spans
                         if s["trace_id"] == o["trace_id"]]
                if not any(s["name"] == "execute::traced_inner"
                           for s in trace):
                    continue
                pids = {(s.get("attributes") or {}).get("process.pid")
                        for s in trace}
                nodes = {s.get("node_id") for s in trace
                         if s.get("node_id")}
                if len(pids - {None}) >= 3 and len(nodes) >= 2:
                    return trace
            return None

        deadline = time.monotonic() + 90
        trace = None
        while time.monotonic() < deadline and trace is None:
            trace = full_trace()
            if trace is None:
                time.sleep(0.5)
        assert trace is not None, \
            "no trace spanning >=3 processes and >=2 nodes arrived"
        # the nested submit happened INSIDE the outer execute
        outer_exec = next(s for s in trace
                          if s["name"] == "execute::traced_outer")
        inner_sub = [s for s in trace
                     if s["name"] == "submit::traced_inner"]
        assert inner_sub
        assert inner_sub[0]["parent_span_id"] == outer_exec["span_id"]
    finally:
        tracing.disable_tracing()
        tracing._reset_for_tests()
        import os as _os
        _os.environ.pop("RTPU_TRACING", None)


def test_profile_merges_nodes_and_pids_with_components(cluster):
    """ISSUE 9 acceptance: one state.profile() merge contains stacks
    from >= 2 nodes and >= 3 pids with correct component labels —
    worker batches over control-pipe pushes, the daemon's own sampler
    window over GCS-heartbeat ProfileStore deltas, the head's locally.
    Armed MID-SESSION, so the daemon (booted un-armed) must learn via
    the KV/pubsub push and relay to its workers."""
    from conftest import poll_until
    from ray_tpu.util import profiling, state

    cluster.add_node(num_cpus=2, resources={"side": 2})
    _init(cluster)
    _wait_nodes(2)
    profiling.enable_profiling()
    try:
        @ray_tpu.remote(resources={"side": 1})
        def spin_side(sec):
            t = time.monotonic() + sec
            x = 0
            while time.monotonic() < t:
                x += 1
            return x

        @ray_tpu.remote(num_cpus=1)
        def spin_local(sec):
            t = time.monotonic() + sec
            x = 0
            while time.monotonic() < t:
                x += 1
            return x

        # warm both nodes' workers so arming reached them
        ray_tpu.get([spin_side.remote(0.05), spin_local.remote(0.05)],
                    timeout=60)

        def merged_wide_enough():
            # fresh short spins keep worker pushes + heartbeats flowing
            ray_tpu.get([spin_side.remote(0.4), spin_local.remote(0.4)],
                        timeout=60)
            prof = state.profile()
            procs = prof["processes"]
            nodes = {p["node_id"] for p in procs.values()}
            pids = {(p["node_id"], p["pid"]) for p in procs.values()}
            comps = {p["component"] for p in procs.values()}
            top_w = prof["top_self_by_component"].get("worker", [])
            if len(nodes) >= 2 and len(pids) >= 3 \
                    and {"driver", "worker", "raylet"} <= comps \
                    and any("spin_" in r["function"] for r in top_w):
                return prof
            return None

        prof = poll_until(merged_wide_enough, timeout=90, interval=0.5,
                          desc="profile merge spanning >=2 nodes, "
                               ">=3 pids, driver+worker components")
        procs = prof["processes"]
        # component labels are correct per origin: worker batches carry
        # worker@, the daemon's own sampler reports raylet@, the head
        # driver@ — and every process row carries actual samples
        for key, p in procs.items():
            assert key.startswith(f"{p['component']}@")
            assert p["samples"] + p["idle_samples"] > 0
        assert any(p["component"] == "raylet" for p in procs.values()), \
            "daemon's own sampler batches never arrived via heartbeat"
    finally:
        profiling.disable_profiling()
        profiling._reset_for_tests()
        import os as _os
        _os.environ.pop("RTPU_PROFILING", None)


# ---------------------------------------------------------------------------
# event plane (ISSUE 18): death events with postmortems at the head,
# cluster-wide log federation
# ---------------------------------------------------------------------------


def test_fetch_logs_cross_node_by_task_id(cluster):
    """Log federation: a task id resolves (via its death event) to the
    worker that ran it on a PEER node; the fetch rendezvous brings back
    that node's log tail with the error lines extracted — the
    `rtpu logs --task` backend."""
    from ray_tpu.util import state

    cluster.add_node(num_cpus=2, resources={"faraway": 1})
    _init(cluster)
    _wait_nodes(2)

    @ray_tpu.remote(resources={"faraway": 1}, max_retries=0)
    def remote_crash():
        import os as _os
        import signal as _signal
        import sys as _sys

        _sys.stderr.write("KeyError: federated log marker 456\n")
        _sys.stderr.flush()
        _os.kill(_os.getpid(), _signal.SIGKILL)

    # three waits in a row, a few times the 2 s the passing run takes in
    # all, and together inside the test's limit
    with pytest.raises(Exception):
        ray_tpu.get(remote_crash.remote(), timeout=40)

    ev = poll_until(
        lambda: next((e for e in state.list_events(limit=100000)
                      if e["name"] == "worker_death"
                      and e.get("task") == "remote_crash"), None),
        timeout=30, interval=0.5, desc="remote death event at head")
    assert ev.get("task_id") and ev.get("worker_id")

    def _fetch():
        rows = state.fetch_logs({"task_id": ev["task_id"]}, timeout=10.0)
        return rows or None

    rows = poll_until(_fetch, timeout=30, interval=1.0,
                      desc="cross-node log fetch by task id")
    head_node = state._gcs().node_id.hex()[:8]
    assert rows[0]["node_id"] != head_node  # came from the peer
    assert "federated log marker 456" in rows[0]["tail"]
    assert any("KeyError" in ln for ln in rows[0]["error_lines"])


def test_device_report_federates_across_nodes(cluster, monkeypatch,
                                              capsys):
    """ISSUE 19 acceptance: ``state.device_report()`` on the head merges
    compiled-program registries from >= 2 nodes and >= 3 processes with
    component labels, and both surfaces (``/api/devices`` + ``rtpu
    devices``) render it. Pipeline: worker registries cast version-gated
    "device" snapshots over the control pipe; node stores ride the GCS
    heartbeat as idempotent per-node payloads; the head merges local +
    peers at read time."""
    import json
    import urllib.request

    monkeypatch.setenv("RTPU_DEVICE_PUSH_INTERVAL_S", "0.2")
    cluster.add_node(num_cpus=2, resources={"peer": 2})
    _init(cluster)
    _wait_nodes(2)

    # the driver registers a program of its own (process #1)
    import jax.numpy as jnp

    from ray_tpu.util import device_plane

    drv = device_plane.registered_jit(lambda x: x * 3.0,
                                      name="probe::driver",
                                      component="test")
    drv(jnp.ones((8,)))

    def _probe_body(name):
        import os as _os

        import jax as _jax

        _jax.config.update("jax_platforms", "cpu")
        import jax.numpy as _jnp

        from ray_tpu.util import device_plane as _dp

        f = _dp.registered_jit(lambda x: x * 2.0, name=name,
                               component="test")
        _jax.block_until_ready(f(_jnp.ones((8,))))
        return _os.getpid()

    @ray_tpu.remote(resources={"peer": 1})
    def remote_probe():
        return _probe_body("probe::remote")

    @ray_tpu.remote(num_cpus=1)
    def local_probe():
        return _probe_body("probe::local")

    pids = ray_tpu.get([remote_probe.remote(), local_probe.remote()],
                       timeout=60)
    assert len(set(pids)) == 2  # a worker process on each node

    from ray_tpu.util import state

    def _report():  # worker push (0.2s) -> heartbeat (~2s) -> GCS -> head
        rep = state.device_report()
        names = {r.get("program") for r in rep["programs"]}
        if not {"probe::driver", "probe::remote",
                "probe::local"} <= names:
            return None
        nids = {r.get("node_id") for r in rep["programs"]}
        procs = {(p.get("node_id"), p.get("pid"))
                 for p in rep["processes"]}
        comps = {p.get("component") for p in rep["processes"]}
        ok = (len(nids) >= 2 and len(procs) >= 3
              and {"driver", "worker"} <= comps)
        return rep if ok else None

    rep = poll_until(_report, timeout=60, interval=0.5,
                     desc="device report merges 2 nodes / 3 pids")
    assert rep["totals"]["processes"] >= 3
    assert rep["totals"]["compiles"] >= 3
    by_name = {r["program"]: r for r in rep["programs"]}
    assert by_name["probe::remote"]["component"] == "worker"
    head_node = state._gcs().node_id.hex()[:8]
    assert by_name["probe::remote"]["node_id"] != head_node
    assert by_name["probe::driver"]["node_id"] == head_node

    # both render surfaces over a live dashboard
    from ray_tpu.dashboard import start_dashboard, stop_dashboard

    dash = start_dashboard(port=0)
    url = f"http://127.0.0.1:{dash.port}"
    try:
        api = json.loads(urllib.request.urlopen(
            url + "/api/devices", timeout=10).read().decode())["result"]
        assert api["totals"]["processes"] >= 3
        assert {r["program"] for r in api["programs"]} >= {
            "probe::driver", "probe::remote", "probe::local"}

        import argparse

        from ray_tpu.scripts import _cmd_devices

        rc = _cmd_devices(argparse.Namespace(url=url, limit=50,
                                             census=True))
        out = capsys.readouterr().out
        assert rc == 0
        assert "probe::remote" in out and "probe::driver" in out
        assert "process(es)" in out
    finally:
        stop_dashboard()
