"""Multi-node faults: node and worker death, retries, GCS restarts, the
RPC wire handshake (moved from test_cluster.py letter for letter)."""

import time

import pytest

import ray_tpu
from ray_tpu.cluster import Cluster

from conftest import _init, _wait_nodes, poll_until

def test_node_death_retries_task_elsewhere(cluster):
    """Kill a node mid-task: retryable tasks re-run on a surviving node."""
    victim = cluster.add_node(num_cpus=2, resources={"pool": 4})
    cluster.add_node(num_cpus=2, resources={"pool": 4})
    _init(cluster)

    @ray_tpu.remote(resources={"pool": 1}, max_retries=2)
    def slow(i):
        import os
        import time as _t

        _t.sleep(3.0)
        return (i, os.getpid())

    refs = [slow.remote(i) for i in range(4)]
    time.sleep(1.0)  # let tasks start on both nodes
    cluster.kill_node(victim)
    results = ray_tpu.get(refs, timeout=60)
    assert sorted(r[0] for r in results) == [0, 1, 2, 3]


def test_node_death_fails_nonretryable(cluster):
    victim = cluster.add_node(num_cpus=2, resources={"solo": 1})
    _init(cluster)

    @ray_tpu.remote(resources={"solo": 1}, max_retries=0)
    def stuck():
        import time as _t

        _t.sleep(30)

    ref = stuck.remote()
    time.sleep(1.5)
    cluster.kill_node(victim)
    from ray_tpu.core.exceptions import WorkerCrashedError

    with pytest.raises(WorkerCrashedError):
        ray_tpu.get(ref, timeout=60)


def test_gcs_restart_fault_tolerance(tmp_path):
    """Kill + restart the GCS: durable tables (KV, named actors) survive
    via the snapshot; node daemons re-register via heartbeat NACK; new
    work schedules (reference GCS fault tolerance,
    gcs/store_client/redis_store_client.h role)."""
    c = Cluster(gcs_snapshot=str(tmp_path / "gcs.snap"))
    try:
        c.add_node(num_cpus=2, resources={"worker": 2})
        rt = _init(c)

        @ray_tpu.remote(resources={"worker": 1})
        def ping():
            return "pong"

        assert ray_tpu.get(ping.remote(), timeout=60) == "pong"
        rt.kv_op("put", "durable-key", b"survives")
        time.sleep(1.5)  # let the snapshot loop persist

        c.restart_gcs()

        # KV survived the restart
        val = poll_until(lambda: rt.kv_op("get", "durable-key"),
                         timeout=30, interval=0.5,
                         desc="durable KV after GCS restart")
        assert val == b"survives"

        # nodes re-registered: remote work schedules again
        ok = poll_until(
            lambda: ray_tpu.get(ping.remote(), timeout=20) == "pong",
            timeout=60, interval=0.5,
            desc="remote task schedules after GCS restart")
        assert ok, "remote task did not schedule after GCS restart"

        # the daemon's re-registration left a gcs_restart lifecycle
        # event (warning severity) in the head store — the event plane's
        # record that cluster state was rebuilt from the snapshot
        from ray_tpu.util import state

        restarts = poll_until(
            lambda: [e for e in state.list_events(limit=10000)
                     if e["name"] == "gcs_restart"],
            timeout=90, interval=0.5, desc="gcs_restart event collected")
        assert restarts[0]["severity"] == "warning"
    finally:
        ray_tpu.shutdown()
        c.shutdown()


def test_pg_node_death_releases_and_reschedules(cluster):
    """Killing a node releases its bundles; the group reschedules them on
    a surviving node and parked bundle-pinned work completes there."""
    victim = cluster.add_node(num_cpus=2, resources={"slot": 1})
    _init(cluster)
    _wait_nodes(2)
    from ray_tpu.util.placement_group import placement_group
    from ray_tpu.util.scheduling_strategies import (
        PlacementGroupSchedulingStrategy,
    )

    pg = placement_group([{"CPU": 1}], strategy="PACK")
    # bundle 0 must be on the daemon? PACK picks the roomiest node --
    # force it by reserving a slot resource only the daemon has
    from ray_tpu.util.placement_group import remove_placement_group

    remove_placement_group(pg)
    pg = placement_group([{"CPU": 1, "slot": 1}], strategy="PACK")

    @ray_tpu.remote
    def where():
        from ray_tpu.core.runtime import _get_runtime

        return _get_runtime().store.session

    strat = PlacementGroupSchedulingStrategy(
        placement_group=pg, placement_group_bundle_index=0)
    on_daemon = ray_tpu.get(where.options(scheduling_strategy=strat).remote(),
                            timeout=90)

    # a second daemon with the slot resource joins, then the first dies
    cluster.add_node(num_cpus=2, resources={"slot": 1})
    _wait_nodes(3)
    cluster.kill_node(victim)

    # the group reschedules onto the survivor; pinned work completes there
    deadline = time.monotonic() + 90
    landed = None
    while time.monotonic() < deadline:
        try:
            landed = ray_tpu.get(
                where.options(scheduling_strategy=strat).remote(),
                timeout=30)
            break
        except Exception:
            time.sleep(0.5)
    assert landed is not None and landed != on_daemon


def test_cancel_routes_to_remote_node(cluster, tmp_path):
    """Cancelling a ref whose task was forwarded to a peer node must stop
    the REMOTE worker (ADVICE r2 medium: the fallback used to mark the
    object cancelled while the task kept running on the peer)."""
    cluster.add_node(num_cpus=2, resources={"worker": 1})
    _init(cluster)
    _wait_nodes(2)
    marker = str(tmp_path / "remote-spinning")

    @ray_tpu.remote(resources={"worker": 1})
    def spin(path):
        open(path, "w").close()
        import time as _t

        t0 = _t.monotonic()
        while _t.monotonic() - t0 < 60:
            pass
        return "finished"

    import os

    ref = spin.remote(marker)
    deadline = time.monotonic() + 60
    while not os.path.exists(marker):
        assert time.monotonic() < deadline, "remote task never started"
        time.sleep(0.05)
    t0 = time.monotonic()
    ray_tpu.cancel(ref)
    from ray_tpu.core.exceptions import TaskCancelledError

    with pytest.raises(TaskCancelledError):
        ray_tpu.get(ref, timeout=45)
    assert time.monotonic() - t0 < 30, "remote cancel did not interrupt"


def test_rpc_wire_version_handshake():
    """Versioned wire contract (reference protobuf schema role): matching
    majors connect and carry calls; a major mismatch is refused with a
    clear WireVersionError at connect time."""
    import threading

    from multiprocessing.connection import Client as MpClient
    from multiprocessing.connection import Listener

    from ray_tpu.cluster.rpc import (RpcClient, RpcServer, WIRE_VERSION,
                                     WireVersionError, parse_addr)

    server = RpcServer("127.0.0.1", 0, b"k", lambda m, a, c: ("ok", m, a))
    try:
        # happy path: handshake succeeds, calls flow
        cli = RpcClient(server.addr, b"k")
        assert cli.server_wire_version == WIRE_VERSION
        assert cli.call("ping", 1, timeout=10) == ("ok", "ping", (1,))
        cli.close()

        # server refuses a future-major client with a nack
        conn = MpClient(parse_addr(server.addr), family="AF_INET",
                        authkey=b"k")
        conn.send(("hello", (WIRE_VERSION[0] + 1, 0)))
        assert conn.poll(10)
        reply = conn.recv()
        assert reply[0] == "hello_nack" and "wire major" in reply[2]
        conn.close()
    finally:
        server.close()

    # client raises WireVersionError when the server nacks
    lst = Listener(("127.0.0.1", 0), family="AF_INET", authkey=b"k")

    def fake_server():
        c = lst.accept()
        c.recv()
        c.send(("hello_nack", (9, 0), "wire major 1 != 9"))

    threading.Thread(target=fake_server, daemon=True).start()
    try:
        with pytest.raises(WireVersionError, match="refused"):
            RpcClient(f"127.0.0.1:{lst.address[1]}", b"k")
    finally:
        lst.close()


def test_rpc_handshake_malformed_hello_nacked():
    """('hello', 5) and non-hello first messages get a clean nack — the
    reader thread must not die with an uncaught TypeError (that leaks the
    conn and times the peer out with a misleading error)."""
    from multiprocessing.connection import Client as MpClient

    from ray_tpu.cluster.rpc import RpcServer, parse_addr

    server = RpcServer("127.0.0.1", 0, b"k", lambda m, a, c: None)
    try:
        for bad in (("hello", 5), ("hello", ()), ("req", 1, "x", ())):
            conn = MpClient(parse_addr(server.addr), family="AF_INET",
                            authkey=b"k")
            conn.send(bad)
            assert conn.poll(10)
            assert conn.recv()[0] == "hello_nack"
            conn.close()
    finally:
        server.close()


def test_gcs_sqlite_external_store_fault_tolerance(tmp_path):
    """VERDICT r4 #6 done-criterion: the GCS backed by an EXTERNAL sqlite
    store (redis_store_client.h role) survives kill -9 with named
    actors, KV, and placement groups intact — the store file can live on
    storage that outlives the head node's disk."""
    import os

    db = str(tmp_path / "external" / "gcs.db")
    c = Cluster(gcs_snapshot=f"sqlite://{db}")
    try:
        c.add_node(num_cpus=4, resources={"worker": 4})
        rt = _init(c)

        @ray_tpu.remote(resources={"worker": 1})
        class Counter:
            def __init__(self):
                self.n = 0

            def bump(self):
                self.n += 1
                return self.n

        a = Counter.options(name="survivor", lifetime="detached").remote()
        assert ray_tpu.get(a.bump.remote(), timeout=60) == 1
        rt.kv_op("put", "durable-key", b"sqlite-survives")
        from ray_tpu.util.placement_group import placement_group

        pg = placement_group([{"worker": 1}], strategy="PACK")
        assert pg.wait(timeout_seconds=30)
        time.sleep(1.5)  # let the snapshot loop persist
        assert os.path.exists(db)

        c.restart_gcs()  # kill -9 + fresh process reading the sqlite db

        val = poll_until(lambda: rt.kv_op("get", "durable-key"),
                         timeout=30, interval=0.5,
                         desc="durable KV after sqlite GCS restart")
        assert val == b"sqlite-survives"
        # named actor record survived: resolvable by name again
        deadline = time.monotonic() + 60
        got = None
        while time.monotonic() < deadline:
            try:
                h = ray_tpu.get_actor("survivor")
                got = ray_tpu.get(h.bump.remote(), timeout=20)
                break
            except Exception:
                time.sleep(0.5)
        assert got == 2, got
        # pg record survived the restart (read back from the GCS)
        pgs = poll_until(lambda: rt.cluster.gcs.call("pg_list", timeout=10),
                         timeout=30, interval=0.5,
                         desc="pg records after sqlite GCS restart")
        assert pgs, "placement group records lost after GCS restart"
    finally:
        ray_tpu.shutdown()
        c.shutdown()


def test_sqlite_store_client_unit(tmp_path):
    """Round trip, unchanged-table skip, and corrupt-row tolerance of the
    sqlite StoreClient (no cluster boot needed)."""
    import os
    import sqlite3

    from ray_tpu.cluster.gcs_store import (SqliteStoreClient,
                                           make_store_client)

    db = str(tmp_path / "t.db")
    s = make_store_client(f"sqlite://{db}")
    assert isinstance(s, SqliteStoreClient)
    snap = {"kv": {"ns": {"k": b"v"}}, "functions": {"h": b"blob"},
            "actors": {b"a": {"state": "ALIVE"}},
            "named_actors": {"n": b"a"}, "pgs": {}}
    s.save(snap)
    s.save(snap)  # unchanged: second save is a no-op (hash skip)
    s.close()

    s2 = SqliteStoreClient(db)
    got = s2.load()
    assert got["kv"] == snap["kv"] and got["named_actors"] == {"n": b"a"}
    s2.close()

    # corrupt ONE table row: the rest must still load
    conn = sqlite3.connect(db)
    conn.execute("UPDATE gcs_tables SET payload=? WHERE name='functions'",
                 (b"\x80garbage",))
    conn.commit()
    conn.close()
    s3 = SqliteStoreClient(db)
    got = s3.load()
    assert "functions" not in got and got["kv"] == snap["kv"]
    s3.close()

    # a corrupt/truncated db file must not block boot: it is set aside
    # and a fresh store opens (the file backend boots empty the same way)
    bad = str(tmp_path / "bad.db")
    with open(bad, "wb") as fh:
        fh.write(b"this is not a sqlite file at all")
    s4 = SqliteStoreClient(bad)
    assert s4.load() is None
    assert s4.save(snap) is True
    s4.close()
    assert os.path.exists(bad + ".corrupt")

    # file backend still the default for bare paths
    from ray_tpu.cluster.gcs_store import FileStoreClient

    f = make_store_client(str(tmp_path / "plain.snap"))
    assert isinstance(f, FileStoreClient)
    f.save(snap)
    assert f.load()["kv"] == snap["kv"]
    assert make_store_client(None) is None


def test_worker_sigkill_one_death_event_at_head(cluster):
    """A worker SIGKILLed on a PEER node produces exactly ONE
    worker_death event at the head — correct cause class, non-empty
    postmortem with the worker's stderr tail — shipped over the daemon
    heartbeat with the acked-cursor dedup contract."""
    from ray_tpu.util import state

    cluster.add_node(num_cpus=2, resources={"die": 1})
    cluster.add_node(num_cpus=2)
    _init(cluster)
    _wait_nodes(3)

    @ray_tpu.remote(resources={"die": 1}, max_retries=0)
    def victim():
        import os as _os
        import signal as _signal
        import sys as _sys

        _sys.stderr.write("OSError: cross-node death marker\n")
        _sys.stderr.flush()
        _os.kill(_os.getpid(), _signal.SIGKILL)

    from ray_tpu.core.exceptions import WorkerCrashedError

    with pytest.raises(WorkerCrashedError) as ei:
        ray_tpu.get(victim.remote(), timeout=60)
    assert ei.value.error_type == "worker_died:signal:SIGKILL"
    assert "cross-node death marker" in str(ei.value)

    deaths = poll_until(
        lambda: [e for e in state.list_events(limit=100000)
                 if e["name"] == "worker_death"
                 and e.get("task") == "victim"],
        timeout=60, interval=0.5, desc="worker_death event at head")
    # several heartbeats have passed by now: the cursor contract must
    # have deduped re-ships down to exactly one record
    time.sleep(2.0)
    deaths = [e for e in state.list_events(limit=100000)
              if e["name"] == "worker_death" and e.get("task") == "victim"]
    assert len(deaths) == 1, deaths
    ev = deaths[0]
    assert ev["cause"] == "signal:SIGKILL"
    assert ev["severity"] == "error"
    assert ev["component"] == "raylet"  # reaped by the peer's daemon
    pm = ev["postmortem"]
    assert pm["cause"] == "signal:SIGKILL"
    assert "cross-node death marker" in pm.get("stderr_tail", "")
    # node_register events from the GCS's own table rode along too
    assert sum(1 for e in state.list_events(limit=100000)
               if e["name"] == "node_register") >= 3


def test_daemon_kill_one_node_death_event(cluster):
    """SIGKILL a node daemon: after the heartbeat timeout the GCS emits
    exactly ONE node_death event whose postmortem records the blast
    radius (there is no process left to read a stderr tail from)."""
    from ray_tpu.util import state

    victim = cluster.add_node(num_cpus=2, resources={"doomed": 1})
    cluster.add_node(num_cpus=2)
    _init(cluster)
    _wait_nodes(3)

    # learn the victim's node id before killing it
    daemons = [n for n in cluster.list_nodes() if not n["is_head"]]
    victim_ids = {n["node_id"].hex()[:8] for n in daemons}
    cluster.kill_node(victim)

    deaths = poll_until(
        lambda: [e for e in state.list_events(limit=100000)
                 if e["name"] == "node_death"],
        timeout=60, interval=0.5,
        desc="node_death event after heartbeat timeout")
    assert len(deaths) == 1, deaths
    ev = deaths[0]
    assert ev["node_id"] in victim_ids
    assert ev["component"] == "gcs"
    assert ev["severity"] == "error"
    # SIGKILL closes the daemon's GCS conn (usually "connection lost");
    # a blip-less box may only notice at the heartbeat timeout
    assert ev["cause"] in ("connection lost", "heartbeat timeout")
    pm = ev["postmortem"]
    assert pm["cause"] == ev["cause"]
    assert {"lost_objects", "dead_actors",
            "lost_pg_bundles"} <= set(pm)
