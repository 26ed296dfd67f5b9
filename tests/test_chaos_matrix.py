"""Chaos matrix (ISSUE 5 tentpole): real workloads driven through
deterministic failpoint injection, asserting correct results + recovery.

Every recovery mechanism the repo claims (task retries, actor restart,
lineage, node-death re-placement, GCS snapshot FT, Serve re-route, Data
exchange re-execution, Train checkpoint resume) keeps a failpoint armed
here as its regression test. Sites live in ``ray_tpu/util/failpoints.py``;
``RTPU_FAILPOINTS=0`` disables the whole plane.

Quick subset (tier-1, unmarked): worker kill mid-exec, store seal failure,
Serve replica death, compiled-DAG actor death. Everything else — including every multi-node case —
is ``slow``. Deadlines are generous (2-vCPU CI box, CLAUDE.md deflake
rules: retried transient-connection polls, no tight wall-clock asserts).
"""

import os
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.util import failpoints

from conftest import poll_until


@pytest.fixture
def chaos_rt(tmp_path):
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield tmp_path
    failpoints.disarm()
    ray_tpu.shutdown()


def _token(tmp_path, name):
    """Path for a cross-process at-most-once kill election (``once=``) —
    per-process ``times=`` would re-arm in every respawned worker."""
    return str(tmp_path / f"fp-{name}.tok")


def _events_named(name, **field_filters):
    """Head-visible lifecycle events matching ``name`` + field values
    (ISSUE 18: every chaos death case leaves exactly one death event
    with a correct cause class and a postmortem)."""
    from ray_tpu.util import state

    return [e for e in state.list_events(limit=100000)
            if e["name"] == name
            and all(e.get(k) == v for k, v in field_filters.items())]


# ---------------------------------------------------------------------------
# quick subset (tier-1): worker kill, seal failure, serve replica death,
# compiled-DAG actor death
# ---------------------------------------------------------------------------

def test_worker_kill_mid_exec_task_graph(chaos_rt):
    """SIGKILL a worker mid-task inside a lineage chain: the task re-runs
    on another worker (max_retries) and the dependent graph completes with
    the correct result."""
    failpoints.arm(
        f"worker.exec=kill@arg=square@once={_token(chaos_rt, 'kill1')}")

    @ray_tpu.remote(max_retries=2)
    def square(x):
        return x * x

    @ray_tpu.remote
    def total(*xs):
        return sum(xs)

    refs = [square.remote(i) for i in range(8)]
    assert ray_tpu.get(total.remote(*refs), timeout=60) == sum(
        i * i for i in range(8))

    # the kill left exactly ONE worker_death event (once= election),
    # with the right cause class and a postmortem from the reaping site
    deaths = poll_until(
        lambda: _events_named("worker_death", task="square"),
        timeout=30, desc="worker_death event for the killed square")
    assert len(deaths) == 1, deaths
    assert deaths[0]["cause"] == "signal:SIGKILL"
    assert deaths[0]["postmortem"]["cause"] == "signal:SIGKILL"


def test_store_seal_failure_retries_task(chaos_rt):
    """A failed object-store seal surfaces as the producing task's error;
    ``retry_exceptions`` resubmits it and the retry succeeds."""
    # once= (not times=1): the retry may land on a DIFFERENT worker whose
    # own per-process times budget would fire again and exhaust max_retries
    failpoints.arm(f"store.seal=raise@once={_token(chaos_rt, 'seal')}")

    @ray_tpu.remote(max_retries=2, retry_exceptions=True)
    def big():
        return np.arange(300_000, dtype=np.int64)  # too big to inline

    out = ray_tpu.get(big.remote(), timeout=60)
    assert out.shape == (300_000,) and int(out[-1]) == 299_999


def test_serve_replica_death_rerouted_and_replaced(chaos_rt):
    """Kill a Serve replica's worker mid-request under load: the handle
    re-routes the failed request to a live replica (no caller-visible
    error) and the controller reconciles a replacement replica."""
    from ray_tpu import serve

    @serve.deployment(num_replicas=2)
    class Echo:
        def __call__(self, x):
            return x * 2

    try:
        handle = serve.run(Echo.bind())
        assert handle.remote(1).result() == 2
        failpoints.arm("worker.exec=kill@arg=handle_request"
                       f"@once={_token(chaos_rt, 'serve')}")
        results = [handle.remote(i).result() for i in range(20)]
        assert results == [2 * i for i in range(20)]

        ctrl = ray_tpu.get_actor("SERVE_CONTROLLER")
        deps = poll_until(
            lambda: ray_tpu.get(ctrl.list_deployments.remote()),
            timeout=30, desc="controller view")
        assert deps["Echo"]["num_replicas"] == 2  # dead one was replaced

        # the controller (an actor: events ride its worker pipe) emitted
        # the replica death + the re-route fanout as lifecycle events
        dead = poll_until(
            lambda: _events_named("serve_replica_death", deployment="Echo"),
            timeout=30, desc="serve_replica_death event")
        assert len(dead) == 1, dead
        assert poll_until(
            lambda: _events_named("serve_reroute", deployment="Echo"),
            timeout=30, desc="serve_reroute event")
    finally:
        serve.shutdown()


def test_compiled_dag_actor_death_mid_loop(chaos_rt):
    """Kill an actor participating in a compiled DAG mid-loop: the next
    get() surfaces DAGExecutionError promptly (loop-ref death detection,
    not a channel-read timeout), the broken DAG refuses new admissions,
    and teardown unlinks every shm channel."""
    import os as _os

    from ray_tpu.dag import DAGExecutionError, InputNode

    @ray_tpu.remote
    class St:
        def bump(self, x):
            return x + 1

    a, b = St.remote(), St.remote()
    with InputNode() as inp:
        dag = b.bump.bind(a.bump.bind(inp))
    compiled = dag.experimental_compile(max_in_flight=4)
    paths = [ch.path for ch in compiled._channels]
    try:
        assert compiled.execute(1).get(timeout=60) == 3
        ray_tpu.kill(a)
        # wait for the death to land in the directory (the loop ref
        # resolves to ActorDiedError) so the race where stage `a` still
        # processes the next input can't make the test flake
        poll_until(
            lambda: len(ray_tpu.wait(
                compiled._loop_refs,
                num_returns=len(compiled._loop_refs), timeout=0.1)[0]) >= 1,
            timeout=30, desc="dead actor's exec-loop ref resolved")
        fut = compiled.execute(2)
        t0 = time.monotonic()
        with pytest.raises(DAGExecutionError):
            fut.get(timeout=60)
        assert time.monotonic() - t0 < 30, "death surfaced via timeout, " \
            "not detection"
        with pytest.raises(DAGExecutionError):
            compiled.execute(3)   # broken pipeline refuses new work

        # ray_tpu.kill() exhausts restarts: exactly one terminal
        # actor_death event for `a`, cause = the kill signal
        deaths = poll_until(
            lambda: _events_named("actor_death",
                                  actor_id=a._actor_id.hex()),
            timeout=30, desc="actor_death event for the killed stage")
        assert len(deaths) == 1, deaths
        assert deaths[0]["cause"].startswith("signal:")
        assert deaths[0]["postmortem"]["cause"] == deaths[0]["cause"]
    finally:
        compiled.teardown()
    assert not any(_os.path.exists(p) for p in paths), \
        "teardown left shm channels linked"


# ---------------------------------------------------------------------------
# single-node slow cases
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_retry_exceptions_resubmission_guard(chaos_rt):
    """An application error on the Nth execution resubmits (bounded); the
    result is published exactly once — consumers never observe the error
    of a retried attempt, and exhausted retries DO surface."""
    # once=+times=2 makes the failure budget GLOBAL (exactly 2 failed
    # executions, wherever the resubmitted attempts land) — a per-process
    # times=2 would re-fire on every fresh worker the retry lands on
    failpoints.arm("worker.exec.before_result=raise@times=2@arg=flaky"
                   f"@once={_token(chaos_rt, 'flaky')}")

    @ray_tpu.remote(max_retries=3, retry_exceptions=True)
    def flaky():
        return "ok"

    assert ray_tpu.get(flaky.remote(), timeout=120) == "ok"

    failpoints.arm("worker.exec.before_result=raise@times=10@arg=doomed")

    @ray_tpu.remote(max_retries=1, retry_exceptions=True)
    def doomed():
        return "never"

    with pytest.raises(Exception):
        ray_tpu.get(doomed.remote(), timeout=120)

    # opting in WITHOUT max_retries must not be silently inert: the
    # reference default budget (3) applies
    failpoints.arm("worker.exec.before_result=raise@arg=bare"
                   f"@times=1@once={_token(chaos_rt, 'bare')}")

    @ray_tpu.remote(retry_exceptions=True)
    def bare():
        return "ok"

    assert ray_tpu.get(bare.remote(), timeout=120) == "ok"

    # reference list form: only the NAMED exception types retry
    failpoints.arm("worker.exec.before_result=raise:ValueError@arg=picky"
                   f"@times=1@once={_token(chaos_rt, 'picky')}")

    @ray_tpu.remote(max_retries=3, retry_exceptions=[ValueError])
    def picky():
        return "ok"

    assert ray_tpu.get(picky.remote(), timeout=120) == "ok"

    failpoints.arm("worker.exec.before_result=raise:ValueError@arg=strict"
                   f"@times=1@once={_token(chaos_rt, 'strict')}")

    @ray_tpu.remote(max_retries=3, retry_exceptions=[KeyError])
    def strict():
        return "never"

    from ray_tpu.core.exceptions import TaskError

    with pytest.raises(TaskError):  # ValueError not in the list: surfaces
        ray_tpu.get(strict.remote(), timeout=120)


@pytest.mark.slow
def test_actor_herd_survives_worker_kill(chaos_rt):
    """An actor herd keeps serving through one member's SIGKILL: the dead
    actor restarts (max_restarts) and every herd member answers after."""
    failpoints.arm(
        f"worker.exec=kill@arg=bump@once={_token(chaos_rt, 'herd')}")

    @ray_tpu.remote(max_restarts=-1)
    class Member:
        def bump(self, x):
            return x + 1

    herd = [Member.remote() for _ in range(4)]

    def herd_answers():
        try:
            return ray_tpu.get([m.bump.remote(41) for m in herd],
                               timeout=30) == [42] * 4
        except Exception:
            return False  # the killed member is mid-restart: retry

    assert poll_until(herd_answers, timeout=120, desc="herd answers")

    # the restart left an actor_restart lifecycle event (warning, not a
    # terminal actor_death — the member came back)
    restarts = poll_until(
        lambda: _events_named("actor_restart", cause="signal:SIGKILL"),
        timeout=30, desc="actor_restart event for the killed member")
    assert restarts[0]["severity"] == "warning"
    herd_ids = {m._actor_id.hex() for m in herd}
    assert not [e for e in _events_named("actor_death")
                if e.get("actor_id") in herd_ids]


@pytest.mark.slow
def test_delayed_and_dropped_control_pipe_messages(chaos_rt):
    """Delayed driver->worker control messages and dropped worker->driver
    telemetry pushes never affect correctness — results stay exact."""
    failpoints.arm("pipe.send=delay:0.02@times=10")
    failpoints.arm("worker.pipe.send=drop@arg=metrics@times=5")

    @ray_tpu.remote
    def mul(x):
        return x * 3

    assert ray_tpu.get([mul.remote(i) for i in range(30)],
                       timeout=120) == [3 * i for i in range(30)]


def test_pipe_send_failpoint_fires_on_native_path(chaos_rt):
    """r14 satellite: the driver->worker chaos filter sits BEFORE the
    native engine, so `pipe.send` keeps firing (and the workload keeps
    its exactness) with the GIL-free pipe armed. Asserts the engine is
    actually attached AND the failpoint actually fired — a silently
    skipped filter would pass the correctness check alone."""
    from ray_tpu.core.runtime import _get_runtime
    from ray_tpu.util.metrics import registry_records

    rt = _get_runtime()
    failpoints.arm("pipe.send=delay:0.01@times=8")

    @ray_tpu.remote
    def mul(x):
        return x * 7

    assert ray_tpu.get([mul.remote(i) for i in range(24)],
                       timeout=60) == [7 * i for i in range(24)]
    # checked AFTER the workload: prestarted workers attach their engine
    # on dial-back, so an at-init check would race the accept loop
    native = [ws for ws in rt.workers.values()
              if ws.status != "dead" and ws.npipe is not None]
    if not native:
        pytest.skip("native pipe engine not active (no .so / killed)")
    fired = 0.0
    for rec in registry_records():
        if rec["name"] == "rtpu_failpoints_fired_total":
            for key, v in rec["samples"]:
                if dict(key).get("site") == "pipe.send":
                    fired += v
    assert fired >= 8, f"pipe.send fired {fired} times on the native path"


@pytest.mark.slow
def test_data_shuffle_reducer_death_recovers(chaos_rt):
    """Kill a streaming-exchange reducer actor mid-ingest: the plan
    re-executes from lineage and the result is exact (sort order + row
    count), for both the sort and the combinable-groupby engines."""
    from ray_tpu import data as rdata

    failpoints.arm(
        f"worker.exec=kill@arg=add_block@once={_token(chaos_rt, 'red1')}")
    rows = rdata.range(2000).sort("id", descending=True).take_all()
    vals = [int(r["id"]) for r in rows]
    assert vals == sorted(range(2000), reverse=True)

    failpoints.arm(
        f"worker.exec=kill@arg=add_block@once={_token(chaos_rt, 'red2')}")
    out = (rdata.range(1000)
           .map(lambda r: {"k": r["id"] % 7, "v": r["id"]})
           .groupby("k").sum("v").take_all())
    expect = {}
    for i in range(1000):
        expect[i % 7] = expect.get(i % 7, 0) + i
    got = {int(r["k"]): int(r["sum(v)"]) for r in out}
    assert got == expect

    # both engine kills left death events, each dead reducer worker
    # exactly ONCE (no dupes, no losses), classified with forensics.
    # Count is >= 2, not == 2: aborting a half-done stage can tear down
    # sibling reducers that were still mid-add_block.
    deaths = poll_until(
        lambda: d if len(d := _events_named(
            "worker_death", task="add_block")) >= 2 else None,
        timeout=60, desc="reducer death events")
    assert len({ev["worker_id"] for ev in deaths}) == len(deaths), deaths
    assert [ev for ev in deaths if ev["cause"] == "signal:SIGKILL"]
    for ev in deaths:
        assert ev["cause"].startswith("signal:"), ev
        assert ev["postmortem"]["cause"] == ev["cause"]


@pytest.mark.slow
def test_trainer_worker_kill_resumes_from_checkpoint(chaos_rt):
    """SIGKILL a train worker mid-run (process death, not a user
    exception): the trainer restarts the gang and resumes from the latest
    checkpoint instead of step 0."""
    from ray_tpu.train import (Checkpoint, FailureConfig, JaxTrainer,
                               RunConfig, ScalingConfig)

    failpoints.arm("worker.exec=kill@arg=next_result@after=4"
                   f"@once={_token(chaos_rt, 'train')}")

    def loop(config):
        import pickle
        import tempfile

        import ray_tpu.train as train

        start = 0
        ckpt = train.get_checkpoint()
        if ckpt is not None:
            rank_dir = os.path.join(ckpt.path, "rank_0")
            with open(os.path.join(rank_dir, "state.pkl"), "rb") as f:
                start = pickle.load(f)["step"] + 1
        for step in range(start, 6):
            d = tempfile.mkdtemp()
            with open(os.path.join(d, "state.pkl"), "wb") as f:
                pickle.dump({"step": step}, f)
            train.report({"step": step, "resumed_from": start},
                         checkpoint=Checkpoint(d))

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(
            storage_path=str(chaos_rt / "train"),
            failure_config=FailureConfig(max_failures=2)),
    )
    result = trainer.fit()
    assert result.metrics["step"] == 5
    assert result.metrics["resumed_from"] > 0  # did NOT restart from 0

    # the resume left a checkpoint_resume lifecycle event (emitted by
    # the driver-side retry loop, so no pipe hop to wait for)
    resumes = _events_named("checkpoint_resume")
    assert resumes and resumes[0]["attempt"] >= 1
    assert resumes[0]["checkpoint"]


# ---------------------------------------------------------------------------
# multi-node slow cases
# ---------------------------------------------------------------------------

@pytest.fixture
def chaos_cluster(tmp_path):
    from ray_tpu.cluster import Cluster

    # deflaked default node_timeout (8s): under 2-vCPU contention a
    # healthy node routinely misses several 0.5s beats, and a false
    # node-death mid-test breaks placement asserts (CLAUDE.md)
    c = Cluster(gcs_snapshot=str(tmp_path / "gcs.snap"))
    yield c
    failpoints.disarm()
    ray_tpu.shutdown()
    c.shutdown()


def _cluster_init(c):
    return ray_tpu.init(address=c.address, cluster_authkey=c.authkey,
                        num_cpus=2)


def _alive_nodes() -> int:
    return sum(1 for n in ray_tpu.nodes() if n["Alive"])


@pytest.mark.slow
def test_daemon_kill_mid_lease_grant_replaces_work(chaos_cluster):
    """A node daemon dies the moment it accepts forwarded work (lease
    grant): the node is declared dead and the task re-places on a
    surviving node within the retry budget."""
    c = chaos_cluster
    c.add_node(num_cpus=2, resources={"pool": 4})
    c.add_node(num_cpus=2, resources={"pool": 4},
               env={"RTPU_FAILPOINTS":
                    "daemon.lease_grant=exit:137@arg=submit_spec"})
    _cluster_init(c)
    poll_until(lambda: _alive_nodes() >= 3, timeout=60, desc="nodes up")

    @ray_tpu.remote(max_retries=3, resources={"pool": 1})
    def work(i):
        return i * 10

    # SPREAD lands work on the doomed daemon; its death re-places
    refs = [work.options(scheduling_strategy="SPREAD").remote(i)
            for i in range(8)]
    assert ray_tpu.get(refs, timeout=180) == [i * 10 for i in range(8)]

    # the dead daemon is exactly ONE node_death at the head (acked
    # heartbeat cursor dedups re-delivery), classified and with the
    # GCS's blast-radius postmortem
    deaths = poll_until(lambda: _events_named("node_death"),
                        timeout=60, desc="node_death event")
    time.sleep(2)  # dedup settle: a re-shipped batch must not dupe it
    deaths = _events_named("node_death")
    assert len(deaths) == 1
    assert deaths[0]["cause"] in ("connection lost", "heartbeat timeout")
    assert deaths[0]["postmortem"]["cause"] == deaths[0]["cause"]


@pytest.mark.slow
def test_gcs_kill_mid_submit_snapshot_recovery(chaos_cluster):
    """kill -9 the GCS while a task stream is in flight: daemons keep
    computing, the restarted GCS reloads the snapshot, nodes re-register,
    and every submitted task completes correctly."""
    c = chaos_cluster
    c.add_node(num_cpus=2, resources={"worker": 4})
    rt = _cluster_init(c)
    rt.kv_op("put", "chaos-key", b"durable")
    time.sleep(1.5)  # let the snapshot loop persist

    @ray_tpu.remote(max_retries=3, resources={"worker": 1})
    def job(i):
        time.sleep(0.05)
        return i + 1000

    results = {}
    errors = []

    def submit_stream():
        for i in range(30):
            try:
                results[i] = ray_tpu.get(job.remote(i), timeout=60)
            except Exception as e:  # noqa: BLE001 — recorded for assert
                errors.append((i, e))

    t = threading.Thread(target=submit_stream)
    t.start()
    time.sleep(0.6)  # land the kill mid-stream
    c.restart_gcs()
    t.join(timeout=240)
    assert not t.is_alive(), "submit stream wedged after GCS restart"
    assert not errors, f"tasks failed across GCS restart: {errors[:3]}"
    assert results == {i: i + 1000 for i in range(30)}
    assert poll_until(lambda: rt.kv_op("get", "chaos-key") == b"durable",
                      timeout=60, desc="KV after restart")

    # the restart itself is a lifecycle event (recorded by the new GCS
    # on snapshot reload, so it survives the process that died)
    restarts = poll_until(lambda: _events_named("gcs_restart"),
                          timeout=60, desc="gcs_restart event")
    assert restarts[0]["severity"] == "warning"


@pytest.mark.slow
def test_heartbeat_blackout_node_reregisters(chaos_cluster):
    """A heartbeat blackout (~ network partition) gets the node declared
    dead; when beats resume, the heartbeat NACK re-registers it and the
    node serves work again."""
    c = chaos_cluster
    # beats at 0.5s, node_timeout 8s: 34 dropped beats (~17s blackout)
    # comfortably crosses the declared-dead line even under contention;
    # the after= prefix lets the node register + settle first
    c.add_node(num_cpus=2, resources={"flaky": 4},
               env={"RTPU_FAILPOINTS":
                    "gcs.heartbeat=drop@after=6@times=34"})
    _cluster_init(c)
    poll_until(lambda: _alive_nodes() >= 2, timeout=60,
               desc="node registered")
    # partition: the node drops out...
    poll_until(lambda: _alive_nodes() < 2, timeout=60, desc="node dead")
    # ...and heals: beats resume, NACK re-registers
    poll_until(lambda: _alive_nodes() >= 2, timeout=120,
               desc="node re-registered")

    @ray_tpu.remote(max_retries=3, resources={"flaky": 1})
    def ping():
        return "pong"

    assert ray_tpu.get(ping.remote(), timeout=120) == "pong"


@pytest.mark.slow
def test_rpc_dispatch_drop_hits_default_deadline(chaos_cluster,
                                                 monkeypatch):
    """The GCS swallowing a request (dropped dispatch) surfaces as a
    TimeoutError on the caller's DEFAULT deadline — no un-deadlined park —
    and the retried poll succeeds; the timeout counter records it."""
    from ray_tpu.core.runtime import _get_runtime
    from ray_tpu.util import metric_defs as md

    monkeypatch.setenv("RTPU_RPC_DEFAULT_TIMEOUT_S", "3")
    c = chaos_cluster
    c.add_node(num_cpus=1)
    rt = _cluster_init(c)
    assert rt is _get_runtime()
    gcs = rt.cluster.gcs
    rt.kv_op("put", "drop-me", b"v")

    def timeouts():
        return sum(v for _, v in
                   md.get("rtpu_rpc_client_timeouts_total")._samples())

    before = timeouts()
    # arg=kv_get: only this test calls kv_get here, so the drop cannot be
    # consumed by a background scheduler/heartbeat RPC
    gcs.call("fp_arm", "rpc.server.dispatch=drop@arg=kv_get@times=1",
             timeout=10)
    t0 = time.monotonic()
    with pytest.raises(TimeoutError):
        gcs.call("kv_get", "drop-me", "default")  # default deadline
    elapsed = time.monotonic() - t0
    assert 2.0 <= elapsed < 30.0, f"default deadline off: {elapsed}"
    assert timeouts() == before + 1
    # the retried poll (the CLAUDE.md deflake idiom) recovers
    assert poll_until(lambda: gcs.call("kv_get", "drop-me", "default") == b"v",
                      timeout=30, desc="kv_get after drop")


@pytest.mark.slow
def test_gcs_kill_between_pg_reserve_and_commit(chaos_cluster):
    """Satellite: kill -9 the GCS INSIDE the 2-phase window (resources
    staged on every node, commit not yet run). The creator's commit is
    node-local, registration retries through the restart, and the group
    converges to READY + schedulable."""
    from ray_tpu.util.placement_group import placement_group
    from ray_tpu.util.scheduling_strategies import (
        PlacementGroupSchedulingStrategy,
    )

    c = chaos_cluster
    c.add_node(num_cpus=2, resources={"slot": 2})
    c.add_node(num_cpus=2, resources={"slot": 2})
    _cluster_init(c)
    poll_until(lambda: _alive_nodes() >= 3, timeout=60, desc="nodes up")

    # the driver is the creator: stall ITS commit phase only (local arm,
    # no broadcast), leaving the window open long enough to land the kill
    failpoints.apply_spec("adapter.pg.before_commit=delay:4")
    box = {}

    def create():
        try:
            box["pg"] = placement_group(
                [{"CPU": 1, "slot": 1}] * 2, strategy="STRICT_SPREAD")
        except Exception as e:  # noqa: BLE001 — asserted below
            box["err"] = e

    t = threading.Thread(target=create)
    t.start()
    time.sleep(1.5)  # prepare done on both nodes; creator is in delay:4
    c.restart_gcs()
    t.join(timeout=120)
    failpoints.clear()
    assert not t.is_alive(), "pg creation wedged across GCS restart"
    assert "err" not in box, f"pg creation failed: {box.get('err')}"
    pg = box["pg"]
    assert pg.wait(timeout_seconds=120)

    @ray_tpu.remote(max_retries=2)
    def where():
        return os.getpid()

    refs = [
        where.options(scheduling_strategy=PlacementGroupSchedulingStrategy(
            placement_group=pg, placement_group_bundle_index=i)).remote()
        for i in range(2)
    ]
    assert len(set(ray_tpu.get(refs, timeout=180))) == 2


@pytest.mark.slow
def test_elastic_trainer_node_loss_shrinks_then_reexpands(chaos_cluster,
                                                         tmp_path):
    """The elasticity drill (r20 acceptance): kill a node mid-epoch —
    training fences, re-forms at N-1, and resumes from the last
    all-ranks-ok checkpoint WITHOUT burning a max_failures attempt
    (max_failures=0: any group restart would fail the run); when a
    replacement node registers, the executor re-expands to N at a
    checkpoint boundary. Both membership transitions are asserted via
    train_world_epoch events, and progress records prove actual steps
    ran at the shrunken world size."""
    import glob
    import json

    from ray_tpu.train import (Checkpoint, FailureConfig, JaxTrainer,
                               RunConfig, ScalingConfig)

    c = chaos_cluster
    c.add_node(num_cpus=2, resources={"trainslot": 1})
    victim = c.add_node(num_cpus=2, resources={"trainslot": 1})
    _cluster_init(c)
    poll_until(lambda: _alive_nodes() >= 3, timeout=60, desc="nodes up")

    total_steps = 80

    def loop(config):
        import pickle
        import tempfile
        import time as _t

        import ray_tpu.train as train

        ctx = train.get_context()
        start = 0
        ckpt = train.get_checkpoint()
        if ckpt is not None:
            with open(os.path.join(ckpt.path, "rank_0", "state.pkl"),
                      "rb") as f:
                start = pickle.load(f)["step"] + 1
        for step in range(start, config["steps"]):
            d = tempfile.mkdtemp()
            with open(os.path.join(d, "state.pkl"), "wb") as f:
                pickle.dump({"step": step}, f)
            train.report({"step": step, "ws": ctx.world_size,
                          "epoch": ctx.world_epoch},
                         checkpoint=Checkpoint(d))
            _t.sleep(0.3)

    storage = str(tmp_path / "train")
    trainer = JaxTrainer(
        loop,
        train_loop_config={"steps": total_steps},
        # trainslot pins one worker per non-head node (the head carries
        # none), so killing the victim daemon kills exactly one rank
        scaling_config=ScalingConfig(
            num_workers=2, min_workers=1,
            resources_per_worker={"trainslot": 1.0}),
        run_config=RunConfig(
            name="elastic", storage_path=storage,
            failure_config=FailureConfig(max_failures=0)),
    )
    box = {}

    def run():
        try:
            box["result"] = trainer.fit()
        except BaseException as e:  # noqa: BLE001 - reported by asserts
            box["err"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()

    # a complete (both-ranks-ok) checkpoint must exist before the kill,
    # or the shrink proves nothing about resume
    def complete_ckpt():
        for p in glob.glob(os.path.join(storage, "elastic", "trial_*",
                                        "checkpoint_*")):
            if (os.path.exists(os.path.join(p, ".rank_0.ok"))
                    and os.path.exists(os.path.join(p, ".rank_1.ok"))):
                return p
        return None

    poll_until(complete_ckpt, timeout=90, desc="first complete checkpoint")
    c.kill_node(victim)

    # node declared dead -> WorkerDeathError -> elastic shrink to 1
    shrink = poll_until(
        lambda: _events_named("train_world_epoch", reason="shrink") or None,
        timeout=120, desc="shrink membership epoch")
    assert int(shrink[-1]["world_size"]) == 1, shrink
    assert int(shrink[-1]["prev_world_size"]) == 2, shrink
    assert shrink[-1]["checkpoint"], "shrink must resume from a checkpoint"

    # capacity returns: a replacement node -> re-expansion to N at a
    # checkpoint boundary
    c.add_node(num_cpus=2, resources={"trainslot": 1})
    expand = poll_until(
        lambda: _events_named("train_world_epoch", reason="expand") or None,
        timeout=180, desc="expand membership epoch")
    assert int(expand[-1]["world_size"]) == 2, expand
    assert int(expand[-1]["prev_world_size"]) == 1, expand

    t.join(timeout=300)
    assert not t.is_alive(), "fit() wedged after membership churn"
    assert "err" not in box, f"elastic fit failed: {box.get('err')!r}"
    result = box["result"]
    assert result.metrics["step"] == total_steps - 1
    assert result.metrics["ws"] == 2          # finished re-expanded
    assert result.metrics["epoch"] >= 2       # shrink + expand epochs

    # actual training steps ran at the shrunken world size (not just a
    # transition event): the progress stream has ws=1 records between
    # the two membership epochs
    (progress_path,) = glob.glob(os.path.join(
        storage, "elastic", "trial_*", "progress.jsonl"))
    ws_seen = [json.loads(line)["ws"]
               for line in open(progress_path) if line.strip()]
    assert 1 in ws_seen and ws_seen[-1] == 2, ws_seen
    # max_failures=0 budget intact: the elastic path never fell back to
    # a group restart (which would have emitted checkpoint_resume)
    assert not _events_named("checkpoint_resume")
