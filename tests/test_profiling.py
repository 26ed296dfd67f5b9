"""Profiling plane (ISSUE 9): the sampling profiler, cross-process
collection into the head ProfileStore, speedscope/collapsed export,
live stack dumps, and object-memory forensics.

The multi-NODE collection path (heartbeat -> GCS profile store) is
covered in test_cluster.py.
"""

import os
import threading
import time

import pytest

import ray_tpu
from ray_tpu.util import profiling, state


def _cleanup_profiling():
    os.environ.pop("RTPU_PROFILING", None)
    os.environ.pop("RTPU_PROFILE_HZ", None)
    os.environ.pop("RTPU_PROFILE_TABLE_MAX", None)
    profiling._reset_for_tests()


@pytest.fixture
def clean_profiling():
    _cleanup_profiling()
    yield
    _cleanup_profiling()


def _wait_for(pred, timeout=45.0, interval=0.25):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = pred()
        if v:
            return v
        time.sleep(interval)
    return pred()


def _burn(seconds):
    t = time.monotonic() + seconds
    x = 0
    while time.monotonic() < t:
        x += 1
    return x


# ---------------------------------------------------------------------------
# recording plane (no runtime needed)
# ---------------------------------------------------------------------------


def test_disabled_is_noop(clean_profiling):
    assert profiling.profiling_enabled() is False
    assert profiling.ensure_sampler() is None
    assert profiling.drain_batches() == []
    assert profiling.sampler_stats() == {}


def test_sampler_captures_busy_and_idle(clean_profiling, monkeypatch):
    monkeypatch.setenv("RTPU_PROFILING", "1")
    profiling._reset_for_tests()
    monkeypatch.setenv("RTPU_PROFILING", "1")
    assert profiling.profiling_enabled() is True
    assert profiling.ensure_sampler() is not None

    # one thread burning CPU, one parked on an Event (idle leaf in
    # threading.py wait)
    park = threading.Event()
    burner = threading.Thread(target=_burn, args=(0.5,), name="burner")
    parker = threading.Thread(target=park.wait, args=(3.0,),
                              name="parker")
    burner.start()
    parker.start()
    time.sleep(0.45)
    batches = profiling.drain_batches()
    d2 = profiling.drain_batches()  # immediately: at most ~1 tick landed
    park.set()
    burner.join()
    parker.join()
    assert len(batches) == 1
    b = batches[0]
    assert b["pid"] == os.getpid()
    assert b["total"] > 0
    # busy: the burner's loop frame attributed by name
    assert any(t == "burner" and any("_burn" in f for f in stack)
               for t, stack, n in b["samples"]), b["samples"]
    # idle: the parked thread classified out of the busy signal
    assert any(t == "parker" for t, stack, n in b["idle"]), \
        [t for t, _, _ in b["idle"]]
    # (a tick may catch it starting, before it is parked: never IN the wait)
    assert not any(t == "parker" and any("wait (" in f for f in stack)
                   for t, stack, n in b["samples"])
    # drained exactly once: the adjacent second drain saw at most a
    # tick or two of fresh samples, never the 0.45s window again
    n2 = sum(x["total"] + x["idle_total"] for x in d2)
    assert n2 < (b["total"] + b["idle_total"]) / 2, (n2, b)


def test_disarm_stashes_tail_window(clean_profiling, monkeypatch):
    monkeypatch.setenv("RTPU_PROFILING", "1")
    profiling._reset_for_tests()
    monkeypatch.setenv("RTPU_PROFILING", "1")
    s = profiling.ensure_sampler()
    s.record_for_tests("t", ["root (a.py:1)", "leaf (a.py:9)"])
    profiling.disable_profiling()
    assert profiling.profiling_enabled() is False
    # the stopped sampler's final window (the synthetic sample, plus
    # whatever real ticks landed before the stop) is NOT lost
    batches = profiling.drain_batches()
    assert batches
    assert any(t == "t" and stack == ["root (a.py:1)", "leaf (a.py:9)"]
               for t, stack, n in batches[0]["samples"])
    assert profiling.drain_batches() == []


def test_table_bound_drops(clean_profiling):
    # non-started sampler: deterministic — no live ticks compete for
    # table slots with the synthetic inserts
    s = profiling._Sampler(hz=67.0, table_max=64, start=False)
    for i in range(100):
        s.record_for_tests("t", [f"f{i} (x.py:{i})"])
    st = s.stats()
    assert st["busy_keys"] == 64
    assert st["dropped"] == 36
    b = s.drain()
    assert b["dropped"] == 36
    assert b["total"] == 64
    # the drop settled the bound; the next window starts clean
    s.record_for_tests("t", ["g (y.py:1)"])
    assert s.stats()["dropped"] == 0


def test_merge_top_self_collapsed_and_speedscope(clean_profiling):
    batches = [
        {"pid": 1, "t0": 0.0, "t1": 1.0, "hz": 67.0, "dropped": 0,
         "total": 5, "idle_total": 1,
         "samples": [["MainThread", ["a (m.py:1)", "b (m.py:9)"], 3],
                     ["MainThread", ["a (m.py:1)"], 2]],
         "idle": [["rx", ["r (m.py:4)", "wait (threading.py:300)"], 1]],
         "node_id": "n1", "component": "driver"},
        {"pid": 2, "t0": 0.0, "t1": 1.0, "hz": 67.0, "dropped": 2,
         "total": 4, "idle_total": 0,
         "samples": [["MainThread", ["a (m.py:1)", "b (m.py:9)"], 4]],
         "idle": [],
         "node_id": "n1", "component": "worker", "worker_id": "w1"},
    ]
    merged = profiling.merge_batches(batches)
    assert set(merged["processes"]) == {"driver@n1/1", "worker@n1/2"}
    assert merged["total"] == 9
    assert merged["dropped"] == 2

    top = profiling.top_self(merged)
    assert top[0]["function"] == "b (m.py:9)"  # 7 leaf samples
    assert top[0]["self_samples"] == 7
    top_w = profiling.top_self(merged, component="worker")
    assert top_w[0]["self_samples"] == 4 and len(top_w) == 1

    text = profiling.collapsed_text(merged)
    assert "driver@n1/1;MainThread;a (m.py:1);b (m.py:9) 3" in text
    # idle excluded unless asked
    assert "wait (threading.py:300)" not in text
    assert "wait (threading.py:300)" in profiling.collapsed_text(
        merged, include_idle=True)

    doc = profiling.speedscope_doc(merged)
    # one sampled profile per BUSY (process, thread) — idle threads are
    # classified out so they don't drown the on-CPU signal; weights sum
    # to that thread's sample count; frame indices all valid
    assert len(doc["profiles"]) == 2
    nframes = len(doc["shared"]["frames"])
    for p in doc["profiles"]:
        assert p["type"] == "sampled"
        assert sum(p["weights"]) == p["endValue"]
        assert len(p["samples"]) == len(p["weights"])
        assert all(0 <= i < nframes for s in p["samples"] for i in s)
    by_name = {p["name"]: p for p in doc["profiles"]}
    assert by_name["driver@n1/1 MainThread"]["endValue"] == 5
    assert by_name["worker@n1/2 MainThread"]["endValue"] == 4


def test_speedscope_excludes_idle_threads(clean_profiling):
    # wait-dominated threads are classified out of the speedscope view
    # (they'd drown the on-CPU signal); they remain countable in the
    # merge and visible via collapsed_text(include_idle=True)
    merged = profiling.merge_batches([
        {"pid": 1, "t0": 0, "t1": 1, "hz": 67.0, "dropped": 0,
         "total": 0, "idle_total": 2, "samples": [],
         "idle": [["rx", ["r (m.py:4)"], 2]], "component": "driver",
         "node_id": "n1"}])
    assert profiling.speedscope_doc(merged)["profiles"] == []
    assert merged["idle_total"] == 2
    assert "r (m.py:4) 2" in profiling.collapsed_text(
        merged, include_idle=True)


def test_profile_store_since_cursor(clean_profiling):
    ps = profiling.ProfileStore(cap=100)
    ps.ingest([{"pid": i} for i in range(5)], {"node_id": "n1"})
    batch, start = ps.since(0)
    assert start == 0 and len(batch) == 5
    assert all(b["node_id"] == "n1" for b in batch)
    batch2, start2 = ps.since(start + len(batch))
    assert batch2 == [] and start2 == 5
    ps.ingest([{"pid": 99}])
    batch3, start3 = ps.since(5)
    assert [b["pid"] for b in batch3] == [99] and start3 == 5


def test_current_stacks_needs_no_arming(clean_profiling):
    park = threading.Event()
    t = threading.Thread(target=park.wait, args=(5.0,), name="stackee")
    t.start()
    try:
        stacks = profiling.current_stacks()
        assert "stackee" in stacks
        assert "wait (" in stacks["stackee"].split(";")[-1]
    finally:
        park.set()
        t.join()


def test_idle_sleep_classifies_idle(clean_profiling, monkeypatch):
    monkeypatch.setenv("RTPU_PROFILING", "1")
    profiling._reset_for_tests()
    monkeypatch.setenv("RTPU_PROFILING", "1")
    profiling.ensure_sampler()
    t = threading.Thread(target=profiling.idle_sleep, args=(0.4,),
                         name="idler")
    t.start()
    time.sleep(0.3)
    t.join()
    b = profiling.drain_batches()[0]
    idle = sum(n for tn, _, n in b["idle"] if tn == "idler")
    busy = [(stack, n) for tn, stack, n in b["samples"] if tn == "idler"]
    assert idle > 0
    # parked in the wait it is NEVER busy; a tick may catch the thread
    # starting or ending (a loaded box holds it there for milliseconds),
    # which is not the sleep
    assert not [st for st, _ in busy if any("wait (" in f for f in st)], busy
    assert idle > sum(n for _, n in busy), (idle, busy)


# ---------------------------------------------------------------------------
# collection through a live runtime (workers push over the pipe)
# ---------------------------------------------------------------------------


@pytest.fixture
def profiled_rt(clean_profiling, monkeypatch):
    monkeypatch.setenv("RTPU_PROFILING", "1")
    monkeypatch.setenv("RTPU_PROFILE_PUSH_INTERVAL_S", "0.2")
    profiling._reset_for_tests()
    monkeypatch.setenv("RTPU_PROFILING", "1")
    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


def test_worker_profiles_reach_head_merge(profiled_rt):
    @ray_tpu.remote
    def spin(sec):
        t = time.monotonic() + sec
        x = 0
        while time.monotonic() < t:
            x += 1
        return x

    ray_tpu.get([spin.remote(0.1) for _ in range(4)], timeout=60)

    def merged_ready():
        # keep work flowing so worker pushes fire
        ray_tpu.get([spin.remote(0.3) for _ in range(2)], timeout=60)
        prof = state.profile()
        comps = {p["component"] for p in prof["processes"].values()}
        if "worker" not in comps or "driver" not in comps:
            return None
        top_w = prof["top_self_by_component"]["worker"]
        if not any("spin" in r["function"] for r in top_w):
            return None
        return prof

    prof = _wait_for(merged_ready)
    assert prof, "worker profile batches never reached the head merge"
    # worker batches carry their origin labels
    wprocs = [k for k, p in prof["processes"].items()
              if p["component"] == "worker"]
    assert wprocs and all(k.startswith("worker@") for k in wprocs)
    # speedscope export over the live merge validates its shape contract
    doc = state.export_speedscope()
    assert doc["profiles"]
    for p in doc["profiles"]:
        assert sum(p["weights"]) == p["endValue"]


def test_profile_seconds_temp_arms_and_disarms(clean_profiling):
    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    try:
        @ray_tpu.remote
        def spin(sec):
            t = time.monotonic() + sec
            x = 0
            while time.monotonic() < t:
                x += 1
            return x

        ray_tpu.get(spin.remote(0.05), timeout=60)
        assert profiling.profiling_enabled() is False
        done = threading.Event()

        def drive():
            while not done.is_set():
                try:
                    ray_tpu.get([spin.remote(0.3) for _ in range(2)],
                                timeout=60)
                except Exception:
                    return

        th = threading.Thread(target=drive)
        th.start()
        try:
            prof = state.profile(seconds=1.5)
        finally:
            done.set()
            th.join()
        # temporary arming is undone after the window
        assert profiling.profiling_enabled() is False
        assert prof["total_samples"] > 0
        comps = {p["component"] for p in prof["processes"].values()}
        assert "worker" in comps, prof["processes"]
    finally:
        ray_tpu.shutdown()


def test_live_stack_dump_reaches_workers(clean_profiling):
    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    try:
        @ray_tpu.remote
        def f():
            return 1

        assert ray_tpu.get(f.remote(), timeout=60) == 1

        def parked_worker():
            dump = state.stack(timeout=5.0)
            assert len(dump) == 1  # single node
            procs = next(iter(dump.values()))
            # the head process itself plus >= 1 worker answered
            assert any(k.startswith("driver/") for k in procs), procs.keys()
            wkeys = [k for k in procs if k.startswith("worker:")]
            assert wkeys
            wstacks = procs[wkeys[0]]
            assert "MainThread" in wstacks
            leaf = wstacks["MainThread"].split(";")[-1]
            return "wait (" in leaf or "get (" in leaf

        # the worker main loop is parked in its exec-queue get — once it
        # is done with f: get() returns when the RESULT lands, and the
        # dump can catch the main thread still in the send after it
        from conftest import poll_until

        poll_until(parked_worker, timeout=20, interval=0.3,
                   desc="worker main thread parked in its exec queue")
    finally:
        ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# object-memory forensics (`ray_tpu memory` / state.diff_objects)
# ---------------------------------------------------------------------------


def test_memory_summary_reasons_owner_age(clean_profiling):
    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    try:
        profiling.enable_profiling()  # call sites recorded while armed
        ref = ray_tpu.put(b"z" * 200_000)
        rows = {r["object_id"]: r for r in state.memory_summary()}
        row = rows[ref.id.hex()]
        assert row["size"] >= 200_000
        assert row["owner"] == "driver"
        assert "create-ref" in row["reasons"]
        assert row["age_s"] is not None and row["age_s"] < 60
        assert row["call_site"] and "test_profiling" in row["call_site"]

        # a task RESULT is owned by its worker and reconstructable
        @ray_tpu.remote
        def produce():
            return b"r" * 100_000

        rref = produce.remote()
        ray_tpu.wait([rref], timeout=60)
        rows = {r["object_id"]: r for r in state.memory_summary()}
        rrow = rows[rref.id.hex()]
        assert rrow["owner"].startswith("worker:")
        assert "lineage" in rrow["reasons"]
        profiling.disable_profiling()
    finally:
        ray_tpu.shutdown()


def test_diff_objects_flags_planted_leak(clean_profiling):
    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    try:
        state.snapshot_objects()
        leaked = [ray_tpu.put(b"L" * 150_000)]  # intentionally held
        diff = state.diff_objects()
        sus = [r for r in diff["leak_suspects"]
               if r["object_id"] == leaked[0].id.hex()]
        assert sus, diff["leak_suspects"]
        assert "create-ref" in sus[0]["reasons"]
        assert sus[0]["pins"] >= 1
        assert diff["net_bytes"] >= 150_000

        # dropping the ref clears it from the next diff's population
        del leaked
        import gc

        gc.collect()
        diff2 = state.diff_objects()
        assert all(r["object_id"] != sus[0]["object_id"]
                   for r in diff2["added"])
    finally:
        ray_tpu.shutdown()


def test_store_report_occupancy(clean_profiling):
    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    try:
        keep = ray_tpu.put(b"s" * 500_000)  # noqa: F841 — stays in shm
        rep = state.store_report()
        assert rep["backend"] in ("arena", "file")
        assert rep["capacity_bytes"] > 0
        if rep["backend"] == "arena":
            assert rep["arena_used_bytes"] >= 500_000
            assert "fragmentation_pct" in rep
            assert rep["largest_free_bytes"] <= rep["free_bytes"]
    finally:
        ray_tpu.shutdown()
