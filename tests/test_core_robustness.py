"""Core robustness: runtime_env, spilling, memory monitor, retries."""

import os

import numpy as np
import pytest

import ray_tpu
from ray_tpu.core.ids import ObjectID
from ray_tpu.core.memory_monitor import MemoryMonitor, system_memory
from ray_tpu.core.object_store import StoreClient


@pytest.fixture
def rt_rob():
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


def test_task_runtime_env_env_vars(rt_rob):
    @ray_tpu.remote
    def read_env():
        return os.environ.get("RTPU_TEST_VAR")

    assert ray_tpu.get(read_env.remote()) is None
    with_env = read_env.options(
        runtime_env={"env_vars": {"RTPU_TEST_VAR": "hello"}})
    assert ray_tpu.get(with_env.remote()) == "hello"
    # env is restored for subsequent tasks on the same worker
    assert ray_tpu.get(read_env.remote()) is None


def test_task_runtime_env_working_dir(rt_rob, tmp_path):
    (tmp_path / "marker.txt").write_text("found")

    @ray_tpu.remote
    def read_marker():
        return open("marker.txt").read()

    task = read_marker.options(runtime_env={"working_dir": str(tmp_path)})
    assert ray_tpu.get(task.remote()) == "found"


def test_bad_working_dir_fails_task_not_worker(rt_rob):
    @ray_tpu.remote
    def fine():
        return "ok"

    bad = fine.options(runtime_env={"working_dir": "/does/not/exist"})
    from ray_tpu.core.exceptions import TaskError

    with pytest.raises(TaskError):
        ray_tpu.get(bad.remote(), timeout=30)
    # worker survived; subsequent tasks run normally
    assert ray_tpu.get(fine.remote(), timeout=30) == "ok"


def test_runtime_env_sys_path_restored(rt_rob, tmp_path):
    (tmp_path / "probe_mod.py").write_text("VALUE = 'from_tmp'\n")

    @ray_tpu.remote
    def uses_wd():
        import probe_mod

        return probe_mod.VALUE

    task = uses_wd.options(runtime_env={"working_dir": str(tmp_path)})
    assert ray_tpu.get(task.remote()) == "from_tmp"

    @ray_tpu.remote
    def path_has(entry):
        import sys

        return entry in sys.path

    # run enough probes to cover every pool worker
    checks = ray_tpu.get([path_has.remote(str(tmp_path)) for _ in range(8)])
    assert not any(checks)


def test_actor_runtime_env_persistent(rt_rob):
    @ray_tpu.remote
    class EnvActor:
        def get(self):
            return os.environ.get("RTPU_ACTOR_VAR")

    a = EnvActor.options(
        runtime_env={"env_vars": {"RTPU_ACTOR_VAR": "persistent"}}).remote()
    assert ray_tpu.get(a.get.remote()) == "persistent"
    assert ray_tpu.get(a.get.remote()) == "persistent"


def test_spilling_to_disk(monkeypatch):
    import uuid

    session = uuid.uuid4().hex[:12]
    monkeypatch.setenv("RTPU_SPILL_THRESHOLD", "1")   # spill everything big
    monkeypatch.setenv("RTPU_NATIVE_STORE", "0")      # force the file path
    client = StoreClient(session)
    try:
        oid = ObjectID.from_random()
        data = np.arange(50_000, dtype=np.float64)
        inline, size = client.put(oid, data)
        assert inline is None                         # too big to inline
        assert size >= data.nbytes
        assert client.contains_spilled(oid)           # landed on disk
        assert not os.path.exists(
            f"/dev/shm/rtpu-{session}-{oid.hex()}")
        back = client.get(oid)
        np.testing.assert_array_equal(back, data)
        del back
        client.release(oid)
        client.delete(oid)
        assert not client.contains_spilled(oid)
    finally:
        StoreClient.cleanup_session(session)


def test_memory_monitor_fires_on_threshold():
    fired = []
    mon = MemoryMonitor(usage_threshold=0.0,     # always over
                        on_pressure=lambda mem: fired.append(mem))
    assert mon.check()
    assert fired and fired[0]["total"] > 0
    mon2 = MemoryMonitor(usage_threshold=1.01)   # never over
    assert not mon2.check()


def test_system_memory_sane():
    mem = system_memory()
    assert mem["total"] > (1 << 28)
    assert 0.0 <= mem["used_fraction"] <= 1.0


def test_actor_restart_after_death(rt_rob):
    @ray_tpu.remote
    class Fragile:
        def __init__(self):
            self.count = 0

        def incr(self):
            self.count += 1
            return self.count

        def crash(self):
            import os as _os

            _os._exit(1)

    a = Fragile.options(max_restarts=1).remote()
    assert ray_tpu.get(a.incr.remote(), timeout=30) == 1
    a.crash.remote()
    # restarted actor: fresh state, same handle keeps working
    deadline = __import__("time").time() + 30
    value = None
    while __import__("time").time() < deadline:
        try:
            value = ray_tpu.get(a.incr.remote(), timeout=10)
            break
        except Exception:
            __import__("time").sleep(0.2)
    assert value == 1, f"actor did not restart cleanly (got {value})"


def test_task_retry_after_worker_death(rt_rob, tmp_path):
    marker = tmp_path / "attempted"

    @ray_tpu.remote
    def flaky(marker_path):
        import os as _os

        if not _os.path.exists(marker_path):
            open(marker_path, "w").close()
            _os._exit(1)          # simulate worker crash
        return "recovered"

    ref = flaky.options(max_retries=2).remote(str(marker))
    assert ray_tpu.get(ref, timeout=60) == "recovered"


def test_lineage_reconstruction_driver_get(rt_rob):
    """Delete a task result's segment behind the store's back: get() must
    re-execute the producer and return the value (reference
    object_recovery_manager.h:41 / task_manager.h:468)."""
    import numpy as np

    import ray_tpu
    from ray_tpu.core.runtime import _get_runtime

    calls = []

    @ray_tpu.remote
    def produce(tag):
        import os
        return np.full(1 << 15, 7.5)  # 256 KiB: store segment, not inline

    ref = produce.remote("x")
    first = ray_tpu.get(ref)
    assert first.sum() == 7.5 * (1 << 15)

    rt_obj = _get_runtime()
    rt_obj.store.delete(ref.id)            # lose the segment
    rt_obj.gcs.objects[ref.id].inline = None
    again = ray_tpu.get(ref, timeout=60)   # must reconstruct via lineage
    assert again.sum() == 7.5 * (1 << 15)


def test_lineage_reconstruction_as_dependency(rt_rob):
    """A worker hitting a lost dependency asks the driver to re-execute the
    producer, then the dependent task completes."""
    import numpy as np

    import ray_tpu
    from ray_tpu.core.runtime import _get_runtime

    @ray_tpu.remote
    def produce():
        return np.arange(1 << 15, dtype=np.float64)

    @ray_tpu.remote
    def consume(x):
        return float(x.sum())

    ref = produce.remote()
    ray_tpu.wait([ref], timeout=60)
    _get_runtime().store.delete(ref.id)    # lose it before consumption
    expect = float(np.arange(1 << 15, dtype=np.float64).sum())
    assert ray_tpu.get(consume.remote(ref), timeout=90) == expect


def test_lineage_absent_for_put_objects(rt_rob):
    """ray_tpu.put objects have no lineage: losing them is a real error
    (reference: puts are not reconstructable)."""
    import numpy as np
    import pytest as _pytest

    import ray_tpu
    from ray_tpu.core.runtime import _get_runtime

    ref = ray_tpu.put(np.zeros(1 << 15))
    _get_runtime().store.delete(ref.id)
    with _pytest.raises((FileNotFoundError, OSError)):
        ray_tpu.get(ref, timeout=10)


def test_chaos_random_worker_kills_under_load(rt_rob):
    """Fault-injection soak (reference WorkerKillerActor pattern,
    python/ray/_private/test_utils.py:1560 role): an external killer
    SIGKILLs random busy workers while a burst of retryable tasks runs;
    every task must still complete with the right answer."""
    import random
    import signal
    import threading
    import time as _t

    from ray_tpu.core.runtime import _get_runtime

    @ray_tpu.remote(max_retries=4)
    def work(i):
        import time as _tt

        _tt.sleep(0.15)
        return i * i

    # warm the pool so the killer has victims from the start
    ray_tpu.get([work.remote(i) for i in range(8)])

    rt = _get_runtime()
    stop = threading.Event()
    kills = []

    def killer():
        rng = random.Random(0)
        while not stop.is_set():
            _t.sleep(0.4)
            with rt.lock:
                busy = [ws for ws in rt.workers.values()
                        if ws.kind == "pool" and ws.status == "busy"
                        and ws.proc.poll() is None]
            if busy:
                victim = rng.choice(busy)
                try:
                    victim.proc.kill()
                    kills.append(victim.worker_id.hex()[:8])
                except Exception:
                    pass

    t = threading.Thread(target=killer, daemon=True)
    t.start()
    try:
        refs = [work.remote(i) for i in range(60)]
        results = ray_tpu.get(refs, timeout=60)
    finally:
        stop.set()
        t.join(timeout=5)
    assert results == [i * i for i in range(60)]
    assert kills, "the killer never fired; the soak proved nothing"


def _build_tiny_wheel(wheel_dir, name="rtpu_testpkg", version="0.1"):
    """Hand-rolled wheel (no network, no build backend): a wheel is a zip
    with the package + dist-info metadata."""
    import base64
    import hashlib
    import zipfile

    os.makedirs(wheel_dir, exist_ok=True)
    whl = os.path.join(wheel_dir, f"{name}-{version}-py3-none-any.whl")
    files = {
        f"{name}/__init__.py": f"MAGIC = 'wheel-{version}'\n",
        f"{name}-{version}.dist-info/METADATA":
            f"Metadata-Version: 2.1\nName: {name}\nVersion: {version}\n",
        f"{name}-{version}.dist-info/WHEEL":
            "Wheel-Version: 1.0\nGenerator: rtpu-test\nRoot-Is-Purelib: "
            "true\nTag: py3-none-any\n",
    }
    record_rows = []
    for path, text in files.items():
        digest = base64.urlsafe_b64encode(
            hashlib.sha256(text.encode()).digest()).rstrip(b"=").decode()
        record_rows.append(f"{path},sha256={digest},{len(text.encode())}")
    record_rows.append(f"{name}-{version}.dist-info/RECORD,,")
    with zipfile.ZipFile(whl, "w") as zf:
        for path, text in files.items():
            zf.writestr(path, text)
        zf.writestr(f"{name}-{version}.dist-info/RECORD",
                    "\n".join(record_rows) + "\n")
    return whl


def test_pip_runtime_env_venv_isolation_and_cache(rt_rob, tmp_path,
                                                  monkeypatch):
    """VERDICT r4 #5 done-criteria: a pip runtime_env installs a wheel
    into a cached per-hash venv; the task imports it, the driver env is
    untouched, and the second use hits the cache (no reinstall)."""
    import importlib

    wheel_dir = str(tmp_path / "wheels")
    _build_tiny_wheel(wheel_dir)
    env_root = str(tmp_path / "pip-envs")
    monkeypatch.setenv("RTPU_PIP_ENV_DIR", env_root)

    renv = {"pip": {"packages": ["rtpu_testpkg==0.1"],
                    "pip_args": ["--no-index", "--find-links", wheel_dir]},
            # workers inherit the cache root via env_vars (the fixture's
            # workers predate the monkeypatch)
            "env_vars": {"RTPU_PIP_ENV_DIR": env_root}}

    @ray_tpu.remote
    def use_pkg():
        import rtpu_testpkg

        return os.getpid(), rtpu_testpkg.MAGIC, rtpu_testpkg.__file__

    # warm the pool first: the probe below must land on the worker that
    # applied the env, and an idle pool dispatches to its first worker
    # every time — submitted while the workers are still booting, use_pkg
    # goes to whichever dials back first and the probes may never meet it
    @ray_tpu.remote
    def noop():
        return None

    ray_tpu.get([noop.remote() for _ in range(16)], timeout=60)

    pkg_pid, magic, path = ray_tpu.get(
        use_pkg.options(runtime_env=renv).remote(), timeout=60)
    assert magic == "wheel-0.1"
    assert env_root in path  # imported from the venv, not the image

    # driver env untouched
    with pytest.raises(ImportError):
        importlib.import_module("rtpu_testpkg")

    # a task WITHOUT the env cannot see the package (undo worked). Only
    # the worker that APPLIED the env can tell, and nothing steers a task
    # to one worker, so probe every pool worker at once: four one-CPU
    # probes fill the fixture's four CPUs, and each holds its worker until
    # all four have started, so they are four different workers (the pool
    # holds at most four).
    barrier = tmp_path / "probes"
    barrier.mkdir()

    @ray_tpu.remote
    def cannot_import(barrier_dir, n):
        import time as _t

        open(os.path.join(barrier_dir, str(os.getpid())), "w").close()
        deadline = _t.monotonic() + 30
        while len(os.listdir(barrier_dir)) < n and _t.monotonic() < deadline:
            _t.sleep(0.02)
        try:
            import rtpu_testpkg  # noqa: F401
            return os.getpid(), "leaked"
        except ImportError:
            return os.getpid(), "isolated"

    probes = dict(ray_tpu.get(
        [cannot_import.remote(str(barrier), 4) for _ in range(4)],
        timeout=45))
    assert len(probes) == 4, probes
    assert probes[pkg_pid] == "isolated", probes

    # second use hits the cache: .ready mtime unchanged, and fast
    envs = [d for d in os.listdir(env_root) if d.startswith("pipenv-")
            and not d.endswith(".lock")]
    assert len(envs) == 1
    ready = os.path.join(env_root, envs[0], ".ready")
    mtime = os.path.getmtime(ready)
    _, magic2, _ = ray_tpu.get(
        use_pkg.options(runtime_env=renv).remote(), timeout=60)
    assert magic2 == "wheel-0.1"
    assert os.path.getmtime(ready) == mtime  # no reinstall

    # same requirements in a different order -> same env URI (hash of the
    # SORTED spec), still one venv on disk
    from ray_tpu.runtime_env import normalize_pip_env

    a = normalize_pip_env(["x==1", "y==2"])
    b = normalize_pip_env(["y==2", "x==1"])
    assert a["uri"] == b["uri"]

    # conda stays rejected loudly
    @ray_tpu.remote
    def nope():
        return 1

    with pytest.raises(ValueError, match="conda"):
        nope.options(runtime_env={"conda": ["x"]}).remote()


def test_two_pumps_never_claim_one_worker(rt_rob, monkeypatch):
    """Every worker's reader thread pumps the scheduler. A pump claims an
    idle worker under the lock and dispatches after dropping it; the
    worker used to stay "idle" in between, so a second pump gave it a
    second task and overwrote ``held``: one CPU gone for good at each
    collision, and a module-scoped runtime starved after four
    (test_data.py hung one run in three)."""
    import threading

    from ray_tpu.core.runtime import _get_runtime

    rt = _get_runtime()

    @ray_tpu.remote
    def pid():
        # long enough that the two tasks below overlap: a first task that
        # had finished would free its worker for the second, rightly
        import time

        time.sleep(0.3)
        return os.getpid()

    ray_tpu.get([pid.remote() for _ in range(8)], timeout=60)  # warm pool
    total = dict(rt.total)
    first_in, gate = threading.Event(), threading.Event()
    attach = rt._attach_inline_args

    def held_back(spec):
        # the first dispatch stops between claim and "busy"
        if not first_in.is_set():
            first_in.set()
            gate.wait(10)
        return attach(spec)

    monkeypatch.setattr(rt, "_attach_inline_args", held_back)
    refs = []
    a = threading.Thread(target=lambda: refs.append(pid.remote()))
    a.start()
    assert first_in.wait(10)
    refs.append(pid.remote())      # a second pump, while the first waits
    gate.set()
    a.join(10)
    assert not a.is_alive()
    assert len(set(ray_tpu.get(refs, timeout=60))) == 2  # two workers

    from conftest import poll_until

    poll_until(lambda: rt.avail == total, timeout=10,
               desc=f"every CPU released (total {total})")
