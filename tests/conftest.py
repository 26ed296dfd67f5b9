import os
import sys

# Must happen before any jax import anywhere in the test session: run tests
# on a virtual 8-device CPU mesh so multi-chip sharding logic is exercised
# without TPU hardware (the driver separately dry-runs the multichip path).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402

# `kill -USR1 <pytest pid>` dumps all thread stacks — the only way to see
# where the DRIVER side of a hung cluster test is parked (workers already
# register this in worker.py).
import faulthandler  # noqa: E402
import signal  # noqa: E402

try:
    faulthandler.register(signal.SIGUSR1, all_threads=True)
except (AttributeError, ValueError):
    pass


#: Seconds each phase of a test (set-up, call, tear-down) may take before
#: it FAILS with the stacks of every thread. About three times the longest
#: honest test of the suite; a test measured to need more says
#: ``@pytest.mark.limit(<seconds>)``; one marked ``slow`` (outside tier-1:
#: capacity and stress runs) gets five times as long. A wait inside a
#: test is a few times what the passing run needs, never minutes, so the
#: wait's own message (which names what ran out) comes long before this
#: one.
TEST_LIMIT_S = 120


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running capacity/stress tests")
    config.addinivalue_line(
        "markers", "limit(seconds): this test's phases may take up to "
        "`seconds` instead of conftest.TEST_LIMIT_S")


def _limited(item, phase):
    """Run one phase of ``item`` under its limit: SIGALRM in the main
    thread (where pytest and xdist run tests) raises a failure from
    wherever the phase is parked, so fixtures still tear down what the
    test started and the run goes on to the next test."""
    marker = item.get_closest_marker("limit")
    if marker:
        limit = float(marker.args[0])
    elif item.get_closest_marker("slow"):
        limit = 5.0 * TEST_LIMIT_S
    else:
        limit = float(TEST_LIMIT_S)

    expired = []

    def _expired(signum, frame):
        import tempfile

        expired.append(phase)

        with tempfile.TemporaryFile("w+") as f:
            faulthandler.dump_traceback(file=f, all_threads=True)
            f.seek(0)
            stacks = f.read()
        pytest.fail(f"{phase} of {item.nodeid} passed its limit of "
                    f"{limit:g}s; stacks of all threads:\n{stacks}",
                    pytrace=False)

    previous = signal.signal(signal.SIGALRM, _expired)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)
        if expired:
            # a runtime that hung a test is not handed to the next one:
            # with a module-scoped runtime every later test of the file
            # would sit out its own limit (24 x 120 s in test_data.py)
            import ray_tpu

            ray_tpu.shutdown()


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    return (yield from _limited(item, "set-up"))


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    return (yield from _limited(item, "call"))


@pytest.hookimpl(wrapper=True)
def pytest_runtest_teardown(item):
    return (yield from _limited(item, "tear-down"))


def poll_until(predicate, timeout=30.0, interval=0.2, desc="condition"):
    """Retry ``predicate`` until it returns a truthy value (returned).

    Deflake helper for cluster tests (round-5 flake notes): transient
    ``ConnectionError``/``TimeoutError``/``OSError`` raised by a poll —
    a GCS client mid-reconnect, an HTTP scrape racing server start — are
    retried instead of failing the test; any other exception propagates.
    Raises AssertionError with the last transient error on timeout.
    """
    import time as _time

    deadline = _time.monotonic() + timeout
    last_exc = None
    while _time.monotonic() < deadline:
        try:
            val = predicate()
            if val:
                return val
            last_exc = None
        except (ConnectionError, TimeoutError, OSError) as e:
            last_exc = e
        _time.sleep(interval)
    raise AssertionError(
        f"poll_until({desc}) timed out after {timeout}s"
        + (f"; last transient error: {last_exc!r}" if last_exc else ""))


def watch_step_widths(engine):
    """Record the real positions of every step ``engine`` (an
    ``LLMEngine``) plans from here on: returns the list they are appended
    to, for :func:`assert_three_widths` once the engine has drained."""
    reals, plan = [], engine._plan

    def watched(*args):
        out = plan(*args)
        reals.append(out[2])
        return out

    engine._plan = watched
    return reals


def assert_three_widths(engine, reals):
    """The drained ``engine`` ran steps at each of its step program's three
    widths, and its counters say what the program's rule gives each step of
    ``reals`` (:func:`watch_step_widths`, from the engine's first step):
    the budget, twice it, or the whole grid, the narrowest that holds the
    step's real positions. Read ``STEP_BUDGET`` as the test patched it."""
    from ray_tpu.serve import llm

    budget, grid = llm.STEP_BUDGET, engine.max_slots * engine.prefill_chunk
    assert 2 * budget < grid
    want = [budget if real <= budget
            else 2 * budget if real <= 2 * budget else grid
            for real in reals]
    s = engine.stats
    assert s["step_positions_real"] == sum(reals)
    assert s["step_positions_run"] == sum(want)
    assert want.count(budget) > 0
    assert s["steps_second_width"] == want.count(2 * budget) > 0
    assert s["steps_full_width"] == want.count(grid) > 0
    assert s["step_s_second_width"] > 0
    # the second width is counted BESIDE a step's kind: three kinds still
    assert s["steps"] == len(reals) == (
        s["steps_decode_only"] + s["steps_chunk"] + s["steps_full_width"])


@pytest.fixture(scope="session", autouse=True)
def _native_build_contract():
    """The native extension is either fully loaded or cleanly fallen
    back — never a silent half-state (r14 satellite): a .so that loads
    but lacks the pipe-engine symbols after the automatic rebuild is a
    broken build this suite refuses to paper over."""
    from ray_tpu import _native

    st = _native.native_status()
    assert not st.get("stale"), (
        f"native extension half-state {st}: the .so loaded but lacks the "
        f"pipe engine after a rebuild attempt — run `make -C native` and "
        f"check compiler output")
    # loaded implies every feature family is bound; not loaded means the
    # pure-Python fallbacks are active everywhere (a consistent state)
    if st["loaded"]:
        assert st["pipe"] and st["lz4"], st
    yield


def ray_tpu_leftovers(grace_s=5.0):
    """What a shut-down runtime must not leave in this process: threads
    still running ``ray_tpu`` code, and child processes. Both are given
    ``grace_s`` to finish dying; returns a description of the survivors
    (empty when clean)."""
    import threading
    import time as _time

    import psutil

    import ray_tpu

    pkg = os.path.dirname(os.path.abspath(ray_tpu.__file__)) + os.sep
    names = {t.ident: t.name for t in threading.enumerate()}

    def survivors():
        out = []
        me = threading.get_ident()
        for ident, frame in sys._current_frames().items():
            f, stack = frame, []
            while f is not None:
                stack.append(f"{f.f_code.co_filename}:{f.f_lineno} "
                             f"{f.f_code.co_name}")
                f = f.f_back
            if ident != me and any(s.startswith(pkg) for s in stack):
                out.append(f"thread {names.get(ident, ident)}: "
                           + " <- ".join(stack[:4]))
        for child in psutil.Process().children(recursive=True):
            try:
                if child.status() != psutil.STATUS_ZOMBIE:
                    out.append(f"process {child.pid}: "
                               f"{' '.join(child.cmdline())[:160]}")
            except psutil.NoSuchProcess:
                pass
        return out

    deadline = _time.monotonic() + grace_s
    while (left := survivors()) and _time.monotonic() < deadline:
        _time.sleep(0.05)
    return left


@pytest.fixture(autouse=True)
def _nothing_left_behind():
    """After every test that leaves no runtime up (a module-scoped one is
    checked after its module's last user), nothing of ray_tpu may still
    run in or under this process: a leaked accept thread skews the next
    test's profile, a leaked daemon answers the next test's cluster."""
    yield
    from ray_tpu.core import runtime

    if runtime._runtime is not None:
        return
    left = ray_tpu_leftovers()
    if left:
        import psutil

        for child in psutil.Process().children(recursive=True):
            child.kill()  # the next test starts clean either way
        pytest.fail("left behind after tear-down:\n" + "\n".join(left),
                    pytrace=False)


@pytest.fixture
def rt():
    import ray_tpu

    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture(scope="module")
def rt_module():
    import ray_tpu

    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture
def cluster():
    """A GCS process; tests add node daemons and join as the head
    (test_cluster*.py)."""
    import ray_tpu
    from ray_tpu.cluster import Cluster

    c = Cluster()
    yield c
    ray_tpu.shutdown()
    c.shutdown()


def _init(c, **kw):
    import ray_tpu

    return ray_tpu.init(address=c.address, cluster_authkey=c.authkey,
                        num_cpus=2, **kw)


def _wait_nodes(n, timeout=15):
    import ray_tpu

    # poll_until retries transient GCS connection drops under suite load
    poll_until(
        lambda: len([x for x in ray_tpu.nodes() if x["Alive"]]) >= n,
        timeout=timeout, desc=f"cluster reaches {n} nodes")
