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


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running capacity/stress tests")


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
