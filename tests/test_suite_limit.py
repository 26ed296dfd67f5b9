"""The limit conftest.py gives every test, tested on a suite of its own.

pytest runs in a subprocess on a file under ``tmp_path`` with the repo's
``conftest.py`` loaded as a plugin: a body and a fixture that sleep past
their limit fail with the stacks in the report, their tear-downs still
run (the hung body holds a live module-scoped runtime: it is shut down
with the test, nothing of it survives, the next user fails fast), and the
run goes on to the next test.
"""

import os
import subprocess
import sys
import time

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))

SUITE = '''
import pathlib
import time

import pytest

LOG = pathlib.Path(__file__).with_name("torn_down")


def _note(what):
    with LOG.open("a") as f:
        f.write(what + "\\n")


@pytest.fixture
def resource():
    yield
    _note("resource")


@pytest.fixture
def hung_fixture(resource):
    time.sleep(60)
    yield


@pytest.mark.limit(10)     # set-up boots a runtime: seconds on a loaded box
def test_body_sleeps(rt_module, resource):
    @rt_module.remote
    def f():
        return 1

    assert rt_module.get(f.remote(), timeout=30) == 1   # workers are up
    time.sleep(60)


def test_next_user_of_the_hung_runtime_fails_fast(rt_module):
    # the module's runtime was shut down with the test it hung
    with pytest.raises(RuntimeError, match="init"):
        rt_module.put(1)


@pytest.mark.limit(3)
def test_fixture_sleeps(hung_fixture):
    pass


def test_wait_names_itself():
    from conftest import poll_until

    poll_until(lambda: None, timeout=0.3, desc="the wait that ran out")


def test_after():
    _note("after")
'''


def test_hung_body_and_hung_fixture_fail_in_their_limit(tmp_path):
    (tmp_path / "test_hangs.py").write_text(SUITE)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=TESTS_DIR + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    t0 = time.monotonic()
    run = subprocess.run(
        [sys.executable, "-m", "pytest", "-p", "conftest", "-p",
         "no:cacheprovider", "-p", "no:xdist", "-q", "test_hangs.py"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=90)
    took = time.monotonic() - t0
    out = run.stdout + run.stderr
    assert took < 55, out          # nobody slept its 60 s out
    # the body: failed in the call, its stacks show where it was parked,
    # and the tear-down after it found no thread or process left behind
    assert "call of test_hangs.py::test_body_sleeps passed its limit of 10s" \
        in out, out
    assert "set-up of test_hangs.py::test_fixture_sleeps passed its limit " \
        "of 3s" in out, out
    assert "stacks of all threads" in out
    assert 'test_hangs.py", line' in out     # faulthandler's frames
    assert "left behind" not in out, out
    # a wait inside a test names what ran out, long before the limit
    assert "poll_until(the wait that ran out) timed out after 0.3s" in out
    # tear-downs ran for both, and the run went on
    assert (tmp_path / "torn_down").read_text().split() == [
        "resource", "resource", "after"]
    assert "2 failed, 2 passed, 1 error" in out, out
