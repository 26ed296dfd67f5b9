"""Event plane (ISSUE 18): lifecycle events, death postmortems, the
alerting watchdog, and log federation — local-mode unit + integration.

Multi-node shipping (heartbeat cursor, GCS node events, cross-node log
rendezvous) lives in test_cluster.py; chaos-path death assertions in
test_chaos_matrix.py. This file covers the recording plane (ring,
arming, drain), the postmortem builder (the forensics folded into
WorkerCrashedError/ActorDiedError), the Watchdog hysteresis engine with
synthetic metric views, and the single-process ends of list_events/
fetch_logs.
"""

import os
import signal
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu.util import events
from ray_tpu.util.event_store import EventStore

from conftest import poll_until


@pytest.fixture
def plane():
    """Fresh events-module state; restores the default-ON env after."""
    saved = os.environ.pop("RTPU_EVENTS", None)
    events._reset_for_tests()
    yield events
    if saved is None:
        os.environ.pop("RTPU_EVENTS", None)
    else:
        os.environ["RTPU_EVENTS"] = saved
    events._reset_for_tests()


# ---------------------------------------------------------------------------
# recording plane: ring, arming, drain
# ---------------------------------------------------------------------------

def test_events_on_by_default_and_kill_switch(plane):
    assert events.events_enabled()  # no env -> ON
    events.emit("worker_spawn", pid=1)
    assert events.ring_stats()["len"] == 1

    os.environ["RTPU_EVENTS"] = "0"
    events._reset_for_tests()
    assert not events.events_enabled()
    assert events.record("worker_spawn", pid=2) is None
    events.emit("worker_spawn", pid=2)  # no-op, not an error
    assert events.drain_ring() == []


def test_record_stamps_name_ts_severity(plane):
    rec = events.record("worker_death", worker_id="abcd1234")
    assert rec["name"] == "worker_death"
    assert rec["severity"] == "error"  # death events default to error
    assert rec["worker_id"] == "abcd1234"
    assert rec["ts"] == pytest.approx(time.time(), abs=30)
    assert events.record("worker_spawn")["severity"] == "info"
    assert events.record("actor_restart")["severity"] == "warning"
    # explicit severity wins over the catalog default
    assert events.record("worker_spawn",
                         severity="error")["severity"] == "error"


def test_ring_bounded_drains_once_and_counts_drops(plane):
    events._ring_cap = 4  # shrink the ring for the overflow path
    for i in range(6):
        events.emit("object_spill", object_id=f"{i:016x}")
    stats = events.ring_stats()
    assert stats["len"] == 4 and stats["dropped"] == 2
    batch = events.drain_ring()
    assert [e["object_id"] for e in batch] == [
        f"{i:016x}" for i in range(2, 6)]  # oldest overflowed out
    assert events.drain_ring() == []  # events leave the ring exactly once


def test_arming_flip_roundtrip(plane):
    events.disable_events()
    assert os.environ["RTPU_EVENTS"] == "0"
    assert not events.events_enabled()
    events.enable_events()
    assert os.environ["RTPU_EVENTS"] == "1"
    assert events.events_enabled()
    # apply_remote is the worker/daemon side of the same payload
    events.apply_remote({"enabled": False})
    assert not events.events_enabled()
    events.apply_remote(events.push_spec() | {"enabled": True})
    assert events.events_enabled()


def test_event_store_cursor_and_eviction():
    st = EventStore(cap=64)
    st.ingest([{"name": "worker_spawn", "i": i} for i in range(10)],
              {"node_id": "aa", "component": "raylet"})
    assert len(st) == 10
    assert st.snapshot(3)[-1]["i"] == 9
    assert st.snapshot()[0]["component"] == "raylet"  # labels stamped
    batch, start = st.since(0, max_n=4)
    assert start == 0 and [e["i"] for e in batch] == [0, 1, 2, 3]
    batch, start = st.since(4)
    assert start == 4 and [e["i"] for e in batch] == list(range(4, 10))
    # eviction advances the readable window: cursor 0 resumes at start>0
    st2 = EventStore(cap=64)  # deque floor is 64
    st2.ingest([{"i": i} for i in range(100)])
    batch, start = st2.since(0)
    assert start == 36 and batch[0]["i"] == 36


# ---------------------------------------------------------------------------
# postmortems: the death forensics builder
# ---------------------------------------------------------------------------

def test_describe_exit_cause_classes():
    assert events.describe_exit(None) == "unknown"
    assert events.describe_exit(0) == "clean_exit"
    assert events.describe_exit(3) == "exit:3"
    assert events.describe_exit(-9) == "signal:SIGKILL"
    assert events.describe_exit(-15) == "signal:SIGTERM"


def test_read_log_tail_proc_fd_fallback(tmp_path):
    """A log file deleted under a live process is still readable through
    /proc/<pid>/fd — the known 0-byte-log failure mode on this box."""
    log = tmp_path / "w.log"
    with open(log, "w") as f:
        child = subprocess.Popen(
            [sys.executable, "-c",
             "import sys,time; sys.stderr.write('RuntimeError: boom\\n');"
             "sys.stderr.flush(); time.sleep(60)"],
            stdout=subprocess.DEVNULL, stderr=f)
    try:
        poll_until(lambda: log.stat().st_size > 0, timeout=20,
                   desc="child wrote stderr")
        os.unlink(log)  # delete the file under the live process
        tail = events._read_log_tail(str(log), child.pid, 4096)
        assert "RuntimeError: boom" in tail
    finally:
        child.kill()
        child.wait()


def test_extract_error_lines_and_last_stack():
    text = "\n".join([
        "boot ok",
        "Traceback (most recent call last):",
        '  File "x.py", line 1, in <module>',
        "ValueError: first",
        "Current thread 0x00007f0000000000 (most recent call first):",
        '  File "old.py", line 9 in spin',
        "noise",
        "Current thread 0x00007f1111111111 (most recent call first):",
        '  File "new.py", line 3 in work',
        "MemoryError",
    ])
    errs = events.extract_error_lines(text)
    assert "Traceback (most recent call last):" in errs
    assert "ValueError: first" in errs and "MemoryError" in errs
    assert "boot ok" not in errs
    stack = events.extract_last_stack(text)
    assert stack.startswith("Current thread 0x00007f1111111111")
    assert "new.py" in stack and "old.py" not in stack
    assert events.extract_last_stack("no dumps here") is None


def test_build_and_format_postmortem(tmp_path):
    log = tmp_path / "worker.log"
    log.write_text("starting\nZeroDivisionError: division by zero\n")
    pm = events.build_postmortem(exit_status=1, log_path=str(log))
    assert pm["cause"] == "exit:1" and pm["exit_status"] == 1
    assert "ZeroDivisionError" in pm["stderr_tail"]
    assert pm["error_lines"] == ["ZeroDivisionError: division by zero"]
    txt = events.format_postmortem(pm)
    assert "cause: exit:1" in txt and "ZeroDivisionError" in txt
    # bounded even for a crash-loop's worth of log
    huge = events.build_postmortem(
        exit_status=-9, log_path=str(log),
        extra_field="x")
    huge["error_lines"] = ["SomeError: y" * 50] * 200
    assert len(events.format_postmortem(huge)) <= 1200
    assert events.format_postmortem(None) == ""
    # never raises on unreadable inputs
    pm2 = events.build_postmortem(exit_status=-11,
                                  log_path="/nonexistent/x.log", pid=None)
    assert pm2["cause"] == "signal:SIGSEGV" and "stderr_tail" not in pm2


# ---------------------------------------------------------------------------
# alerting watchdog: hysteresis over synthetic metric views
# ---------------------------------------------------------------------------

@pytest.fixture
def watchdog_env(plane):
    from ray_tpu.util import alerts

    saved = os.environ.pop("RTPU_ALERTS", None)
    alerts._reset_for_tests()
    yield alerts
    if saved is None:
        os.environ.pop("RTPU_ALERTS", None)
    else:
        os.environ["RTPU_ALERTS"] = saved
    alerts._reset_for_tests()


def _drained_names():
    return [e["name"] for e in events.drain_ring()]


def test_gauge_rule_hysteresis_raise_and_clear(watchdog_env):
    alerts = watchdog_env
    rule = {"name": "hot", "kind": "gauge_above", "metric": "g",
            "threshold": 0.5, "severity": "warning", "description": "d"}
    wd = alerts.Watchdog(rules=[rule], sample_fn=lambda: {})
    hot = {"g": [((), 0.9)]}
    cold = {"g": [((), 0.1)]}
    assert wd.evaluate_once(hot) == []          # tick 1: breach, no raise
    assert _drained_names() == []
    active = wd.evaluate_once(hot)              # tick 2: FOR_TICKS met
    assert [a["alert"] for a in active] == ["hot"]
    assert active[0]["value"] == 0.9 and active[0]["threshold"] == 0.5
    assert _drained_names() == ["alert_raised"]
    assert wd.evaluate_once(cold) != []         # healthy tick 1: still on
    assert wd.evaluate_once(cold) == []         # healthy tick 2: cleared
    assert _drained_names() == ["alert_cleared"]
    # no data at all: nothing flaps, nothing raises
    assert wd.evaluate_once({}) == []


def test_gauge_flapping_never_raises(watchdog_env):
    """A metric alternating around the threshold never accumulates
    FOR_TICKS consecutive breaches — hysteresis kills the flap."""
    alerts = watchdog_env
    rule = {"name": "flap", "kind": "gauge_above", "metric": "g",
            "threshold": 0.5, "severity": "warning", "description": "d"}
    wd = alerts.Watchdog(rules=[rule], sample_fn=lambda: {})
    for i in range(8):
        view = {"g": [((), 0.9 if i % 2 == 0 else 0.1)]}
        assert wd.evaluate_once(view) == []
    assert _drained_names() == []


def test_hist_p_rule_windows_bucket_deltas(watchdog_env):
    """hist_p_above quantiles the WINDOW (bucket deltas vs the previous
    tick), not cumulative history — old slowness can't page forever."""
    alerts = watchdog_env
    rule = {"name": "slow", "kind": "hist_p_above", "metric": "h",
            "q": 0.5, "threshold": 1.0, "min_count": 1,
            "severity": "warning", "description": "d"}
    wd = alerts.Watchdog(rules=[rule], sample_fn=lambda: {})
    bounds = [0.1, 1.0, 10.0]

    def view(counts, total):
        return {"h": [((), (counts, 0.0, total, bounds))]}

    # ticks 1+2: five slow observations -> p50 = 10.0 > 1.0 -> raise
    wd.evaluate_once(view([0, 0, 5], 5))
    # same cumulative counts: empty window -> below min_count -> holds
    assert wd.evaluate_once(view([0, 0, 5], 5)) == []
    active = wd.evaluate_once(view([0, 0, 6], 6))  # one more slow obs
    assert [a["alert"] for a in active] == ["slow"]
    # two windows of only-fast observations clear it
    wd.evaluate_once(view([20, 0, 6], 26))
    assert wd.evaluate_once(view([40, 0, 6], 46)) == []
    assert _drained_names() == ["alert_raised", "alert_cleared"]


def test_stall_rule_needs_depth_and_no_flow(watchdog_env):
    alerts = watchdog_env
    rule = {"name": "stall", "kind": "stall", "metric": "depth",
            "flow": "done", "min_depth": 1, "threshold": 0,
            "severity": "warning", "description": "d"}
    wd = alerts.Watchdog(rules=[rule], sample_fn=lambda: {})

    def view(depth, done):
        return {"depth": [((), depth)], "done": [((), done)]}

    assert wd.evaluate_once(view(3, 100)) == []  # first tick: baseline
    assert wd.evaluate_once(view(3, 100)) == []  # stalled tick 1
    active = wd.evaluate_once(view(3, 100))      # stalled tick 2: raise
    assert [a["alert"] for a in active] == ["stall"]
    # flow resumes (counter advances) -> clears after CLEAR_TICKS
    wd.evaluate_once(view(3, 120))
    assert wd.evaluate_once(view(2, 140)) == []


def test_watchdog_kill_switch_and_active_alerts(watchdog_env):
    alerts = watchdog_env
    os.environ["RTPU_ALERTS"] = "0"
    alerts._reset_for_tests()
    os.environ["RTPU_ALERTS"] = "0"
    assert alerts.start_watchdog() is None
    assert alerts.active_alerts() == []


def test_default_rules_evaluate_against_real_registry(watchdog_env):
    """The shipped rule table runs against this process's live metric
    view without raising (smoke: names/kinds/fields are coherent)."""
    alerts = watchdog_env
    wd = alerts.Watchdog()
    out = wd.evaluate_once()
    assert isinstance(out, list)
    rule_names = {r["name"] for r in wd.rules}
    assert {"heartbeat_gap", "queue_stall", "arena_occupancy"} <= rule_names


# ---------------------------------------------------------------------------
# runtime integration: death postmortems in user errors + local planes
# ---------------------------------------------------------------------------

@pytest.fixture
def rt(plane):
    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


def test_sigkilled_task_error_carries_postmortem(rt):
    """The r16 machine-readable contract extended with forensics: a
    SIGKILLed worker surfaces as WorkerCrashedError with
    error_type='worker_died:signal:SIGKILL', a structured postmortem,
    and the stderr excerpt folded into the message."""
    from ray_tpu.core.exceptions import WorkerCrashedError

    @ray_tpu.remote(max_retries=0)
    def doomed():
        sys.stderr.write("RuntimeError: pre-kill marker\n")
        sys.stderr.flush()
        os.kill(os.getpid(), signal.SIGKILL)

    with pytest.raises(WorkerCrashedError) as ei:
        ray_tpu.get(doomed.remote(), timeout=60)
    err = ei.value
    assert err.error_type == "worker_died:signal:SIGKILL"
    assert err.postmortem["cause"] == "signal:SIGKILL"
    assert "pre-kill marker" in err.postmortem.get("stderr_tail", "")
    assert "worker postmortem" in str(err)
    assert "pre-kill marker" in str(err)


def test_worker_death_event_visible_with_postmortem(rt):
    """Exactly one worker_death event per reaped worker, queryable via
    state.list_events, carrying the cause class and the postmortem."""
    from ray_tpu.util import state

    @ray_tpu.remote(max_retries=0)
    def seppuku():
        sys.stderr.write("ValueError: event marker\n")
        sys.stderr.flush()
        os.kill(os.getpid(), signal.SIGKILL)

    with pytest.raises(Exception):
        ray_tpu.get(seppuku.remote(), timeout=60)

    deaths = poll_until(
        lambda: [e for e in state.list_events(limit=10000)
                 if e["name"] == "worker_death"
                 and e.get("task") == "seppuku"],
        timeout=60, desc="worker_death event collected")
    assert len(deaths) == 1  # one reap -> one event
    ev = deaths[0]
    assert ev["cause"] == "signal:SIGKILL"
    assert ev["severity"] == "error"
    assert ev["component"] in ("driver", "worker")
    pm = ev["postmortem"]
    assert pm["cause"] == "signal:SIGKILL"
    assert "event marker" in pm.get("stderr_tail", "")
    # spawn events exist too (the worker had to be born to die)
    assert any(e["name"] == "worker_spawn"
               for e in state.list_events(limit=10000))
    # name filter narrows server-side
    only = state.list_events(filters=[("name", "=", "worker_death")])
    assert only and all(e["name"] == "worker_death" for e in only)


def test_fetch_logs_by_worker_and_task_id_local(rt):
    """Log federation, single-node half: a dead worker's log resolves by
    worker_id AND by task_id (via the death event), with error lines
    extracted from the tail."""
    from ray_tpu.util import state

    @ray_tpu.remote(max_retries=0)
    def shouty():
        sys.stderr.write("IndexError: log marker 123\n")
        sys.stderr.flush()
        os.kill(os.getpid(), signal.SIGKILL)

    with pytest.raises(Exception):
        ray_tpu.get(shouty.remote(), timeout=60)
    ev = poll_until(
        lambda: next((e for e in state.list_events(limit=10000)
                      if e["name"] == "worker_death"
                      and e.get("task") == "shouty"), None),
        timeout=60, desc="death event for shouty")

    rows = state.fetch_logs({"worker_id": ev["worker_id"]})
    assert rows and "log marker 123" in rows[0]["tail"]
    assert any("IndexError" in ln for ln in rows[0]["error_lines"])

    rows2 = state.fetch_logs({"task_id": ev["task_id"]})
    assert rows2 and "log marker 123" in rows2[0]["tail"]


def test_disarmed_plane_records_nothing(rt):
    """RTPU_EVENTS=0 at runtime: disable_events() stops recording in the
    driver and its workers; re-enabling restores the flow."""
    from ray_tpu.util import state

    events.disable_events()
    try:
        @ray_tpu.remote
        def ping():
            return 1

        assert ray_tpu.get(ping.remote(), timeout=60) == 1
        before = len(state.list_events(limit=100000))

        @ray_tpu.remote(max_retries=0)
        def die_quiet():
            os.kill(os.getpid(), signal.SIGKILL)

        with pytest.raises(Exception):
            ray_tpu.get(die_quiet.remote(), timeout=60)
        time.sleep(1.0)
        assert len(state.list_events(limit=100000)) == before
    finally:
        events.enable_events()


def test_dashboard_routes_and_cli(rt, capsys):
    """/api/events, /api/logs, /api/alerts serve the plane over HTTP,
    and the `rtpu events` / `rtpu logs` CLI render them (the operator
    surface: ISSUE 18 acceptance that a death is explainable end to
    end without ssh)."""
    import argparse
    import json
    import urllib.request

    from ray_tpu.dashboard import start_dashboard, stop_dashboard
    from ray_tpu import scripts

    @ray_tpu.remote(max_retries=0)
    def crash():
        sys.stderr.write("TypeError: http marker 789\n")
        sys.stderr.flush()
        os.kill(os.getpid(), signal.SIGKILL)

    with pytest.raises(Exception):
        ray_tpu.get(crash.remote(), timeout=60)

    dash = start_dashboard(port=0)
    base = f"http://127.0.0.1:{dash.port}"
    try:
        def _api(path):
            return json.loads(urllib.request.urlopen(
                base + path, timeout=15).read())["result"]

        deaths = poll_until(
            lambda: [e for e in _api("/api/events?name=worker_death")
                     if e.get("task") == "crash"],
            timeout=60, desc="death event over /api/events")
        ev = deaths[0]
        assert ev["postmortem"]["cause"] == "signal:SIGKILL"

        rows = _api(f"/api/logs?worker_id={ev['worker_id']}")
        assert rows and "http marker 789" in rows[0]["tail"]

        assert _api("/api/alerts") == []  # healthy box: nothing raised

        # CLI renderers against the same endpoints
        rc = scripts._cmd_events(argparse.Namespace(
            url=base, limit=200, name="worker_death"))
        out = capsys.readouterr().out
        assert rc == 0 and "worker_death" in out
        assert "postmortem: cause=signal:SIGKILL" in out

        rc = scripts._cmd_logs(argparse.Namespace(
            url=base, task_id=ev["task_id"], actor_id=None,
            worker_id=None, node_id=None, errors_only=True))
        out = capsys.readouterr().out
        assert rc == 0 and "TypeError: http marker 789" in out

        rc = scripts._cmd_logs(argparse.Namespace(
            url=base, task_id=None, actor_id=None, worker_id=None,
            node_id=None, errors_only=False))
        assert rc == 2  # no target given
    finally:
        stop_dashboard()
