"""Test/dev cluster: GCS + extra node daemons as local subprocesses.

Role analog: ``python/ray/cluster_utils.py:135`` (``Cluster``) whose
``add_node`` (``:201``) boots extra raylets as separate processes on one
machine — the reference's standard way to test multi-node scheduling,
transfer, and failover without real machines.

Usage::

    cluster = Cluster()                      # starts a GCS process
    cluster.add_node(resources={"worker": 1})
    ray_tpu.init(address=cluster.address)    # driver joins as head node
    ...
    cluster.shutdown()
"""

from __future__ import annotations

import os
import subprocess
import sys
import time
import uuid
from typing import Dict, List, Optional

from ray_tpu.cluster.rpc import RpcClient, free_port


class Cluster:
    def __init__(self, node_timeout_s: float = 8.0,
                 gcs_snapshot: Optional[str] = None):
        self.authkey = uuid.uuid4().hex[:16]
        self._node_timeout_s = node_timeout_s
        self._gcs_snapshot = gcs_snapshot
        self._procs: List[subprocess.Popen] = []
        self._node_procs: Dict[int, subprocess.Popen] = {}
        self._next_node = 0
        # free_port() is inherently TOCTOU: under a loaded test suite the
        # chosen port can be grabbed (or still be held by a dying server
        # from a previous cluster) before our GCS binds it, and the first
        # client then talks to a foreign listener (observed as OSError
        # "bad message length" during the auth challenge). First boot has
        # no published address yet, so just retry on a fresh port.
        last = None
        for attempt in range(3):
            self._port = free_port()
            self.address = f"127.0.0.1:{self._port}"
            self._gcs_proc = self._spawn_gcs()
            try:
                self._wait_for_gcs()
                # reconnect=True: wait_for_nodes/list_nodes retry polls
                # through transient drops — without it the first drop
                # kills the client permanently and every retry spins on
                # a dead socket
                self._client = RpcClient(self.address,
                                         self.authkey.encode(),
                                         reconnect=True)
                return
            except BaseException as e:
                last = e
                try:
                    self._gcs_proc.kill()
                    self._gcs_proc.wait(timeout=10)
                except Exception:
                    pass
                self._procs.remove(self._gcs_proc)
                if not isinstance(e, Exception):
                    raise  # interrupt / a test's limit: no orphan GCS
        raise RuntimeError(f"cluster GCS failed to boot after 3 ports: {last}")

    def _spawn_gcs(self) -> subprocess.Popen:
        cmd = [sys.executable, "-m", "ray_tpu.cluster.gcs_server",
               "--port", str(self._port), "--authkey", self.authkey,
               "--node-timeout", str(self._node_timeout_s)]
        if self._gcs_snapshot:
            cmd += ["--snapshot", self._gcs_snapshot]
        proc = subprocess.Popen(cmd, env=self._env(),
                                stdout=subprocess.DEVNULL,
                                stderr=subprocess.STDOUT)
        self._procs.append(proc)
        return proc

    def restart_gcs(self):
        """Kill + restart the GCS process on the same port (GCS FT test
        path; with a snapshot configured, durable tables survive and
        daemons re-register via heartbeat NACK)."""
        self._gcs_proc.kill()
        self._gcs_proc.wait()
        import time as _t

        _t.sleep(0.2)  # let the port free
        self._gcs_proc = self._spawn_gcs()
        self._wait_for_gcs()
        try:
            self._client.close()
        except Exception:
            pass
        self._client = RpcClient(self.address, self.authkey.encode())

    def _env(self):
        env = dict(os.environ)
        pkg_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")
        # cluster workers are CPU-only by default (same as single-node)
        env.setdefault("JAX_PLATFORMS", "cpu")
        return env

    def _wait_for_gcs(self, timeout: float = 20.0):
        deadline = time.monotonic() + timeout
        last = None
        while time.monotonic() < deadline:
            try:
                c = RpcClient(self.address, self.authkey.encode())
                assert c.call("ping", timeout=2) == "pong"
                c.close()
                return
            except Exception as e:
                last = e
                time.sleep(0.1)
        raise TimeoutError(f"gcs did not come up at {self.address}: {last}")

    def add_node(self, *, num_cpus: float = 2,
                 resources: Optional[Dict[str, float]] = None,
                 labels: Optional[Dict[str, str]] = None,
                 env: Optional[Dict[str, str]] = None,
                 wait: bool = True) -> int:
        """Boot a node daemon subprocess; returns a handle id for kill_node.

        ``env``: extra environment for the daemon (chaos tests arm
        per-daemon failpoints by exporting ``RTPU_FAILPOINTS``)."""
        import json

        node_idx = self._next_node
        self._next_node += 1
        full_env = self._env()
        full_env.update(env or {})
        proc = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu.cluster.node_daemon",
             "--gcs", self.address, "--authkey", self.authkey,
             "--num-cpus", str(num_cpus),
             "--resources", json.dumps(resources or {}),
             "--labels", json.dumps(labels or {})],
            env=full_env, stdout=subprocess.DEVNULL,
            stderr=subprocess.STDOUT,
        )
        self._node_procs[node_idx] = proc
        self._procs.append(proc)
        if wait:
            want = len([p for p in self._node_procs.values()
                        if p.poll() is None])
            self.wait_for_nodes(want)
        return node_idx

    def wait_for_nodes(self, n_daemons: int, timeout: float = 60.0):
        """Wait until ``n_daemons`` non-head nodes are alive in the GCS.

        The 60s default is an under-load margin, not an expectation: on
        this 2-vCPU box a daemon boot races pytest + watcher probes for
        CPU and the r19 flake log shows registration occasionally taking
        >30s while always completing; the poll also retries OSError —
        a daemon mid-boot can RST the probe connection, which surfaces
        as plain OSError, not its ConnectionError subclass."""
        deadline = time.monotonic() + timeout
        alive = []
        while time.monotonic() < deadline:
            try:
                nodes = self._client.call("node_list", timeout=5)
            except (OSError, TimeoutError):
                # transient GCS connection drop under load: the client
                # reconnects; a poll must retry, not abort the wait
                time.sleep(0.3)
                continue
            alive = [x for x in nodes if x["alive"] and not x["is_head"]]
            if len(alive) >= n_daemons:
                return
            time.sleep(0.1)
        raise TimeoutError(f"only {len(alive)} of {n_daemons} nodes alive")

    def list_nodes(self):
        return self._client.call("node_list", timeout=5)

    def kill_node(self, node_idx: int):
        """SIGKILL a node daemon (failure-injection; reference
        ``RayletKiller`` role)."""
        proc = self._node_procs.get(node_idx)
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait()

    def shutdown(self):
        try:
            self._client.close()
        except Exception:
            pass
        for proc in self._procs:
            if proc.poll() is None:
                proc.terminate()
        deadline = time.monotonic() + 3.0
        for proc in self._procs:
            try:
                proc.wait(max(0.1, deadline - time.monotonic()))
            except Exception:
                proc.kill()
