"""Per-node daemon: a runtime (worker pool + scheduler + store) as a process.

Role analog: the raylet (``src/ray/raylet/main.cc:123`` /
``node_manager.h:119``) — per-node worker pool, local task dispatch, local
shared-memory store, object serving to peers, heartbeats to the GCS. The
execution engine is the same ``DriverRuntime`` the single-node path uses;
the :class:`~ray_tpu.cluster.adapter.ClusterAdapter` provides the
cluster-facing RPC service and directory wiring.

Daemons never spill tasks (``is_scheduler=False``): whatever the head
forwards here runs here, mirroring the reference's lease semantics at MVP
fidelity.
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import os
import signal
import sys
import time


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--gcs", required=True, help="GCS address host:port")
    p.add_argument("--authkey", required=True)
    p.add_argument("--num-cpus", type=float, default=None)
    p.add_argument("--resources", default="{}",
                   help="extra resources as JSON, e.g. '{\"worker\": 1}'")
    p.add_argument("--labels", default="{}",
                   help="node labels as JSON, e.g. "
                        "'{\"tpu-generation\": \"v5e\"}'")
    p.add_argument("--listen-host", default="127.0.0.1")
    args = p.parse_args(argv)

    from ray_tpu.cluster.adapter import ClusterAdapter
    from ray_tpu.core.runtime import DriverRuntime

    rt = DriverRuntime(
        num_cpus=int(args.num_cpus) if args.num_cpus else None,
        num_tpus=0,
        resources=json.loads(args.resources),
        log_to_driver=False,  # daemon stdout goes nowhere useful
        labels=json.loads(args.labels),
    )
    # a dial is refused or times out in seconds (core/connection.py); a
    # GCS that is still booting or too loaded to finish one handshake is
    # no reason for a node to give up
    dial_deadline = time.monotonic() + 30.0
    while True:
        try:
            adapter = ClusterAdapter(args.gcs, args.authkey.encode(),
                                     is_scheduler=False,
                                     listen_host=args.listen_host)
            break
        except OSError:
            if time.monotonic() >= dial_deadline:
                rt.shutdown()
                raise
            time.sleep(0.5)
    adapter.attach(rt)
    # daemon uptime, refreshed whenever this process's registry snapshots
    # (heartbeat federation payloads) — a reset on the head /metrics
    # reveals a silently restarted daemon
    try:
        from ray_tpu.util import metric_defs, metrics

        started = time.monotonic()
        uptime = metric_defs.get("rtpu_daemon_uptime_seconds")
        metrics.register_collector(
            lambda: uptime.set(time.monotonic() - started))
    except Exception:
        pass
    # `kill -USR1 <daemon pid>` dumps every thread's stack — into the
    # session's log dir, NOT the daemon's stdout (spawners routinely point
    # that at /dev/null, which used to lose daemon dumps and blind
    # hung-cluster debugging; workers/pytest already log theirs).
    dump_path = os.path.join(rt.session_dir, "logs",
                             f"daemon-{rt.node_id.hex()[:8]}.log")
    try:
        dump_file = open(dump_path, "a")  # held open for process lifetime
        faulthandler.register(signal.SIGUSR1, file=dump_file,
                              all_threads=True)
    except (AttributeError, ValueError, OSError):
        dump_path = "(unavailable)"
    print(f"node daemon {rt.node_id.hex()[:8]} serving on "
          f"{adapter.server.addr} (gcs {args.gcs}); "
          f"USR1 stack dumps -> {dump_path}", flush=True)

    stop = []

    def _sig(*_):
        stop.append(True)

    signal.signal(signal.SIGTERM, _sig)
    signal.signal(signal.SIGINT, _sig)
    while not stop:
        time.sleep(0.2)
    rt.shutdown()
    sys.exit(0)


if __name__ == "__main__":
    main()
