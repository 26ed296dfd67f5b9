"""``python -m ray_tpu.scripts`` — the CLI.

Role analog: ``python/ray/scripts/scripts.py`` (``ray status/list/
timeline/job ...``) adapted to the daemonless architecture: commands that
need a cluster boot one in-process (job submit), the rest inspect local
artifacts (shm sessions, timelines, experiment dirs).
"""

from __future__ import annotations

import argparse
import json
import os


def _cmd_status(args) -> int:
    shm = [f for f in os.listdir("/dev/shm") if f.startswith("rtpu-")]
    arenas = [f for f in shm if f.startswith("rtpu-arena-")]
    print(f"shm arenas: {len(arenas)}")
    for a in arenas:
        size = os.stat(os.path.join("/dev/shm", a)).st_size
        print(f"  {a}  ({size >> 20} MiB mapped)")
    print(f"other rtpu shm segments: {len(shm) - len(arenas)}")
    if getattr(args, "url", None):
        # raised watchdog alerts from a running head (/api/alerts)
        try:
            alerts = _fetch_api(args.url, "/api/alerts") or []
        except Exception as e:
            print(f"alerts: unavailable ({e})")
            return 0
        if not alerts:
            print("alerts: none raised")
        for a in alerts:
            print(f"ALERT [{a.get('severity', '?'):7}] {a.get('alert')}: "
                  f"value={a.get('value'):.4g} "
                  f"threshold={a.get('threshold')} — "
                  f"{a.get('description', '')}")
    return 0


def _cmd_events(args) -> int:
    """``rtpu events --url http://head:8265`` — the lifecycle-event log
    (worker/actor/node deaths with postmortems, spills, serve reroutes,
    alerts), newest last. ``--name worker_death`` filters; death rows
    print their postmortem cause + first error line."""
    path = f"/api/events?limit={args.limit}"
    if args.name:
        path += f"&name={args.name}"
    evs = _fetch_api(args.url, path) or []
    import datetime

    for ev in evs:
        ts = datetime.datetime.fromtimestamp(
            ev.get("ts", 0)).strftime("%H:%M:%S")
        sev = ev.get("severity", "info")
        extras = {k: v for k, v in ev.items()
                  if k not in ("name", "ts", "severity", "postmortem")}
        kv = " ".join(f"{k}={v}" for k, v in sorted(extras.items()))
        print(f"{ts} [{sev:7}] {ev.get('name', '?'):22} {kv}")
        pm = ev.get("postmortem")
        if pm:
            print(f"    postmortem: cause={pm.get('cause', '?')}")
            for ln in (pm.get("error_lines") or [])[-3:]:
                print(f"      {ln}")
    print(f"-- {len(evs)} event(s)")
    return 0


def _cmd_devices(args) -> int:
    """``rtpu devices --url http://head:8265`` — the device plane:
    every process's compiled-program registry (compiles/retraces/cost),
    HBM watermarks, and live-buffer census, merged cluster-wide. The
    first thing to read when steps are slow: a climbing retrace count
    on one program is a recompile storm."""
    rep = _fetch_api(args.url, "/api/devices") or {}
    tot = rep.get("totals") or {}
    line = (f"{tot.get('processes', 0)} process(es), "
            f"{tot.get('programs', 0)} program row(s), "
            f"{tot.get('compiles', 0)} compile(s), "
            f"{tot.get('retraces', 0)} retrace(s)")
    hbm = tot.get("hbm")
    if hbm:
        line += (f", hbm {hbm.get('bytes_in_use', 0) / 2**30:.2f}"
                 f"/{hbm.get('bytes_limit', 0) / 2**30:.2f} GiB")
    print(line)
    rows = rep.get("programs") or []
    if not rows:
        print("(no compiled programs registered yet)")
        return 0
    print(f"{'program':<34} {'where':<24} {'compiles':>8} "
          f"{'retraces':>8} {'calls':>8} {'compile_s':>9} "
          f"{'gflop/step':>10}")
    for r in rows[:args.limit]:
        where = (f"{r.get('node_id', '?')}/"
                 f"{r.get('worker_id') or r.get('component', '?')}")
        cost = r.get("cost") or {}
        flops = cost.get("flops")
        gf = (f"{flops / max(1, int(r.get('steps', 1))) / 1e9:.2f}"
              if flops else "-")
        print(f"{r.get('program', '?'):<34} {where:<24} "
              f"{r.get('compiles', 0):>8} {r.get('retraces', 0):>8} "
              f"{r.get('calls', 0):>8} "
              f"{r.get('compile_s_total', 0.0):>9.2f} {gf:>10}")
    if args.census:
        for proc in rep.get("processes") or ():
            lb = proc.get("live_buffers")
            if not lb:
                continue
            where = (f"{proc.get('node_id', '?')}/"
                     f"{proc.get('worker_id') or proc.get('component')}"
                     f" pid={proc.get('pid', '?')}")
            print(f"-- live buffers @ {where}: {lb.get('buffers', 0)} "
                  f"({lb.get('bytes', 0) / 2**20:.1f} MiB)")
            for g in (lb.get("groups") or ())[:10]:
                shape = "x".join(str(d) for d in g.get("shape", ()))
                print(f"     {g['dtype']:<10} [{shape:<20}] "
                      f"x{g['count']:<5} {g['bytes'] / 2**20:>8.1f} MiB")
    return 0


def _cmd_logs(args) -> int:
    """``rtpu logs --task <id> --url http://head:8265`` — cluster-wide
    log federation: resolve a task/actor/worker/node id to its log
    file(s) wherever they live and print bounded tails (error lines
    first). Dead workers resolve through their death events; live
    processes whose log file was deleted are read via /proc fds."""
    target = {k: getattr(args, k) for k in ("task_id", "actor_id",
                                            "worker_id", "node_id")
              if getattr(args, k, None)}
    if not target:
        print("rtpu logs needs one of --task/--actor/--worker/--node")
        return 2
    from urllib.parse import urlencode

    rows = _fetch_api(args.url, "/api/logs?" + urlencode(target)) or []
    for r in rows:
        print(f"==== node {r.get('node_id', '?')} · {r.get('label')} "
              f"({r.get('bytes', 0)} bytes) ====")
        if args.errors_only:
            for ln in r.get("error_lines") or []:
                print(f"  {ln}")
        else:
            print(r.get("tail", ""), end="")
            if not (r.get("tail") or "").endswith("\n"):
                print()
    if not rows:
        print(f"no logs resolved for {target}")
        return 1
    return 0


def _cmd_job_submit(args) -> int:
    import ray_tpu
    from ray_tpu.job_submission import JobSubmissionClient

    ray_tpu.init(ignore_reinit_error=True)
    client = JobSubmissionClient()
    runtime_env = {}
    if args.working_dir:
        runtime_env["working_dir"] = args.working_dir
    import shlex

    job_id = client.submit_job(entrypoint=shlex.join(args.entrypoint),
                               runtime_env=runtime_env)
    print(f"submitted {job_id}")
    if args.no_wait:
        return 0
    status = client.wait_until_finished(job_id, timeout=args.timeout)
    print(client.get_job_logs(job_id), end="")
    print(f"job {job_id}: {status}")
    return 0 if status == "SUCCEEDED" else 1


def _cmd_job_list(args) -> int:
    import ray_tpu
    from ray_tpu.job_submission import JobSubmissionClient

    ray_tpu.init(ignore_reinit_error=True)
    for info in JobSubmissionClient().list_jobs():
        print(f"{info.job_id}  {info.status}  {info.entrypoint!r}")
    return 0


def _cmd_timeline(args) -> int:
    """Chrome-trace export. ``--perfetto`` writes the UNIFIED timeline
    (cluster-federated spans + flight-recorder task phases + lock-wait
    slices + train-step telemetry, one process row per node, one thread
    track per worker) — load it in ui.perfetto.dev. ``--url`` fetches the
    same document from a running head's ``/api/perfetto`` endpoint, so no
    in-process session is needed."""
    import ray_tpu

    perfetto = getattr(args, "perfetto", None)
    if perfetto:
        out = perfetto
        url = getattr(args, "url", None)
        if url:
            import urllib.request

            with urllib.request.urlopen(
                    url.rstrip("/") + "/api/perfetto", timeout=60) as resp:
                doc = json.loads(resp.read()).get("result", {})
            with open(out, "w") as f:
                json.dump(doc, f)
        else:
            if not ray_tpu.is_initialized():
                print("no active session; pass --url http://<head>:8265 "
                      "to export from a running head's dashboard")
                return 1
            from ray_tpu.util.state import export_perfetto

            doc = export_perfetto(out)
        n = len(doc.get("traceEvents", []))
        print(f"wrote {out} ({n} events) — open in ui.perfetto.dev")
        return 0
    if not ray_tpu.is_initialized():
        print("no active session in this process; timeline must be "
              "exported by the driver (ray_tpu.timeline(filename=...)) — "
              "or use --perfetto --url against a running head")
        return 1
    out = args.output or "timeline.json"
    ray_tpu.timeline(filename=out)
    print(f"wrote {out}")
    return 0


def _fetch_api(url: str, path: str):
    import urllib.request

    with urllib.request.urlopen(url.rstrip("/") + path,
                                timeout=120) as resp:
        return json.loads(resp.read()).get("result")


def _cmd_memory(args) -> int:
    """Object-memory forensics (reference ``ray memory`` role): every
    live object with size, owner, pin count + reasons, age, and the
    creating call-site when the profiler was armed. Three sources:
    --url fetches a running head's ``/api/memory``; --address dumps the
    cluster GCS object directory; otherwise the in-process driver's
    forensic view (requires an active session)."""
    rows = None
    report = None
    if getattr(args, "url", None):
        rows = _fetch_api(args.url, f"/api/memory?limit={args.limit}")
        try:
            report = _fetch_api(args.url, "/api/store")
        except Exception:
            report = None
    elif args.address:
        from ray_tpu.cluster.rpc import RpcClient

        cli = RpcClient(args.address, args.authkey.encode())
        try:
            rows = cli.call("obj_list", args.limit, timeout=30)
        finally:
            cli.close()
    else:
        import ray_tpu

        if not ray_tpu.is_initialized():
            print("no active session; pass --url http://<head>:8265 or "
                  "--address <gcs> --authkey <key>, or run inside a "
                  "driver")
            return 1
        from ray_tpu.util.state import memory_summary, store_report

        rows = memory_summary(limit=args.limit)
        report = store_report()
    total = sum(r["size"] or 0 for r in rows)
    print(f"{'OBJECT_ID':34} {'STATUS':8} {'SIZE':>12} {'PINS':>5} "
          f"{'AGE_S':>8} {'OWNER':16} REASONS")
    for r in sorted(rows, key=lambda r: -(r["size"] or 0)):
        reasons = ",".join(r.get("reasons") or ()) or "-"
        if r.get("call_site"):
            reasons += f"  @ {r['call_site']}"
        age = r.get("age_s")
        print(f"{r['object_id'][:32]:34} {r['status']:8} "
              f"{r['size'] or 0:>12} {r.get('pins', '-'):>5} "
              f"{age if age is not None else '-':>8} "
              f"{str(r.get('owner', '-'))[:16]:16} {reasons}")
    print(f"-- {len(rows)} objects, {total / 1e6:.1f} MB total")
    if report:
        frag = (f", fragmentation {report['fragmentation_pct']}% "
                f"(largest free {report.get('largest_free_bytes', 0) >> 20}"
                f" MiB over {report.get('free_blocks', '?')} blocks)"
                if "fragmentation_pct" in report else "")
        print(f"store[{report['backend']}]: "
              f"{report.get('arena_used_bytes', 0) >> 20} MiB in arena, "
              f"{report['file_segment_bytes'] >> 20} MiB file segments, "
              f"{report['spill_dir_bytes'] >> 20} MiB spilled{frag}")
    return 0


def _cmd_profile(args) -> int:
    """Cluster-wide CPU profile (the profiling plane): sample for
    --seconds (arming temporarily if needed) and write speedscope JSON /
    collapsed stacks, or print the merged top-self summary. --url runs
    against a running head's ``/api/profile`` — no in-process session
    needed."""
    fmt = ("speedscope" if (args.output or "").endswith(".json")
           else args.fmt)
    if args.url:
        q = f"/api/profile?fmt={fmt}"
        if args.seconds is not None:
            q += f"&seconds={args.seconds}"
        doc = _fetch_api(args.url, q)
    else:
        import ray_tpu

        if not ray_tpu.is_initialized():
            print("no active session; pass --url http://<head>:8265 to "
                  "profile a running head")
            return 1
        from ray_tpu.util import state

        if fmt == "speedscope":
            doc = state.export_speedscope(seconds=args.seconds)
        elif fmt == "collapsed":
            doc = state.profile_collapsed(seconds=args.seconds)
        else:
            doc = state.profile(seconds=args.seconds)
    if args.output:
        with open(args.output, "w") as f:
            if isinstance(doc, str):
                f.write(doc)
            else:
                json.dump(doc, f)
        print(f"wrote {args.output} — open at https://speedscope.app"
              if fmt == "speedscope" else f"wrote {args.output}")
        return 0
    if isinstance(doc, str):
        print(doc)
    elif fmt == "summary":
        print(f"{doc['total_samples']} samples "
              f"({doc['idle_samples']} idle) across "
              f"{len(doc['processes'])} processes")
        for comp, top in sorted(
                (doc.get("top_self_by_component") or {}).items()):
            print(f"[{comp}] top self-time:")
            for row in top[:10]:
                print(f"  {row['self_pct']:5.1f}%  "
                      f"{row['self_samples']:>6}  {row['function']}")
    else:
        print(json.dumps(doc, indent=1))
    return 0


def _cmd_serve(args) -> int:
    """`ray_tpu serve run/deploy/status/shutdown` (reference serve CLI,
    ``python/ray/serve/scripts.py`` role). `run` hosts in-process; the
    others talk REST to a running instance's dashboard."""
    import json as _json
    import urllib.request

    def rest(method: str, url: str, payload=None):
        data = _json.dumps(payload).encode() if payload is not None else None
        req = urllib.request.Request(
            url + "/api/serve/applications", data=data, method=method,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            return _json.loads(resp.read())

    if args.serve_cmd == "run":
        import ray_tpu
        from ray_tpu import serve
        from ray_tpu.dashboard import start_dashboard
        from ray_tpu.serve.config_api import (deploy_config, import_attr,
                                              load_config)

        ray_tpu.init(ignore_reinit_error=True)
        start_dashboard()
        if args.target.endswith((".yaml", ".yml", ".json")):
            names = deploy_config(load_config(args.target))
        else:
            app = import_attr(args.target)
            serve.run(app)
            names = ["default"]
        proxy = serve.start_http_proxy(port=args.http_port)
        print(f"serving {names} on http://127.0.0.1:{proxy.port} "
              f"(Ctrl-C to stop)", flush=True)
        try:
            import time as _time

            while True:
                _time.sleep(1)
        except KeyboardInterrupt:
            serve.shutdown()
            ray_tpu.shutdown()
        return 0
    if args.serve_cmd == "deploy":
        from ray_tpu.serve.config_api import load_config

        print(_json.dumps(rest("PUT", args.dashboard_url,
                               load_config(args.config)), indent=1))
        return 0
    if args.serve_cmd == "status":
        print(_json.dumps(rest("GET", args.dashboard_url), indent=1))
        return 0
    if args.serve_cmd == "shutdown":
        print(_json.dumps(rest("DELETE", args.dashboard_url), indent=1))
        return 0
    return 1


def _cmd_stack(args) -> int:
    """Dump python stacks of every live ray_tpu process (reference
    ``ray stack``, scripts.py:1830 — the py-spy role). With --url, a
    LIVE cluster-wide dump through the profiling plane: the head walks
    its own threads, pulls every worker over the control pipes, and
    fans a GCS pubsub stack request to every daemon (and ITS workers).
    Without, the local fallback: SIGUSR1+faulthandler into the session
    logs (works with no dashboard, even on wedged drivers)."""
    import signal
    import time

    if getattr(args, "url", None):
        dump = _fetch_api(args.url, "/api/stack")
        for node, procs in sorted((dump or {}).items()):
            for proc, threads in sorted(procs.items()):
                print(f"\n==== node {node} · {proc} ====")
                for tname, stack in sorted(threads.items()):
                    print(f"-- {tname}")
                    for frame in stack.split(";"):
                        print(f"   {frame}")
        return 0

    signaled = []
    for pid_dir in os.listdir("/proc"):
        if not pid_dir.isdigit():
            continue
        pid = int(pid_dir)
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmdline = f.read().replace(b"\0", b" ").decode("utf-8",
                                                               "replace")
        except OSError:
            continue
        # zygote-forked workers inherit the fork-server's cmdline
        # (ray_tpu.core.zygote); the zygote parent itself ignores SIGUSR1,
        # so signaling every match is safe and reaches all workers
        if ("ray_tpu.core.worker" in cmdline
                or "ray_tpu.core.zygote" in cmdline):
            try:
                os.kill(pid, signal.SIGUSR1)
                signaled.append(pid)
            except OSError:
                pass
    if not signaled:
        print("no live ray_tpu workers found")
        return 0
    time.sleep(0.5)  # let faulthandler write
    print(f"signaled {len(signaled)} workers: {signaled}")
    import glob

    shown = 0
    for log in sorted(glob.glob("/tmp/rtpu-*/logs/worker-*.log"),
                      key=os.path.getmtime, reverse=True):
        try:
            with open(log, errors="replace") as f:
                content = f.read()
        except OSError:
            continue
        if "Current thread" not in content:
            continue
        idx = content.rindex("Current thread")
        window = content[max(0, idx - 2000):idx + 4000]
        print(f"\n==== {log} ====")
        print(window)
        shown += 1
        if shown >= args.limit:
            break
    return 0


def _cmd_list_models(args) -> int:
    """``rtpu list models --url http://head:8265`` — per-replica model
    residency (tier, swap counters, inflight) + prefix-digest summaries,
    from the serve controller's load reports via ``/api/models``."""
    doc = _fetch_api(args.url, "/api/models") or {}
    deployments = doc.get("deployments") or {}
    if doc.get("error"):
        print(f"error: {doc['error']}")
    n_models = 0
    for dep, rec in sorted(deployments.items()):
        print(f"deployment {dep}:")
        for rid, rep in sorted((rec.get("replicas") or {}).items()):
            print(f"  replica {rid[:16]} inflight={rep.get('inflight', 0)}")
            for mid, m in sorted((rep.get("models") or {}).items()):
                n_models += 1
                extra = ""
                if "swaps_in" in m:
                    extra = (f" swaps={m.get('swaps_in', 0)}/"
                             f"{m.get('swaps_out', 0)}")
                print(f"    {mid:<24} {str(m.get('state', '-')):<8} "
                      f"inflight={m.get('inflight', 0)}{extra}")
            digest = rep.get("prefix_digest") or []
            if digest:
                tops = ", ".join(f"{d[0][:12]}:{d[1]}" for d in digest[:4])
                print(f"    prefix-digest: {tops}")
    print(f"-- {n_models} model(s) across {len(deployments)} "
          "multiplexed deployment(s)")
    return 0


def _cmd_list(args) -> int:
    """``rtpu list actors|pgs|models`` — dump the cluster GCS actor /
    placement-group directories (reference ``ray list actors`` role;
    these are the CLI senders for the ``actor_list`` / ``pg_list``
    RPCs the graftlint protocol family tracks), or the serve plane's
    model-residency report (``models``, dashboard-backed)."""
    from ray_tpu.cluster.rpc import RpcClient

    if args.what == "models":
        if not args.url:
            print("rtpu list models needs --url http://<head>:8265")
            return 2
        return _cmd_list_models(args)
    if not args.address:
        print(f"rtpu list {args.what} needs --address <gcs host:port>")
        return 2

    def _hex(v, n=32):
        return v.hex()[:n] if isinstance(v, bytes) else str(v or "-")[:n]

    cli = RpcClient(args.address, args.authkey.encode())
    try:
        if args.what == "actors":
            recs = cli.call("actor_list", timeout=30) or {}
            print(f"{'ACTOR_ID':34} {'STATE':10} {'NODE':18} NAME/CLASS")
            for aid, rec in sorted(recs.items(), key=lambda kv: _hex(kv[0])):
                label = (rec.get("name") or rec.get("class_name")
                         or rec.get("cls") or "-")
                print(f"{_hex(aid):34} {str(rec.get('state', '-')):10} "
                      f"{_hex(rec.get('node_id'), 16):18} {label}")
            print(f"-- {len(recs)} actor(s)")
        else:
            recs = cli.call("pg_list", timeout=30) or {}
            print(f"{'PG_ID':34} {'STRATEGY':12} {'BUNDLES':>7} ASSIGNED")
            for pid, rec in sorted(recs.items(), key=lambda kv: _hex(kv[0])):
                assignments = rec.get("assignments") or []
                assigned = sum(1 for a in assignments if a)
                print(f"{_hex(pid):34} {str(rec.get('strategy', '-')):12} "
                      f"{len(rec.get('bundles') or []):>7} "
                      f"{assigned}/{len(assignments)}")
            print(f"-- {len(recs)} placement group(s)")
    finally:
        cli.close()
    return 0


def _cmd_clean(args) -> int:
    import glob

    removed = 0
    for path in glob.glob("/dev/shm/rtpu-*"):
        try:
            os.unlink(path)
            removed += 1
        except OSError:
            pass
    print(f"removed {removed} shm segments")
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="ray_tpu")
    sub = p.add_subparsers(dest="cmd", required=True)

    stat = sub.add_parser("status", help="show local shm sessions/arenas "
                                         "(+ raised alerts with --url)")
    stat.add_argument("--url", default=None,
                      help="also show the watchdog's raised alerts from "
                           "a running head (http://host:8265)")

    ev = sub.add_parser("events", help="lifecycle-event log (deaths w/ "
                                       "postmortems, spills, alerts)")
    ev.add_argument("--url", default="http://127.0.0.1:8265",
                    help="running head's dashboard (http://host:8265)")
    ev.add_argument("--limit", type=int, default=200)
    ev.add_argument("--name", default=None,
                    help="only this event name (e.g. worker_death)")

    dv = sub.add_parser("devices", help="device plane: compiled-program "
                                        "registry + HBM census, "
                                        "cluster-wide")
    dv.add_argument("--url", default="http://127.0.0.1:8265",
                    help="running head's dashboard (http://host:8265)")
    dv.add_argument("--limit", type=int, default=50,
                    help="max program rows printed")
    dv.add_argument("--census", action="store_true",
                    help="also print each process's live-buffer census "
                         "grouped by shape/dtype")

    lg = sub.add_parser("logs", help="cluster-wide log fetch by task/"
                                     "actor/worker/node id")
    lg.add_argument("--url", default="http://127.0.0.1:8265",
                    help="running head's dashboard (http://host:8265)")
    lg.add_argument("--task", dest="task_id", default=None)
    lg.add_argument("--actor", dest="actor_id", default=None)
    lg.add_argument("--worker", dest="worker_id", default=None)
    lg.add_argument("--node", dest="node_id", default=None)
    lg.add_argument("--errors-only", action="store_true",
                    help="print only the extracted error lines")
    sub.add_parser("config", help="print every runtime knob (name, env "
                                  "var, default, current value)")
    sub.add_parser("clean", help="remove leftover rtpu shm segments")

    tl = sub.add_parser("timeline", help="export chrome trace")
    tl.add_argument("--output", "-o", default=None)
    tl.add_argument("--perfetto", metavar="OUT.json", default=None,
                    help="write the unified cluster timeline (spans + "
                         "task phases + lock waits + train steps) for "
                         "ui.perfetto.dev")
    tl.add_argument("--url", default=None,
                    help="with --perfetto: fetch from a running head's "
                         "dashboard (http://host:8265) instead of an "
                         "in-process session")

    mem = sub.add_parser("memory", help="object-memory forensics "
                                        "(reference `ray memory` role)")
    mem.add_argument("--address", default=None,
                     help="GCS address host:port (cluster mode)")
    mem.add_argument("--authkey", default="",
                     help="cluster authkey (with --address)")
    mem.add_argument("--url", default=None,
                     help="fetch from a running head's dashboard "
                          "(http://host:8265) instead of in-process")
    mem.add_argument("--limit", type=int, default=10000)

    ls = sub.add_parser("list", help="list cluster actors / placement "
                                     "groups / served models")
    ls.add_argument("what", choices=["actors", "pgs", "models"])
    ls.add_argument("--address", default=None,
                    help="GCS address host:port (actors/pgs)")
    ls.add_argument("--authkey", default="", help="cluster authkey")
    ls.add_argument("--url", default=None,
                    help="dashboard URL http://host:8265 (models)")

    st = sub.add_parser("stack", help="dump python stacks of live "
                                      "ray_tpu processes (py-spy role)")
    st.add_argument("--limit", type=int, default=16)
    st.add_argument("--url", default=None,
                    help="live cluster-wide dump via a running head's "
                         "dashboard (http://host:8265); default: local "
                         "SIGUSR1 into session logs")

    prof = sub.add_parser("profile",
                          help="cluster-wide sampling profile "
                               "(flamegraph/speedscope export)")
    prof.add_argument("--seconds", type=float, default=2.0,
                      help="sampling window; arms the profiler "
                           "temporarily when not already armed")
    prof.add_argument("--output", "-o", default=None,
                      help="write here (.json => speedscope)")
    prof.add_argument("--fmt", default="summary",
                      choices=["summary", "speedscope", "collapsed"])
    prof.add_argument("--url", default=None,
                      help="profile a running head via its dashboard "
                           "(http://host:8265)")

    up = sub.add_parser("up", help="launch a cluster from a yaml "
                                   "(reference `ray up` role)")
    up.add_argument("config")
    down = sub.add_parser("down", help="tear a cluster down")
    down.add_argument("config")

    srv = sub.add_parser("serve", help="serve deploy/run/status/shutdown "
                                       "(reference `serve` CLI role)")
    srvsub = srv.add_subparsers(dest="serve_cmd", required=True)
    sr = srvsub.add_parser("run", help="deploy a config or app and block")
    sr.add_argument("target", help="config.yaml OR module:app import path")
    sr.add_argument("--http-port", type=int, default=8000)
    sd = srvsub.add_parser("deploy",
                           help="PUT a config to a running instance's "
                                "dashboard REST endpoint")
    sd.add_argument("config")
    sd.add_argument("--dashboard-url", default="http://127.0.0.1:8265")
    ss = srvsub.add_parser("status")
    ss.add_argument("--dashboard-url", default="http://127.0.0.1:8265")
    sx = srvsub.add_parser("shutdown")
    sx.add_argument("--dashboard-url", default="http://127.0.0.1:8265")

    job = sub.add_parser("job", help="job submission")
    jobsub = job.add_subparsers(dest="job_cmd", required=True)
    js = jobsub.add_parser("submit")
    js.add_argument("--working-dir", default=None)
    js.add_argument("--no-wait", action="store_true")
    js.add_argument("--timeout", type=float, default=600.0)
    js.add_argument("entrypoint", nargs=argparse.REMAINDER)
    jobsub.add_parser("list")

    args = p.parse_args(argv)
    if args.cmd == "status":
        return _cmd_status(args)
    if args.cmd == "config":
        from ray_tpu import config as _config

        rows = _config.describe()
        w = max(len(r["env"]) for r in rows)
        for r in rows:
            mark = " *" if r["overridden"] else "  "
            print(f"{r['env']:<{w}}{mark} {r['current']!r:>14}  "
                  f"(default {r['default']!r}) — {r['doc']}")
        print("\n(* = overridden via environment)")
        return 0
    if args.cmd == "clean":
        return _cmd_clean(args)
    if args.cmd == "timeline":
        return _cmd_timeline(args)
    if args.cmd == "memory":
        return _cmd_memory(args)
    if args.cmd == "list":
        return _cmd_list(args)
    if args.cmd == "stack":
        return _cmd_stack(args)
    if args.cmd == "events":
        return _cmd_events(args)
    if args.cmd == "devices":
        return _cmd_devices(args)
    if args.cmd == "logs":
        return _cmd_logs(args)
    if args.cmd == "profile":
        return _cmd_profile(args)
    if args.cmd == "up":
        from ray_tpu.autoscaler import launcher

        out = launcher.up(launcher.load_config(args.config))
        print(f"head {'created' if out['head_created'] else 'alive'}: "
              f"{out['head'].node_id} @ {out['address']}; "
              f"{len(out['workers_started'])} worker host(s) started")
        return 0
    if args.cmd == "down":
        from ray_tpu.autoscaler import launcher

        n = launcher.down(launcher.load_config(args.config))
        print(f"terminated {n} node(s)/slice(s)")
        return 0
    if args.cmd == "serve":
        return _cmd_serve(args)
    if args.cmd == "job":
        if args.job_cmd == "submit":
            return _cmd_job_submit(args)
        if args.job_cmd == "list":
            return _cmd_job_list(args)
    return 2


if __name__ == "__main__":
    raise SystemExit(main())
