"""GCS server process: cluster control plane.

Role analog: ``src/ray/gcs/gcs_server/gcs_server.cc:307-692`` — node table
with heartbeat health checks (``GcsHealthCheckManager``), global object
directory with locations (``ownership_based_object_directory.h`` role),
InternalKV (``gcs_kv_manager.h``), function table
(``gcs_function_manager.h``), named-actor registry
(``gcs_actor_manager.h``), and pubsub (``src/ray/pubsub``) collapsed into
one threaded process over the message-RPC layer.

State is deliberately coarse: per-node execution detail (worker pools,
actor call queues) lives in the node daemons; the GCS holds only what must
be globally consistent.
"""

from __future__ import annotations

import argparse
import faulthandler
import signal
import threading
import time
from typing import Any, Dict, Optional, Set

from ray_tpu import config
from ray_tpu.cluster.rpc import RpcServer, ServerConn

DEFAULT_HEARTBEAT_S = 1.0
DEFAULT_NODE_TIMEOUT_S = 5.0

PENDING, READY, ERROR = "PENDING", "READY", "ERROR"


class _GlobalObject:
    __slots__ = ("status", "inline", "error", "size", "locations",
                 "pins", "was_pinned", "t_terminal")

    def __init__(self):
        self.status = PENDING
        self.inline: Optional[bytes] = None
        self.error: Optional[bytes] = None
        self.size = 0
        self.locations: Set[bytes] = set()  # node ids holding the segment
        # distributed refcount (reference reference_count.h:61 role):
        # nodes with >=1 live reference. Pinned entries are never evicted;
        # when the LAST pin drops on a terminal object that was ever
        # pinned, holders are told to free their segments.
        self.pins: Set[bytes] = set()
        self.was_pinned = False
        self.t_terminal = 0.0


class _NodeEntry:
    __slots__ = ("node_id", "addr", "resources", "avail", "last_seen",
                 "alive", "is_head", "labels", "stats")

    def __init__(self, node_id: bytes, addr: str, resources: Dict[str, float],
                 is_head: bool, labels: Optional[Dict[str, str]] = None):
        self.node_id = node_id
        self.addr = addr  # node daemon RPC address ("" for the driver/head)
        self.resources = dict(resources)
        self.avail = dict(resources)
        self.last_seen = time.monotonic()
        self.alive = True
        self.is_head = is_head
        # static key=value node labels (reference NodeLabels): TPU
        # generation / slice type / user labels, set at node start
        self.labels = dict(labels or {})
        # latest host utilization sample from the heartbeat (reporter role)
        self.stats: Dict = {}


class GcsService:
    def __init__(self, node_timeout_s: float = DEFAULT_NODE_TIMEOUT_S,
                 snapshot_path: Optional[str] = None):
        import os

        from ray_tpu.util.contention import timed_rlock

        # one coarse state lock — instrumented, because every RPC handler
        # serializes on it (the "is the GCS the bottleneck?" question is
        # answered by this lock's wait histogram)
        self.lock = timed_rlock("gcs.state")
        # built-in GCS metrics (defs in util/metric_defs.py; exported to
        # the head /metrics by rpc_metrics_get with component=gcs labels)
        from ray_tpu.util import metric_defs as _md

        self._m_rpc = _md.get("rtpu_gcs_rpc_total")
        self._m_rpc_lat = _md.get("rtpu_gcs_rpc_seconds")
        self._m_pubsub = _md.get("rtpu_gcs_pubsub_messages_total")
        self._m_tables = _md.get("rtpu_gcs_table_size")
        self._m_alive = _md.get("rtpu_gcs_nodes_alive")
        self._m_hb_gap = _md.get("rtpu_gcs_heartbeat_gap_seconds")
        self._method_keys: Dict[str, tuple] = {}
        self._channel_keys: Dict[str, tuple] = {}
        self.nodes: Dict[bytes, _NodeEntry] = {}
        self.objects: Dict[bytes, _GlobalObject] = {}
        self.max_objects = int(config.get("gcs_max_objects"))
        self.evict_min_age_s = float(config.get("gcs_evict_min_age_s"))
        # refcount-zero objects are freed after a GRACE, not inline: a
        # consumer's pin cast rides a different connection than the
        # producer's obj_ready, so "no pins right now" can be an in-flight
        # pin (freeing inline deleted entries a consumer was about to
        # watch, hanging its get forever)
        self.free_grace_s = float(config.get("gcs_free_grace_s"))
        self._free_candidates: Dict[bytes, float] = {}
        # oids swept by the free path: a late pin on one of these gets a
        # terminal ObjectLostError entry instead of a silent empty PENDING
        self._freed_tombstones: Dict[bytes, float] = {}
        # cluster-wide task events (reference GcsTaskManager store)
        from collections import deque

        self.task_events = deque(maxlen=int(config.get("gcs_max_task_events")))
        # per-node high-water mark of received task-event sequence numbers
        # (dedup for cursor rewinds after node re-registration)
        self._task_ev_seq: Dict[bytes, int] = {}
        # trace plane: collected spans shipped on node heartbeats (same
        # cursor+dedup contract as task_events); head /api/traces and
        # state.list_spans pull via rpc_trace_events_get
        self.trace_events = deque(
            maxlen=int(config.get("gcs_max_trace_events")))
        self._trace_ev_seq: Dict[bytes, int] = {}
        # profiling plane: profile batches shipped on node heartbeats
        # (same cursor+dedup contract); head state.profile() pulls via
        # rpc_profile_events_get. Stack-dump request/reply rendezvous for
        # the cluster-wide `ray_tpu stack` (py-spy role).
        self.profile_events = deque(
            maxlen=int(config.get("gcs_max_profile_events")))
        self._profile_ev_seq: Dict[bytes, int] = {}
        self._stack_req_seq = 0
        self._stack_replies: Dict[int, Dict[str, Any]] = {}
        # event plane: lifecycle events shipped on node heartbeats (same
        # cursor+dedup contract); the GCS appends its OWN node-lifecycle
        # events (register / death) here directly. Log-fetch rendezvous
        # for `rtpu logs` mirrors the stack-dump rendezvous above.
        self.lifecycle_events = deque(
            maxlen=int(config.get("gcs_max_lifecycle_events")))
        self._lifecycle_ev_seq: Dict[bytes, int] = {}
        self._log_req_seq = 0
        self._log_replies: Dict[int, Dict[str, Any]] = {}
        # metrics federation: latest [(origin_labels, records)] payload per
        # node, replaced wholesale on each carrying heartbeat (idempotent;
        # reference metrics-agent -> head pipeline role). Head /metrics
        # pulls via rpc_metrics_get at scrape time.
        self._node_metrics: Dict[bytes, list] = {}
        # device plane: latest process-entry list per node (compiled-
        # program registries + HBM census), replaced on each heartbeat
        # ride like _node_metrics — idempotent, self-healing
        self._node_devices: Dict[bytes, list] = {}
        self.kv: Dict[str, Dict[str, bytes]] = {}
        self.functions: Dict[str, bytes] = {}
        # named/global actor registry: actor_id -> record dict
        self.actors: Dict[bytes, Dict[str, Any]] = {}
        self.named_actors: Dict[str, bytes] = {}
        # placement groups (reference GcsPlacementGroupManager): pg_id ->
        # {"bundles": [res dicts], "strategy", "assignments": [node_id or
        # None per bundle], "creator": node_id}. The GCS records placement
        # decisions; the 2-phase reservation itself runs creator->daemons.
        self.pgs: Dict[bytes, Dict[str, Any]] = {}
        self.node_timeout_s = node_timeout_s
        self.server: Optional[RpcServer] = None
        self._stop = threading.Event()
        # Fault tolerance (reference: GCS tables over a Redis StoreClient,
        # gcs/store_client/redis_store_client.h): durable tables persist
        # through a pluggable StoreClient (gcs_store.py) — a file snapshot
        # by default, or an EXTERNAL sqlite database ("sqlite://<path>")
        # that survives head-node disk loss. A restarted GCS reloads them,
        # nodes re-register via heartbeat NACK, and the directory
        # repopulates as owners publish. objects/nodes are runtime state
        # and deliberately NOT persisted.
        from ray_tpu.cluster.gcs_store import make_store_client

        self.snapshot_path = snapshot_path
        self._store = make_store_client(snapshot_path)
        self._dirty = False
        if self._store is not None:
            self._load_snapshot()
            threading.Thread(target=self._snapshot_loop, daemon=True,
                             name="gcs-snapshot").start()

    def _load_snapshot(self):
        snap = self._store.load()
        if not snap:
            return
        self.kv = snap.get("kv", {})
        self.functions = snap.get("functions", {})
        self.actors = snap.get("actors", {})
        self.named_actors = snap.get("named_actors", {})
        self.pgs = snap.get("pgs", {})

    def _snapshot_loop(self):
        while not self._stop.wait(1.0):
            with self.lock:
                if not self._dirty:
                    continue
                snap = {"kv": {ns: dict(d) for ns, d in self.kv.items()},
                        "functions": dict(self.functions),
                        "actors": {a: dict(r)
                                   for a, r in self.actors.items()},
                        "named_actors": dict(self.named_actors),
                        "pgs": {p: dict(r) for p, r in self.pgs.items()}}
                self._dirty = False
            if not self._store.save(snap):
                # transient store failure (lock/IO): the snapshot was NOT
                # persisted — re-arm so the next tick retries even if no
                # new mutation arrives
                with self.lock:
                    self._dirty = True

    # ------------------------------------------------------------------
    # RPC dispatch
    # ------------------------------------------------------------------

    def handle(self, method: str, args: tuple, ctx: ServerConn) -> Any:
        fn = getattr(self, "rpc_" + method, None)
        if fn is None:
            raise AttributeError(f"gcs: unknown method {method!r}")
        # per-method RPC count + latency (reference metric_defs.cc GCS
        # rpc metrics role); cached pre-sorted keys keep this at two
        # metric-lock hops per call
        keys = self._method_keys
        key = keys.get(method) or keys.setdefault(
            method, (("method", method),))
        t0 = time.perf_counter()
        try:
            return fn(ctx, *args)
        finally:
            self._m_rpc._inc_key(key)
            self._m_rpc_lat._observe_key(key, time.perf_counter() - t0)

    # -- nodes ----------------------------------------------------------

    def rpc_node_register(self, ctx, node_id: bytes, addr: str,
                          resources: Dict[str, float], is_head: bool,
                          labels: Optional[Dict[str, str]] = None):
        with self.lock:
            # returned to the caller: False = this GCS had no entry for
            # the node (fresh process after a restart — dead entries are
            # kept with alive=False, so a blackout re-register stays
            # True). A re-registering daemon uses it to detect GCS state
            # loss (the gcs_restart lifecycle event).
            known = node_id in self.nodes
            self.nodes[node_id] = _NodeEntry(node_id, addr, resources,
                                             is_head, labels)
        ctx.meta["node_id"] = node_id
        ctx.on_close = self._conn_closed
        self._publish("nodes", {"event": "up", "node_id": node_id,
                                "addr": addr, "resources": dict(resources),
                                "labels": dict(labels or {})})
        try:
            from ray_tpu.util import events as _events

            self._append_lifecycle(_events.record(
                "node_register", node_id=node_id.hex()[:8], addr=addr,
                is_head=bool(is_head), component="gcs"))
        except Exception:
            pass
        return known

    def rpc_node_heartbeat(self, ctx, node_id: bytes,
                           avail: Dict[str, float], queue_depth: int,
                           stats: Optional[Dict] = None,
                           metrics: Optional[list] = None):
        with self.lock:
            ent = self.nodes.get(node_id)
            if ent is None:
                return False
            # inter-heartbeat gap (nominal 0.5s): the cheapest cluster-
            # wide contention canary — a loaded sender or GCS stretches it
            self._m_hb_gap._observe_key(
                (), time.monotonic() - ent.last_seen)
            if metrics is not None:
                self._node_metrics[node_id] = metrics
            changed = ent.avail != avail
            ent.avail = dict(avail)
            if stats:
                # host utilization sample (reporter-module role) — rides
                # the heartbeat, surfaces via node_list/dashboard. The
                # timestamp lets readers spot a dead reporter (a node
                # whose sampling fails keeps heartbeating with stats
                # None, so ts stops advancing).
                ent.stats = dict(stats, ts=time.time())
            ent.last_seen = time.monotonic()
            if not ent.alive:
                ent.alive = True
        if changed:
            # streaming resource gossip (reference ray_syncer,
            # ray_syncer.h:88 role): subscribers patch their node views
            # from these deltas instead of re-polling node_list
            self._publish("nodes", {"event": "resources",
                                    "node_id": node_id,
                                    "avail": dict(avail),
                                    "depth": queue_depth})
        return True

    def rpc_node_list(self, ctx):
        with self.lock:
            return [
                {"node_id": e.node_id, "addr": e.addr, "alive": e.alive,
                 "resources": dict(e.resources), "avail": dict(e.avail),
                 "is_head": e.is_head, "labels": dict(e.labels),
                 "stats": dict(e.stats)}
                for e in self.nodes.values()
            ]

    def rpc_node_drain(self, ctx, node_id: bytes):
        self._mark_node_dead(node_id, "drained")
        return True

    def _conn_closed(self, ctx: ServerConn):
        node_id = ctx.meta.get("node_id")
        if node_id is not None:
            self._mark_node_dead(node_id, "connection lost")

    def _mark_node_dead(self, node_id: bytes, cause: str):
        with self.lock:
            ent = self.nodes.get(node_id)
            if ent is None or not ent.alive:
                return
            ent.alive = False
            # stop serving the dead node's frozen metric samples (a
            # reconnecting node reships a full snapshot on its next
            # carrying heartbeat, so nothing is lost on a blip)
            self._node_metrics.pop(node_id, None)
            self._node_devices.pop(node_id, None)
            # _task_ev_seq is deliberately NOT popped here: a node marked
            # dead by a connection blip keeps its node_id, reconnects, and
            # reships history from seq 0 — the high-water mark is what
            # dedups that reshipment (advisor r3). Entries thus live as
            # long as the node record itself (self.nodes also keeps dead
            # entries), so growth is bounded by distinct nodes per cluster
            # lifetime, not leaked beyond it.
            # objects whose only copies lived there are lost
            lost = [oid for oid, o in self.objects.items()
                    if o.status == READY and o.inline is None
                    and o.locations and o.locations <= {node_id}]
            for oid in lost:
                o = self.objects[oid]
                o.status = PENDING
                o.locations.discard(node_id)
            # a dead node's references die with it; objects it alone kept
            # alive free (after the grace) on the surviving holders
            for oid, o in self.objects.items():
                if node_id in o.pins:
                    o.pins.discard(node_id)
                    self._mark_free_candidate_locked(oid, o)
            # actors hosted there are dead (restart is the owner's call)
            dead_actors = [aid for aid, rec in self.actors.items()
                           if rec.get("node_id") == node_id
                           and rec.get("state") != "DEAD"]
            for aid in dead_actors:
                self.actors[aid]["state"] = "DEAD"
                name = self.actors[aid].get("name")
                if name:
                    self.named_actors.pop(name, None)
            # bundles reserved there are released (reference
            # gcs_placement_group_scheduler node-death bundle release);
            # the creating adapter reschedules them on live nodes
            lost_pgs: Dict[bytes, list] = {}
            for pg_id, rec in self.pgs.items():
                idxs = [i for i, nid in enumerate(rec["assignments"])
                        if nid == node_id]
                if idxs:
                    for i in idxs:
                        rec["assignments"][i] = None
                    lost_pgs[pg_id] = idxs
                    self._dirty = True
        self._publish("nodes", {"event": "down", "node_id": node_id,
                                "cause": cause, "lost_objects": lost,
                                "dead_actors": dead_actors,
                                "lost_pgs": lost_pgs})
        try:
            from ray_tpu.util import events as _events

            # the node-death postmortem is the BLAST RADIUS — there is
            # no process left to read a stderr tail from, so the useful
            # forensics are what the cluster lost with the node
            self._append_lifecycle(_events.record(
                "node_death", node_id=node_id.hex()[:8], cause=cause,
                component="gcs",
                postmortem={"cause": cause,
                            "lost_objects": len(lost),
                            "dead_actors": len(dead_actors),
                            "lost_pg_bundles": sum(
                                len(v) for v in lost_pgs.values())}))
        except Exception:
            pass

    def _health_loop(self):
        while not self._stop.wait(DEFAULT_HEARTBEAT_S):
            now = time.monotonic()
            with self.lock:
                stale = [e.node_id for e in self.nodes.values()
                         if e.alive and not e.is_head
                         and now - e.last_seen > self.node_timeout_s]
            for node_id in stale:
                self._mark_node_dead(node_id, "heartbeat timeout")
            self._sweep_free_candidates()
            self._sample_table_sizes()

    def _sample_table_sizes(self):
        """Refresh the table-size gauges once per health tick (~1s) —
        operators read growth trends, not per-mutation precision."""
        try:
            with self.lock:
                sizes = {"objects": len(self.objects),
                         "nodes": len(self.nodes),
                         "actors": len(self.actors),
                         "kv": sum(len(d) for d in self.kv.values()),
                         "functions": len(self.functions),
                         "pgs": len(self.pgs),
                         "task_events": len(self.task_events),
                         "trace_events": len(self.trace_events),
                         "profile_events": len(self.profile_events),
                         "lifecycle_events": len(self.lifecycle_events),
                         "free_candidates": len(self._free_candidates),
                         "tombstones": len(self._freed_tombstones)}
                alive = sum(1 for e in self.nodes.values() if e.alive)
            for t, n in sizes.items():
                self._m_tables.set(n, tags={"table": t})
            self._m_alive.set(alive)
        except Exception:
            pass

    # -- object directory ----------------------------------------------

    def _obj_locked(self, oid: bytes) -> _GlobalObject:
        o = self.objects.get(oid)
        if o is None:
            o = _GlobalObject()
            self.objects[oid] = o
        return o

    def rpc_obj_ready(self, ctx, oid: bytes, inline: Optional[bytes],
                      node_id: Optional[bytes], size: int = 0):
        with self.lock:
            o = self._obj_locked(oid)
            if o.status == ERROR:
                return False
            o.status = READY
            o.inline = inline
            o.size = size
            o.t_terminal = time.monotonic()
            if node_id is not None and inline is None:
                o.locations.add(node_id)
            # every ref was already dropped while the task ran
            # (fire-and-forget): mark for freeing on the terminal
            # transition — unpin alone never re-checks a then-PENDING entry
            self._mark_free_candidate_locked(oid, o)
            self._maybe_evict_locked()
        # the broadcast is a NOTIFICATION, not a payload channel: inline
        # bytes stay on the server (interested adapters fetch via
        # obj_state), so completion traffic stays O(nodes), not
        # O(nodes x payload)
        self._publish("objects", {"oid": oid, "status": READY})
        return True

    def rpc_obj_error(self, ctx, oid: bytes, err: bytes):
        with self.lock:
            o = self._obj_locked(oid)
            o.status = ERROR
            o.error = err
            o.t_terminal = time.monotonic()
            self._mark_free_candidate_locked(oid, o)
            self._maybe_evict_locked()
        self._publish("objects", {"oid": oid, "status": ERROR})
        return True

    def _maybe_evict_locked(self):
        """Bound the directory: evict old TERMINAL entries past the cap —
        but NEVER one some node still references (pins) and never one that
        turned terminal within the age floor (a consumer may be between
        its subscribe and its pin; reference reference_count.h role)."""
        if len(self.objects) <= self.max_objects:
            return
        now = time.monotonic()
        drop = []
        for oid, o in self.objects.items():  # insertion order = oldest first
            if (o.status in (READY, ERROR) and not o.pins
                    and now - o.t_terminal >= self.evict_min_age_s):
                drop.append(oid)
                if len(self.objects) - len(drop) <= self.max_objects * 0.9:
                    break
        now2 = time.monotonic()
        for oid in drop:
            del self.objects[oid]
            # same tombstone as the free sweep: a late pin on an evicted
            # entry must surface ObjectLostError, not resurrect a silent
            # empty PENDING that hangs the pinner's get()
            self._record_tombstone_locked(oid, now2)

    def _record_tombstone_locked(self, oid: bytes, now: float) -> None:
        """Record a swept/evicted/freed oid (bounded map shared by all
        three removal paths); caller holds the lock."""
        self._freed_tombstones[oid] = now
        while len(self._freed_tombstones) > 20000:
            self._freed_tombstones.pop(next(iter(self._freed_tombstones)))

    def rpc_obj_pin(self, ctx, oid: bytes, node_id: bytes):
        lost = False
        with self.lock:
            if oid not in self.objects and oid in self._freed_tombstones:
                # late pin on a SWEPT object (advisor r3): silently
                # resurrecting an empty PENDING entry would hang the
                # pinner's get() forever. Recreate it terminal-with-error
                # so waiters surface ObjectLostError (or kick lineage
                # reconstruction) instead.
                import cloudpickle

                from ray_tpu.core.exceptions import ObjectLostError

                o = self._obj_locked(oid)
                o.status = ERROR
                o.error = cloudpickle.dumps(ObjectLostError(
                    f"object {oid.hex()[:16]} was freed (refcount reached "
                    f"zero) before this reference arrived"))
                o.t_terminal = time.monotonic()
                o.pins.add(node_id)
                o.was_pinned = True
                lost = True
            else:
                o = self._obj_locked(oid)
                o.pins.add(node_id)
                o.was_pinned = True
                self._free_candidates.pop(oid, None)
        if lost:
            # the ERROR publish is the pinner's signal (obj_pin arrives as
            # a fire-and-forget cast; a return value would go unseen)
            self._publish("objects", {"oid": oid, "status": ERROR})
        return True

    def rpc_obj_unpin(self, ctx, oid: bytes, node_id: bytes):
        with self.lock:
            o = self.objects.get(oid)
            if o is None:
                return False
            o.pins.discard(node_id)
            self._mark_free_candidate_locked(oid, o)
        return True

    def _mark_free_candidate_locked(self, oid: bytes, o: _GlobalObject):
        """Refcount hit zero on a terminal, previously-referenced object:
        queue it for freeing after the grace (see free_grace_s — an
        in-flight pin on another connection may still land)."""
        if o.pins or not o.was_pinned or o.status not in (READY, ERROR):
            return
        self._free_candidates.setdefault(oid, time.monotonic())

    def _sweep_free_candidates(self):
        """Free candidates whose grace elapsed with no pin arriving: drop
        the directory entry and tell holder nodes to free their segments
        (the reference's owner-driven object free)."""
        now = time.monotonic()
        freed = []
        with self.lock:
            for oid, t in list(self._free_candidates.items()):
                if now - t < self.free_grace_s:
                    continue
                del self._free_candidates[oid]
                o = self.objects.get(oid)
                if (o is None or o.pins or not o.was_pinned
                        or o.status not in (READY, ERROR)):
                    continue
                freed.append((oid, list(o.locations)))
                del self.objects[oid]
                # bounded tombstone: lets a LATE pin distinguish "swept"
                # from "not yet created" (advisor r3)
                self._record_tombstone_locked(oid, now)
        for oid, locations in freed:
            self._publish("objects", {"oid": oid, "freed": True,
                                      "locations": locations})

    def rpc_task_events(self, ctx, node_id: bytes, events, start_seq=None):
        """Batched task events from a node runtime (reference
        TaskEventBuffer -> GcsTaskManager pipeline,
        ``core_worker/task_event_buffer.h:206`` role): bounded store
        feeding the cluster-wide state API and timeline.

        ``start_seq`` is the sender's local index of events[0]. A node
        that re-registers after a heartbeat blip rewinds its cursor to 0
        and reships history into a GCS that often still holds the earlier
        copies (advisor r3): events with seq below this store's per-node
        high-water mark are dropped as duplicates. Senders that predate
        the field (start_seq None) keep the old append-all behavior."""
        with self.lock:
            nid = node_id.hex()[:8]
            if start_seq is not None:
                seen = self._task_ev_seq.get(node_id, 0)
                skip = max(0, seen - start_seq)
                if skip >= len(events):
                    return True
                events = events[skip:]
                start_seq += skip
                self._task_ev_seq[node_id] = start_seq + len(events)
            for ev in events:
                ev = dict(ev)
                ev["node"] = nid
                self.task_events.append(ev)
        return True

    def rpc_task_events_get(self, ctx, limit: int = 10000):
        limit = int(limit)
        if limit <= 0:
            return []
        with self.lock:
            evs = list(self.task_events)
        return evs[-limit:]

    def rpc_trace_events(self, ctx, node_id: bytes, events, start_seq=None):
        """Batched spans from a node's TraceStore (trace-plane twin of
        rpc_task_events — same cursor semantics: ``start_seq`` is the
        sender's absolute index of events[0], re-registration rewinds are
        deduped against the per-node high-water mark)."""
        with self.lock:
            if start_seq is not None:
                seen = self._trace_ev_seq.get(node_id, 0)
                skip = max(0, seen - start_seq)
                if skip >= len(events):
                    return True
                events = events[skip:]
                start_seq += skip
                self._trace_ev_seq[node_id] = start_seq + len(events)
            self.trace_events.extend(events)
        return True

    def rpc_trace_events_get(self, ctx, limit: int = 10000):
        limit = int(limit)
        if limit <= 0:
            return []
        with self.lock:
            evs = list(self.trace_events)
        return evs[-limit:]

    def rpc_profile_events(self, ctx, node_id: bytes, events,
                           start_seq=None):
        """Batched profile batches from a node's ProfileStore
        (profiling-plane twin of rpc_trace_events — same acked-cursor/
        dedup contract against the per-node high-water mark)."""
        rx = time.time()
        with self.lock:
            if start_seq is not None:
                seen = self._profile_ev_seq.get(node_id, 0)
                skip = max(0, seen - start_seq)
                if skip >= len(events):
                    return True
                events = events[skip:]
                start_seq += skip
                self._profile_ev_seq[node_id] = start_seq + len(events)
            for ev in events:
                # re-stamp arrival with THIS clock: the sender's _rx is
                # its own (possibly skewed) wall clock, and the head's
                # window filter needs a receiver-side reference
                ev["_rx"] = rx
            self.profile_events.extend(events)
        return True

    def rpc_profile_events_get(self, ctx, limit: int = 2048):
        limit = int(limit)
        if limit <= 0:
            return []
        with self.lock:
            evs = list(self.profile_events)
        return evs[-limit:]

    # -- live cluster-wide stack dumps (`ray_tpu stack` py-spy role) ----

    def rpc_lifecycle_events(self, ctx, node_id: bytes, events,
                             start_seq=None):
        """Batched lifecycle events from a node's EventStore (event-plane
        twin of rpc_trace_events — same acked-cursor/dedup contract
        against the per-node high-water mark)."""
        with self.lock:
            if start_seq is not None:
                seen = self._lifecycle_ev_seq.get(node_id, 0)
                skip = max(0, seen - start_seq)
                if skip >= len(events):
                    return True
                events = events[skip:]
                start_seq += skip
                self._lifecycle_ev_seq[node_id] = start_seq + len(events)
            self.lifecycle_events.extend(events)
        return True

    def rpc_lifecycle_events_get(self, ctx, limit: int = 10000):
        limit = int(limit)
        if limit <= 0:
            return []
        with self.lock:
            evs = list(self.lifecycle_events)
        return evs[-limit:]

    def _append_lifecycle(self, rec) -> None:
        """Append a GCS-origin event record: node register/death are
        observed HERE (no daemon survives to report its own death), so
        the record skips the ring/heartbeat hop and lands in the head
        store directly with component=gcs provenance. ``rec`` is an
        ``events.record(...)`` result (None when the plane is killed)."""
        if rec is None:
            return
        with self.lock:
            self.lifecycle_events.append(rec)

    def rpc_stack_request(self, ctx):
        """Start a cluster-wide stack dump: publish the request on the
        ``profiling`` channel (every node's adapter collects its process
        + workers and calls stack_reply) and return the request id the
        caller later passes to stack_collect."""
        with self.lock:
            self._stack_req_seq += 1
            req_id = self._stack_req_seq
            self._stack_replies[req_id] = {}
            # bound: keep only the most recent requests
            while len(self._stack_replies) > 8:
                self._stack_replies.pop(min(self._stack_replies))
        self._publish("profiling", {"op": "stackdump", "req": req_id})
        return req_id

    def rpc_stack_reply(self, ctx, req_id: int, node_id: bytes, stacks):
        with self.lock:
            bucket = self._stack_replies.get(req_id)
            if bucket is not None:
                bucket[node_id.hex()[:8]] = stacks
        return True

    def rpc_stack_collect(self, ctx, req_id: int):
        """{node_id: {proc_label: {thread: collapsed_stack}}} gathered so
        far for a stack_request id (callers poll until enough nodes
        answered or their own deadline passes)."""
        with self.lock:
            return dict(self._stack_replies.get(req_id) or {})

    # -- cluster-wide log federation (`rtpu logs` rendezvous) -----------

    def rpc_log_request(self, ctx, target: dict,
                        tail_bytes: Optional[int] = None):
        """Start a cluster-wide log fetch: publish the resolution target
        on the ``events`` channel (every node's adapter resolves it
        against its own workers/session logs and calls log_reply only
        when it has rows) and return the request id the caller later
        passes to log_collect."""
        with self.lock:
            self._log_req_seq += 1
            req_id = self._log_req_seq
            self._log_replies[req_id] = {}
            # bound: keep only the most recent requests
            while len(self._log_replies) > 8:
                self._log_replies.pop(min(self._log_replies))
        self._publish("events", {"op": "logfetch", "req": req_id,
                                 "target": dict(target or {}),
                                 "tail_bytes": tail_bytes})
        return req_id

    def rpc_log_reply(self, ctx, req_id: int, node_id: bytes, rows):
        with self.lock:
            bucket = self._log_replies.get(req_id)
            if bucket is not None:
                bucket[node_id.hex()[:8]] = rows
        return True

    def rpc_log_collect(self, ctx, req_id: int):
        """{node_id: [log rows]} gathered so far for a log_request id
        (callers poll until a reply lands or their deadline passes —
        unlike stackdumps, only nodes that RESOLVED the target reply)."""
        with self.lock:
            return dict(self._log_replies.get(req_id) or {})

    def rpc_metrics_get(self, ctx, exclude_node: Optional[bytes] = None):
        """Flattened [(origin_labels, records)] across nodes for the head
        /metrics exposition. ``exclude_node``: the caller's own node id —
        its samples are already rendered locally (its registry and its
        workers' federation store live in-process). The GCS process's OWN
        registry (rpc counts/latency, pubsub fanout, table sizes, lock
        waits) rides along under component=gcs — the server has no other
        path to a scrape."""
        out = []
        with self.lock:
            for nid, payload in self._node_metrics.items():
                if nid == exclude_node:
                    continue
                out.extend(payload)
        try:
            from ray_tpu.util import metrics as _metrics

            recs = _metrics.registry_records()
            if any(r["samples"] for r in recs):
                out.append(({"component": "gcs"}, recs))
        except Exception:
            pass
        return out

    def rpc_device_report(self, ctx, node_id: bytes, entries) -> bool:
        """Replace a node's device-plane process entries (compiled-
        program registries + HBM census) — the metrics-payload pattern,
        not the acked-cursor one: registry rows are mutable state, so
        the latest snapshot is the whole truth for that node."""
        with self.lock:
            self._node_devices[node_id] = list(entries or ())
        return True

    def rpc_device_report_get(self, ctx,
                              exclude_node: Optional[bytes] = None):
        """Flattened process entries across nodes for the head's
        state.device_report(). ``exclude_node``: the caller's own node —
        its entries live in-process (local registry + DeviceStore)."""
        out = []
        with self.lock:
            for nid, entries in self._node_devices.items():
                if nid == exclude_node:
                    continue
                out.extend(entries)
        return out

    def rpc_obj_info(self, ctx, oids):
        """Batch (size, locations) for READY segment objects — the
        scheduler's dependency-locality signal (reference scorer.h role).
        Pending/inline/error entries are omitted: they carry no locality."""
        out = {}
        with self.lock:
            for oid in oids:
                o = self.objects.get(oid)
                if (o is not None and o.status == READY
                        and o.inline is None and o.locations):
                    out[oid] = (o.size, list(o.locations))
        return out

    def rpc_obj_state(self, ctx, oid: bytes):
        with self.lock:
            o = self.objects.get(oid)
            if o is None:
                return None
            return {"status": o.status, "inline": o.inline, "error": o.error,
                    "size": o.size, "locations": list(o.locations)}

    def rpc_obj_list(self, ctx, limit: int = 10000):
        """Object-directory dump for ``ray_tpu memory`` (reference
        ``ray memory`` refcount-dump role, ``scripts.py:1941``): per-object
        status, size, pin count (distributed refcount holders), and
        location count."""
        out = []
        with self.lock:
            for oid, o in list(self.objects.items())[:limit]:
                out.append({
                    "object_id": oid.hex(),
                    "status": o.status,
                    "size": o.size,
                    "inline": o.inline is not None,
                    "pins": len(o.pins),
                    "locations": len(o.locations),
                })
        return out

    def rpc_obj_drop(self, ctx, oid: bytes):
        """Explicit owner-driven free (``ray_tpu.free``): unlike the
        refcount sweep there is no grace — the caller asserts the object
        is fully consumed. Holder nodes must free their segments (and
        spill files) too, or every free()d exchange intermediate leaks on
        the node that produced it."""
        with self.lock:
            o = self.objects.pop(oid, None)
            locations = list(o.locations) if o is not None else []
            self._free_candidates.pop(oid, None)
            self._record_tombstone_locked(oid, time.monotonic())
        if o is not None:
            self._publish("objects", {"oid": oid, "freed": True,
                                      "locations": locations})
        return True

    def rpc_obj_forget_location(self, ctx, oid: bytes, node_id: bytes):
        """A pull found the segment missing (evicted/deleted behind the
        directory's back): drop the stale location so re-execution can run."""
        with self.lock:
            o = self.objects.get(oid)
            if o is None:
                return False
            o.locations.discard(node_id)
            if not o.locations and o.inline is None and o.status == READY:
                o.status = PENDING
        return True

    # -- KV / functions -------------------------------------------------

    def rpc_kv_put(self, ctx, key: str, value: bytes, namespace: str,
                   overwrite: bool):
        with self.lock:
            ns = self.kv.setdefault(namespace, {})
            if not overwrite and key in ns:
                return False
            ns[key] = value
            self._dirty = True
            return True

    def rpc_kv_get(self, ctx, key: str, namespace: str):
        with self.lock:
            return self.kv.get(namespace, {}).get(key)

    def rpc_kv_del(self, ctx, key: str, namespace: str):
        with self.lock:
            self._dirty = True
            return self.kv.get(namespace, {}).pop(key, None) is not None

    def rpc_kv_keys(self, ctx, prefix: str, namespace: str):
        with self.lock:
            return [k for k in self.kv.get(namespace, {})
                    if k.startswith(prefix)]

    def rpc_fn_put(self, ctx, h: str, blob: bytes):
        with self.lock:
            self.functions.setdefault(h, blob)
            self._dirty = True
        return True

    def rpc_fn_get(self, ctx, h: str):
        with self.lock:
            return self.functions.get(h)

    # -- actors ---------------------------------------------------------

    def rpc_actor_register(self, ctx, actor_id: bytes, node_id: bytes,
                           name: str):
        with self.lock:
            if name and name in self.named_actors:
                existing = self.actors.get(self.named_actors[name])
                if existing is not None and existing.get("state") != "DEAD":
                    raise ValueError(f"actor name {name!r} already taken")
            self.actors[actor_id] = {"node_id": node_id, "name": name,
                                     "state": "PENDING"}
            if name:
                self.named_actors[name] = actor_id
            self._dirty = True
        return True

    def rpc_actor_update(self, ctx, actor_id: bytes, state: str,
                         node_id: Optional[bytes] = None):
        with self.lock:
            rec = self.actors.get(actor_id)
            if rec is None:
                return False
            rec["state"] = state
            if node_id is not None:
                rec["node_id"] = node_id
            if state == "DEAD" and rec.get("name"):
                if self.named_actors.get(rec["name"]) == actor_id:
                    self.named_actors.pop(rec["name"], None)
            self._dirty = True
        return True

    def rpc_actor_get(self, ctx, actor_id: bytes):
        with self.lock:
            rec = self.actors.get(actor_id)
            return dict(rec) if rec else None

    def rpc_actor_lookup(self, ctx, name: str):
        with self.lock:
            return self.named_actors.get(name)

    def rpc_actor_list(self, ctx):
        with self.lock:
            return {aid: dict(rec) for aid, rec in self.actors.items()}

    # -- placement groups ------------------------------------------------

    def rpc_pg_register(self, ctx, pg_id: bytes, bundles, strategy: str,
                        assignments, creator: bytes):
        with self.lock:
            self.pgs[pg_id] = {"bundles": [dict(b) for b in bundles],
                               "strategy": strategy,
                               "assignments": list(assignments),
                               "creator": creator}
            self._dirty = True
        self._publish("pgs", {"event": "update", "pg_id": pg_id,
                              "assignments": list(assignments)})
        return True

    def rpc_pg_get(self, ctx, pg_id: bytes):
        with self.lock:
            rec = self.pgs.get(pg_id)
            return dict(rec) if rec else None

    def rpc_pg_update_assignment(self, ctx, pg_id: bytes, updates):
        """``updates``: {bundle_idx: node_id} after a reschedule."""
        with self.lock:
            rec = self.pgs.get(pg_id)
            if rec is None:
                return False
            for i, nid in updates.items():
                rec["assignments"][int(i)] = nid
            assignments = list(rec["assignments"])
            self._dirty = True
        self._publish("pgs", {"event": "update", "pg_id": pg_id,
                              "assignments": assignments})
        return True

    def rpc_pg_remove(self, ctx, pg_id: bytes):
        with self.lock:
            rec = self.pgs.pop(pg_id, None)
            self._dirty = True
        if rec is not None:
            self._publish("pgs", {"event": "removed", "pg_id": pg_id})
        return True

    def rpc_pg_list(self, ctx):
        with self.lock:
            return {p: dict(r) for p, r in self.pgs.items()}

    # -- pubsub ---------------------------------------------------------

    def rpc_subscribe(self, ctx, channel: str):
        ctx.subscriptions.add(channel)
        return True

    def rpc_publish(self, ctx, channel: str, payload):
        self._publish(channel, payload)
        return True

    def _publish(self, channel: str, payload):
        if self.server is not None:
            n = self.server.broadcast(channel, payload)
            if n:
                keys = self._channel_keys
                key = keys.get(channel) or keys.setdefault(
                    channel, (("channel", channel),))
                self._m_pubsub._inc_key(key, n)

    def rpc_ping(self, ctx):
        return "pong"

    # -- chaos plane ----------------------------------------------------

    def rpc_fp_arm(self, ctx, spec: str):
        """Arm failpoints in the GCS SERVER process itself (sites like
        rpc.server.dispatch live here); cluster-wide distribution rides
        the ``failpoints`` pubsub channel + KV, not this call."""
        from ray_tpu.util import failpoints

        failpoints.apply_spec(spec)
        return True

    def rpc_fp_disarm(self, ctx):
        from ray_tpu.util import failpoints

        failpoints.clear()
        return True

    # ------------------------------------------------------------------

    def serve(self, host: str, port: int, authkey: bytes) -> RpcServer:
        self.server = RpcServer(host, port, authkey, self.handle)
        threading.Thread(target=self._health_loop, daemon=True,
                         name="gcs-health").start()
        return self.server

    def stop(self):
        self._stop.set()
        if self.server is not None:
            self.server.close()


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, required=True)
    p.add_argument("--authkey", required=True)
    p.add_argument("--node-timeout", type=float,
                   default=DEFAULT_NODE_TIMEOUT_S)
    p.add_argument("--snapshot", default=None,
                   help="snapshot file for durable-table fault tolerance")
    args = p.parse_args(argv)

    # `kill -USR1 <gcs pid>` dumps every thread's stack to stderr (the
    # default action of the signal would kill the cluster's one GCS)
    faulthandler.register(signal.SIGUSR1, all_threads=True)
    svc = GcsService(node_timeout_s=args.node_timeout,
                     snapshot_path=args.snapshot)
    svc.serve(args.host, args.port, args.authkey.encode())
    print(f"gcs listening on {args.host}:{args.port}", flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        svc.stop()


if __name__ == "__main__":
    main()
