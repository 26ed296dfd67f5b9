"""Message RPC over ``multiprocessing.connection`` (TCP + authkey).

Role analog: the reference's gRPC plumbing (``src/ray/rpc/grpc_server.h``,
``client_call.h``) — reduced to what the cluster needs: request/reply with
out-of-order completion, one-way casts, and server->client pushes
(pubsub-lite). Wire messages are pickled tuples:

    ("req",  id, method, args)      client -> server, expects a reply
    ("rep",  id, ok, payload)       server -> client
    ("cast", method, args)          client -> server, no reply
    ("push", channel, payload)      server -> client (subscriptions)

Each server connection gets a reader thread; request handlers run on a
shared thread pool so a blocking handler (e.g. a directory wait) never
stalls the connection. TCP (AF_INET) so the same code carries multi-host;
tests run everything on localhost.

Wire versioning (reference role: the protobuf schema in
``src/ray/protobuf/`` gives every message a versioned contract): the
client's FIRST message is ``("hello", (major, minor))``; the server
replies ``("hello_ack", (major, minor))``. A major mismatch refuses the
connection with :class:`WireVersionError` — a clear error at connect
time instead of an unpickling crash mid-conversation when heterogeneous
node versions meet. Minor bumps are additive (new methods/fields) and
interoperate.
"""

from __future__ import annotations

import itertools
import pickle
import socket
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from multiprocessing.reduction import ForkingPickler
from time import perf_counter
from typing import Any, Callable, Dict, Optional, Tuple

from ray_tpu.core import connection

WIRE_VERSION: Tuple[int, int] = (1, 0)

#: transport instrumentation (defs in util/metric_defs.py): framed bytes
#: both directions, server queue-wait (socket read -> handler start, the
#: GCS accept-loop contention signal), client reconnects/timeouts.
#: metric_defs.get is a cached fast path that survives clear_registry,
#: so the accessor just rebuilds; tag keys stay pre-sorted.
_REQ_KEY = (("kind", "req"),)
_CAST_KEY = (("kind", "cast"),)


def _rpc_metrics():
    from ray_tpu.util import metric_defs as md

    return {"sent": md.get("rtpu_rpc_sent_bytes_total"),
            "recv": md.get("rtpu_rpc_recv_bytes_total"),
            "requests": md.get("rtpu_rpc_server_requests_total"),
            "queue_wait": md.get("rtpu_rpc_server_queue_wait_seconds"),
            "reconnects": md.get("rtpu_rpc_client_reconnects_total"),
            "reconnect_attempts": md.get(
                "rtpu_rpc_client_reconnect_attempts_total"),
            "timeouts": md.get("rtpu_rpc_client_timeouts_total")}


def _send_framed(conn, send_lock, msg) -> None:
    """Pickle-then-send_bytes (what ``conn.send`` does internally — same
    reducer, no extra copy) so the framed size feeds the byte counters."""
    buf = ForkingPickler.dumps(msg)
    with send_lock:
        conn.send_bytes(buf)
    try:
        _rpc_metrics()["sent"]._inc_key((), len(buf))
    except Exception:
        pass


def _recv_framed(conn):
    buf = conn.recv_bytes()
    try:
        _rpc_metrics()["recv"]._inc_key((), len(buf))
    except Exception:
        pass
    return pickle.loads(buf)


class WireVersionError(ConnectionError):
    """Peer speaks an incompatible wire major version (terminal)."""


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def parse_addr(addr: str) -> Tuple[str, int]:
    host, port = addr.rsplit(":", 1)
    return host, int(port)


class RpcServer:
    """Serves ``handler(method, args, ctx) -> payload`` over TCP.

    ``ctx`` is the per-connection :class:`ServerConn`, so handlers can
    subscribe the caller to push channels or identify it across calls.
    """

    def __init__(self, host: str, port: int, authkey: bytes,
                 handler: Callable[[str, tuple, "ServerConn"], Any],
                 max_workers: int = 16):
        self._handler = handler
        self._pool = ThreadPoolExecutor(max_workers=max_workers,
                                        thread_name_prefix="rpc")
        self._conns: Dict[int, "ServerConn"] = {}
        self._lock = threading.Lock()
        self._conn_ids = itertools.count()
        self._listener = connection.Listener(
            (host, port), "AF_INET", authkey, self._serve_conn,
            name="rpc-accept")
        self.addr = f"{host}:{self._listener.address[1]}"

    def _serve_conn(self, raw, deadline: float):
        """One authenticated connection, on its own thread."""
        with self._lock:
            conn = ServerConn(next(self._conn_ids), raw, self)
            self._conns[conn.conn_id] = conn
        conn.reader_loop(deadline)

    def _drop_conn(self, conn: "ServerConn"):
        with self._lock:
            self._conns.pop(conn.conn_id, None)

    def broadcast(self, channel: str, payload: Any,
                  only_subscribed: bool = True) -> int:
        """Push to subscribers; returns the delivery count (fanout)."""
        with self._lock:
            conns = list(self._conns.values())
        n = 0
        for c in conns:
            if only_subscribed and channel not in c.subscriptions:
                continue
            c.push(channel, payload)
            n += 1
        return n

    def close(self):
        self._listener.close()
        with self._lock:
            conns = list(self._conns.values())
        for c in conns:
            c.close()
        self._pool.shutdown(wait=False)


class ServerConn:
    def __init__(self, conn_id: int, raw, server: RpcServer):
        self.conn_id = conn_id
        self.raw = raw
        self.server = server
        self.send_lock = threading.Lock()
        self._fd_lock = threading.Lock()  # close() vs the reader's release
        self.subscriptions: set = set()
        self.meta: Dict[str, Any] = {}  # handler scratch (e.g. node_id)
        self.on_close: Optional[Callable[["ServerConn"], None]] = None

    def reader_loop(self, hello_deadline: float):
        try:
            self._serve(hello_deadline)
        finally:
            self.server._drop_conn(self)
            # this thread is the only reader, so the fd is released here
            # and nowhere else; no sender or close() is in mid-call on it
            with self.send_lock, self._fd_lock:
                self.raw.close()
        cb = self.on_close
        if cb is not None:
            try:
                cb(self)
            except Exception:
                pass

    def _serve(self, hello_deadline: float):
        # handshake: first message must be a compatible hello
        try:
            connection.wait_readable(self.raw, hello_deadline, "client")
            first = _recv_framed(self.raw)
        except (EOFError, OSError, TypeError, ValueError):
            first = None
        try:
            ok_shape = (isinstance(first, tuple) and len(first) >= 2
                        and first[0] == "hello")
            peer_version = tuple(first[1]) if ok_shape else ()
            ok_shape = ok_shape and len(peer_version) >= 1 and all(
                isinstance(v, int) for v in peer_version)
        except TypeError:
            ok_shape, peer_version = False, ()
        if not ok_shape:
            self._send(("hello_nack", WIRE_VERSION,
                        "expected hello as first message"))
            return
        if peer_version[0] != WIRE_VERSION[0]:
            self._send(("hello_nack", WIRE_VERSION,
                        f"wire major {peer_version[0]} != {WIRE_VERSION[0]}"))
            return
        self.meta["wire_version"] = peer_version
        self._send(("hello_ack", WIRE_VERSION))
        m = _rpc_metrics()
        while True:
            try:
                msg = _recv_framed(self.raw)
            except (EOFError, OSError, TypeError, ValueError):
                break
            kind = msg[0]
            if kind == "req":
                _, req_id, method, args = msg
                m["requests"]._inc_key(_REQ_KEY)
                self.server._pool.submit(self._run, req_id, method, args,
                                         perf_counter())
            elif kind == "cast":
                _, method, args = msg
                m["requests"]._inc_key(_CAST_KEY)
                self.server._pool.submit(self._run, None, method, args,
                                         perf_counter())

    def _run(self, req_id: Optional[int], method: str, args: tuple,
             enq_ts: Optional[float] = None):
        if enq_ts is not None:
            # thread-pool queue wait: socket read -> handler start. Tail
            # growth here means the server's 16 handler threads (or 2
            # host vCPUs) are saturated — the "is the GCS the
            # bottleneck?" signal.
            try:
                _rpc_metrics()["queue_wait"]._observe_key(
                    (), perf_counter() - enq_ts)
            except Exception:
                pass
        try:
            from ray_tpu.util import failpoints

            if failpoints.hit("rpc.server.dispatch", method):
                return  # chaos: swallow the request; the caller times out
            payload = self.server._handler(method, args, self)
            ok = True
        except BaseException as e:  # noqa: BLE001 — shipped to caller
            payload, ok = e, False
        if req_id is not None:
            self._send(("rep", req_id, ok, payload))

    def push(self, channel: str, payload: Any):
        self._send(("push", channel, payload))

    def _send(self, msg):
        try:
            _send_framed(self.raw, self.send_lock, msg)
        except (OSError, BrokenPipeError, ValueError):
            pass

    def close(self):
        """Wake the reader with EOF; it releases the fd."""
        with self._fd_lock:
            connection.shutdown(self.raw)


def _dial(hostport, authkey: bytes, addr: str):
    """Connect, authenticate and exchange hello/hello_ack, all inside
    ``connection.HANDSHAKE_TIMEOUT_S``. Raises ``OSError`` (refused,
    ``connection.HandshakeTimeout``, ...) for what a retry may heal and
    :class:`WireVersionError` for what it will not. Returns the
    connection and the server's wire version."""
    deadline = time.monotonic() + connection.HANDSHAKE_TIMEOUT_S
    conn = connection.connect(hostport, "AF_INET", authkey, deadline)
    try:
        return conn, _client_handshake(conn, addr, deadline)
    except BaseException:
        conn.close()
        raise


def _client_handshake(conn, addr: str, deadline: float):
    """Exchange hello/hello_ack; raise :class:`WireVersionError` when the
    server refuses (major mismatch) or doesn't speak the handshake."""
    conn.send(("hello", WIRE_VERSION))
    connection.wait_readable(conn, deadline, f"server at {addr}")
    reply = conn.recv()
    if (not isinstance(reply, tuple) or not reply
            or reply[0] != "hello_ack"):
        detail = (reply[2] if isinstance(reply, tuple) and len(reply) > 2
                  else reply)
        raise WireVersionError(
            f"server at {addr} refused wire version {WIRE_VERSION}: {detail}")
    return tuple(reply[1])


class RpcClient:
    """Client with one reader thread demuxing replies and pushes.

    ``reconnect=True`` keeps retrying the server after a drop (in-flight
    calls still fail — callers own retries) and fires ``on_reconnect`` so
    owners can re-subscribe/re-register; this is what lets node daemons
    survive a GCS restart (reference GCS fault tolerance role).
    """

    def __init__(self, addr: str, authkey: bytes,
                 on_push: Optional[Callable[[str, Any], None]] = None,
                 on_disconnect: Optional[Callable[[], None]] = None,
                 reconnect: bool = False,
                 on_reconnect: Optional[Callable[[], None]] = None):
        host, port = parse_addr(addr)
        self.addr = addr
        self._hostport = (host, port)
        self._authkey = authkey
        self._conn, self.server_wire_version = _dial(
            self._hostport, authkey, addr)
        self._send_lock = threading.Lock()
        self._fd_lock = threading.Lock()  # close() vs the reader's release
        self._pending: Dict[int, tuple] = {}  # id -> (event, box)
        self._pending_lock = threading.Lock()
        self._ids = itertools.count(1)
        self._on_push = on_push
        self._on_disconnect = on_disconnect
        self._reconnect = reconnect
        self._on_reconnect = on_reconnect
        self._closed = False
        self._reader = threading.Thread(target=self._reader_loop,
                                        daemon=True,
                                        name="rpc-client-reader")
        self._reader.start()

    def _reader_loop(self):
        try:
            self._read_and_reconnect()
        finally:
            # this thread is the only reader, so the fd is released here
            # and nowhere else; no sender or close() is in mid-call on it
            with self._send_lock, self._fd_lock:
                self._conn.close()
        if not self._closed and self._on_disconnect is not None:
            try:
                self._on_disconnect()
            except Exception:
                pass

    def _read_and_reconnect(self):
        while not self._closed:
            self._read_until_drop()
            with self._pending_lock:
                pending = list(self._pending.values())
                self._pending.clear()
            for ev, box in pending:
                box[:] = [False,
                          ConnectionError(f"rpc connection to {self.addr} lost")]
                ev.set()
            if self._closed or not self._reconnect:
                break
            if not self._try_reconnect():
                break
            if self._on_reconnect is not None:
                # NEVER run the callback on this thread: replies to any RPC
                # it issues are demuxed HERE, so a synchronous callback
                # would deadlock its own calls into timeouts
                def _cb():
                    try:
                        self._on_reconnect()
                    except Exception:
                        pass

                threading.Thread(target=_cb, daemon=True,
                                 name="rpc-reconnect-cb").start()

    def _read_until_drop(self):
        while True:
            try:
                msg = _recv_framed(self._conn)
            except (EOFError, OSError, TypeError, ValueError):
                # TypeError/ValueError: multiprocessing internals raise
                # these when the fd is closed from under a blocked recv
                return
            if msg[0] == "rep":
                _, req_id, ok, payload = msg
                with self._pending_lock:
                    ent = self._pending.pop(req_id, None)
                if ent is not None:
                    ent[1][:] = [ok, payload]
                    ent[0].set()
            elif msg[0] == "push" and self._on_push is not None:
                try:
                    self._on_push(msg[1], msg[2])
                except Exception:
                    pass

    def _try_reconnect(self, max_wait_s: float = 120.0) -> bool:
        deadline = time.monotonic() + max_wait_s
        delay = 0.2
        m = _rpc_metrics()
        while not self._closed and time.monotonic() < deadline:
            try:
                m["reconnect_attempts"]._inc_key(())
                try:
                    conn, _ = _dial(self._hostport, self._authkey,
                                    self.addr)
                except WireVersionError:
                    return False  # a major mismatch won't heal by retrying
                with self._send_lock, self._fd_lock:
                    # calls that raced the outage and sent into the dying
                    # socket would otherwise wait out their full timeout
                    # (or forever): fail them now so callers retry
                    with self._pending_lock:
                        stale = list(self._pending.values())
                        self._pending.clear()
                    for ev, box in stale:
                        box[:] = [False, ConnectionError(
                            f"rpc connection to {self.addr} was replaced")]
                        ev.set()
                    old, self._conn = self._conn, conn
                    old.close()  # don't leak one fd per outage
                m["reconnects"]._inc_key(())
                return True
            except Exception:
                time.sleep(delay)
                delay = min(delay * 1.6, 3.0)
        return False

    def call(self, method: str, *args, timeout: Optional[float] = None) -> Any:
        """Request/reply. ``timeout=None`` applies the default deadline
        (``RTPU_RPC_DEFAULT_TIMEOUT_S``): an un-deadlined call into a
        wedged peer would park this thread forever, and every such parked
        thread is a recovery hole (chaos ISSUE 5). Call sites that truly
        need a longer wait pass it explicitly; a non-positive configured
        default restores the unbounded wait."""
        if timeout is None:
            from ray_tpu import config as _cfg

            t = float(_cfg.get("rpc_default_timeout_s"))
            timeout = t if t > 0 else None
        req_id = next(self._ids)
        ev = threading.Event()
        box: list = []
        with self._pending_lock:
            self._pending[req_id] = (ev, box)
        self._send_counted(("req", req_id, method, args))
        if not ev.wait(timeout):
            with self._pending_lock:
                self._pending.pop(req_id, None)
            try:
                _rpc_metrics()["timeouts"]._inc_key(())
            except Exception:
                pass
            raise TimeoutError(f"rpc {method} timed out after {timeout}s")
        ok, payload = box
        if not ok:
            raise payload
        return payload

    def _send_counted(self, msg) -> None:
        from ray_tpu.util import failpoints

        if failpoints.hit("rpc.client.send",
                          msg[2] if msg[0] == "req" else msg[1]):
            return  # chaos: drop this request/cast on the floor
        # self._conn must be read INSIDE the send lock: the reconnect
        # path swaps it under the same lock
        buf = ForkingPickler.dumps(msg)
        with self._send_lock:
            self._conn.send_bytes(buf)
        try:
            _rpc_metrics()["sent"]._inc_key((), len(buf))
        except Exception:
            pass

    def cast(self, method: str, *args) -> None:
        try:
            self._send_counted(("cast", method, args))
        except (OSError, BrokenPipeError, ValueError):
            pass

    def close(self):
        """Wake the reader with EOF and wait for it: it releases the fd,
        so no thread of this client outlives ``close()`` holding a
        descriptor number the next socket may be given."""
        self._closed = True
        with self._fd_lock:
            connection.shutdown(self._conn)
        if threading.current_thread() is not self._reader:
            self._reader.join(timeout=2.0)
