"""Authenticated ``multiprocessing.connection`` endpoints with a deadline.

The stdlib's ``Client(...)`` and ``Listener.accept()`` run the authkey
challenge with blocking receives and no time limit: a peer that accepts
and never speaks parks the caller for ever, and a listener that
authenticates inside its one accept thread is held out by one slow client
and stopped for good by one that leaves in mid-handshake. Both ends here
speak the same challenge (the stdlib's ``deliver_challenge`` /
``answer_challenge``, so a plain ``Client``/``Listener`` still
interoperates) under one deadline, and the listener authenticates each
accepted socket on that connection's own thread.
"""

from __future__ import annotations

import logging
import socket
import threading
import time
from multiprocessing.connection import (AuthenticationError, Connection,
                                        answer_challenge, deliver_challenge)
from typing import Callable, Optional

logger = logging.getLogger(__name__)

#: connect + authkey challenge + the caller's first exchange, in seconds.
#: A healthy peer answers in milliseconds; a loaded 2-vCPU box in tenths.
HANDSHAKE_TIMEOUT_S = 5.0


class HandshakeTimeout(ConnectionError):
    """The peer did not finish the handshake inside the deadline —
    transient (a loaded box, a restart herd): callers that retry
    ``ConnectionError`` keep retrying."""


class _Deadlined:
    """The two methods the challenge functions call, every receive
    bounded by the one deadline."""

    def __init__(self, conn: Connection, deadline: float, peer):
        self._conn, self._deadline, self._peer = conn, deadline, peer

    def send_bytes(self, buf):
        self._conn.send_bytes(buf)

    def recv_bytes(self, maxlength=None):
        wait_readable(self._conn, self._deadline, self._peer)
        return self._conn.recv_bytes(maxlength)


def wait_readable(conn: Connection, deadline: float, peer) -> None:
    """Return once ``conn`` has something to read; raise
    :class:`HandshakeTimeout` at ``deadline`` (``time.monotonic()``)."""
    if not conn.poll(max(0.0, deadline - time.monotonic())):
        raise HandshakeTimeout(
            f"{peer} sent nothing within the {HANDSHAKE_TIMEOUT_S}s "
            f"handshake deadline")


def connect(address, family: str, authkey: bytes,
            deadline: Optional[float] = None) -> Connection:
    """``multiprocessing.connection.Client(address, family, authkey)``
    that is connected and authenticated by ``deadline`` or raises
    (``OSError``/:class:`HandshakeTimeout`/``AuthenticationError``)."""
    if deadline is None:
        deadline = time.monotonic() + HANDSHAKE_TIMEOUT_S
    with socket.socket(getattr(socket, family)) as sock:
        sock.settimeout(max(0.001, deadline - time.monotonic()))
        sock.connect(address)
        sock.settimeout(None)
        conn = Connection(sock.detach())
    try:
        peer = _Deadlined(conn, deadline, address)
        answer_challenge(peer, authkey)
        deliver_challenge(peer, authkey)
    except BaseException:
        conn.close()
        raise
    return conn


def shutdown(conn: Connection) -> None:
    """End ``conn`` for both directions WITHOUT releasing its fd: a
    thread blocked in ``recv`` on it wakes with EOF, and that thread then
    closes the fd. Closing the fd under a blocked reader does not wake
    it on Linux, and lets the next socket reuse the number while the old
    reader still holds it — the reader then eats the new connection's
    bytes. The owner serialises this call against the reader's final
    ``close()`` with a lock, so the fd cannot be reused in between."""
    try:
        s = socket.socket(fileno=conn.fileno())
    except (OSError, ValueError):
        return  # already closed
    try:
        s.shutdown(socket.SHUT_RDWR)
    except OSError:
        pass  # never connected, or the peer is gone already
    finally:
        s.detach()


class Listener:
    """A listening socket whose accept thread only accepts.

    Each accepted socket gets a thread that runs the authkey challenge
    under :data:`HANDSHAKE_TIMEOUT_S` and then ``serve(conn, deadline)``,
    which owns ``conn`` from there (``deadline`` bounds the protocol's
    own first message). A client that fails, stalls or leaves in
    mid-handshake costs its own thread and socket, nothing else.
    """

    def __init__(self, address, family: str, authkey: bytes,
                 serve: Callable[[Connection, float], None],
                 name: str = "accept"):
        self._authkey = authkey
        self._serve = serve
        self._name = name
        self._sock = socket.socket(getattr(socket, family))
        try:
            if family == "AF_INET":
                self._sock.setsockopt(socket.SOL_SOCKET,
                                      socket.SO_REUSEADDR, 1)
            self._sock.bind(address)
            # a burst of dial-backs must not race the accept thread: a
            # full unix backlog fails the connect with EAGAIN
            self._sock.listen(128)
        except OSError:
            self._sock.close()
            raise
        self.address = self._sock.getsockname()
        self._closed = False
        self._thread = threading.Thread(target=self._accept_loop,
                                        daemon=True, name=name)
        self._thread.start()

    def _accept_loop(self):
        n = 0
        while not self._closed:
            try:
                sock, _ = self._sock.accept()
            except OSError:
                if self._closed:
                    return
                time.sleep(0.05)  # EMFILE/ECONNABORTED: keep accepting
                continue
            n += 1
            threading.Thread(target=self._handshake, args=(sock,),
                             daemon=True, name=f"{self._name}-conn-{n}"
                             ).start()

    def _handshake(self, sock: socket.socket):
        deadline = time.monotonic() + HANDSHAKE_TIMEOUT_S
        conn = Connection(sock.detach())
        try:
            peer = _Deadlined(conn, deadline, "client")
            deliver_challenge(peer, self._authkey)
            answer_challenge(peer, self._authkey)
        except (OSError, EOFError, AuthenticationError) as e:
            logger.debug("%s: handshake failed: %r", self._name, e)
            conn.close()
            return
        try:
            self._serve(conn, deadline)
        except Exception:
            logger.exception("%s: connection handler failed", self._name)
            conn.close()

    def close(self):
        """Stop accepting and END the accept thread (a bare ``close()``
        of a listening fd leaves a thread blocked in ``accept`` there for
        the life of the process)."""
        self._closed = True
        try:
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        if threading.current_thread() is not self._thread:
            self._thread.join(timeout=2.0)
        self._sock.close()
