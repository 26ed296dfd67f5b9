"""Every connection is made or refused in bounded time (core/connection.py).

The cluster fixture used to hang for ever in the authkey challenge: the
stdlib handshake has no deadline, the server ran it on its one accept
thread, and ``RpcClient.close()`` left its reader thread alive on a closed
descriptor whose number the next socket was given.
"""

import socket
import threading
import time

import pytest

from ray_tpu.cluster.rpc import RpcClient, RpcServer, parse_addr
from ray_tpu.core import connection


@pytest.fixture
def server():
    s = RpcServer("127.0.0.1", 0, b"k", lambda m, a, c: ("ok", m, a))
    yield s
    s.close()


@pytest.fixture
def silent_listener():
    """Accepts (the kernel completes the connect) and never speaks."""
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    s.listen(8)
    yield "127.0.0.1:%d" % s.getsockname()[1]
    s.close()


def test_client_raises_when_server_never_speaks(silent_listener,
                                                monkeypatch):
    monkeypatch.setattr(connection, "HANDSHAKE_TIMEOUT_S", 0.5)
    t0 = time.monotonic()
    with pytest.raises(ConnectionError, match="handshake deadline"):
        RpcClient(silent_listener, b"k")
    assert time.monotonic() - t0 < 3.0


def test_worker_dial_raises_when_driver_never_speaks(tmp_path, monkeypatch):
    monkeypatch.setattr(connection, "HANDSHAKE_TIMEOUT_S", 0.5)
    path = str(tmp_path / "driver.sock")
    with socket.socket(socket.AF_UNIX) as s:
        s.bind(path)
        s.listen(8)
        t0 = time.monotonic()
        with pytest.raises(connection.HandshakeTimeout):
            connection.connect(path, "AF_UNIX", b"k")
        assert time.monotonic() - t0 < 3.0


@pytest.mark.parametrize("bad_client", ["leaves", "wrong_key", "stalls"])
def test_server_answers_next_client_after_a_bad_one(server, bad_client):
    """A client that leaves in mid-handshake used to end the accept loop
    (EOFError: return), one with a wrong key killed its thread
    (AuthenticationError), one that stalls held every other client out."""
    hostport = parse_addr(server.addr)
    raw = socket.create_connection(hostport)
    if bad_client == "leaves":
        raw.close()
    elif bad_client == "wrong_key":
        with pytest.raises(Exception):
            connection.connect(hostport, "AF_INET", b"not-the-key")
    try:
        cli = RpcClient(server.addr, b"k")
        assert cli.call("ping", 1, timeout=10) == ("ok", "ping", (1,))
        cli.close()
    finally:
        raw.close()


def test_runtime_listener_survives_bad_dial_back(rt):
    """The driver's worker listener is the same code: after a client that
    leaves and one that never says hello, a NEW worker still dials back."""
    from ray_tpu.core.runtime import _get_runtime

    addr = _get_runtime()._sock_addr
    with socket.socket(socket.AF_UNIX) as s:
        s.connect(addr)
    silent = connection.connect(addr, "AF_UNIX",
                                _get_runtime().session.encode())

    @rt.remote
    class A:
        def hi(self):
            return "hi"

    actors = [A.remote() for _ in range(6)]  # more than the warm pool
    assert rt.get([a.hi.remote() for a in actors], timeout=60) == ["hi"] * 6
    silent.close()


def test_close_ends_every_thread_and_frees_the_fd(server):
    """``close()`` used to leave the reader blocked in ``read`` on the
    closed fd (Linux does not wake it): one leaked thread per client, and
    whichever socket was given the number next had its bytes eaten."""
    before = set(threading.enumerate())
    for _ in range(5):
        cli = RpcClient(server.addr, b"k")
        assert cli.call("ping", timeout=10)[0] == "ok"
        cli.close()
    leaked = [t for t in set(threading.enumerate()) - before
              if t.name == "rpc-client-reader"]
    assert not leaked
    # the server released its end of each (no CLOSE-WAIT pile-up)
    deadline = time.monotonic() + 5
    while server._conns and time.monotonic() < deadline:
        time.sleep(0.02)
    assert not server._conns

    server.close()  # joins the accept thread; a bare fd close never woke it
    assert not server._listener._thread.is_alive()
