"""Native driver engine (r14): GIL-free control pipe + fallback contract.

Three layers:
- engine-level: the C++ pipe over a raw socketpair (framing, batch
  coalescing, packed refpin bookkeeping, EOF, buffer growth);
- runtime-level: a live driver with the engine on vs the kill switch
  (``RTPU_NATIVE_PIPE=0``);
- fallback-level: the pure-Python reader parsing the packed RTP1 frames
  workers ship, so a driver without the .so still interoperates.
"""

import os
import pickle
import socket
import struct
import threading
import time

import pytest

import ray_tpu
from ray_tpu import _native
from conftest import poll_until

pytestmark = pytest.mark.skipif(
    not _native.pipe_engine_available(),
    reason="native pipe engine unavailable (no .so on this box)")


# ---------------------------------------------------------------------------
# engine level
# ---------------------------------------------------------------------------


def _pipe_pair():
    from multiprocessing.connection import Connection

    a, b = socket.socketpair()
    drv = _native.NativePipe(a.fileno(), coalesce_us=0)
    peer = Connection(b.detach())
    return a, drv, peer


def test_single_and_batched_frames_roundtrip():
    a, drv, peer = _pipe_pair()
    try:
        msg = pickle.dumps(("exec", {"task": 1}))
        assert drv.send(msg)
        assert peer.recv_bytes() == msg

        # a burst: whatever coalesces ships as RTB1 batch frames the
        # worker-side unpack understands; order and content are exact
        msgs = [pickle.dumps(("reply", i, "ok", None)) for i in range(64)]
        for m in msgs:
            drv.send(m)
        received = []
        while len(received) < len(msgs):
            buf = peer.recv_bytes()
            if buf[:4] == b"RTB1":
                cnt = int.from_bytes(buf[4:8], "big")
                off = 8
                for _ in range(cnt):
                    ln = int.from_bytes(buf[off:off + 4], "big")
                    off += 4
                    received.append(buf[off:off + ln])
                    off += ln
            else:
                received.append(buf)
        assert received == msgs
        st = drv.stats()
        assert st["sent_msgs"] == len(msgs) + 1
        assert st["sent_frames"] >= 1
    finally:
        drv.close()
        a.close()


def test_drain_returns_assembled_messages_and_split_frames():
    a, drv, peer = _pipe_pair()
    try:
        peer.send_bytes(pickle.dumps(("cast", "put", (b"x" * 20, None, 5))))
        # a frame split across writes must reassemble
        payload = pickle.dumps(("cast", "split", b"y" * 10000))
        raw = struct.pack("!i", len(payload)) + payload
        fd = peer.fileno()
        os.write(fd, raw[:50])
        threading.Timer(0.2, lambda: os.write(fd, raw[50:])).start()
        recs = []
        deadline = time.time() + 10
        while len(recs) < 2 and time.time() < deadline:
            r = drv.drain(timeout=0.5)
            assert r is not None
            recs += r
        assert [t for t, _ in recs] == [0, 0]
        assert pickle.loads(recs[1][1])[1] == "split"
    finally:
        drv.close()
        a.close()


def test_refpin_frames_never_reach_python_uncoalesced():
    a, drv, peer = _pipe_pair()
    try:
        oid1, oid2, oid3 = b"A" * 16, b"B" * 16, b"C" * 16
        # oid1: two +1s -> ONE surfaced transition; oid2: +1 then -1 ->
        # both transitions surface; oid3: +1/-1 within one frame -> both
        frame = b"RTP1" + b"".join(
            struct.pack("<16sb", oid, d)
            for oid, d in [(oid1, 1), (oid1, 1), (oid2, 1), (oid2, -1),
                           (oid3, 1), (oid3, -1)])
        peer.send_bytes(frame)
        recs = []
        deadline = time.time() + 5
        while not recs and time.time() < deadline:
            recs = [r for r in (drv.drain(timeout=0.5) or [])
                    if r[0] == 1]
        assert recs, "no refpin transition record surfaced"
        trans = []
        for _, p in recs:
            for oid, d in struct.iter_unpack("<16sb", p):
                trans.append((oid, d))
        assert (oid1, 1) in trans
        assert trans.count((oid1, 1)) == 1  # second +1 coalesced away
        assert (oid2, 1) in trans and (oid2, -1) in trans
        st = drv.stats()
        assert st["refpin_deltas"] == 6
        # death drain: only oid1 still borrowed
        assert drv.drain_pins() == [(oid1, 2)]
        assert drv.drain_pins() == []  # drained == cleared
    finally:
        drv.close()
        a.close()


def test_big_message_grows_drain_buffer_and_eof():
    a, drv, peer = _pipe_pair()
    big = pickle.dumps(("cast", "blob", b"z" * (3 << 20)))
    threading.Thread(target=lambda: peer.send_bytes(big),
                     daemon=True).start()
    got = []
    deadline = time.time() + 10
    while not got and time.time() < deadline:
        got = drv.drain(timeout=0.5) or []
    assert got and got[0][1] == big
    peer.close()
    r = []
    while r == []:
        r = drv.drain(timeout=0.2)
    assert r is None  # EOF after everything was delivered
    assert not drv.send(b"late")  # sends after close report failure
    drv.close()
    a.close()


# ---------------------------------------------------------------------------
# runtime level: the A/B boundary
# ---------------------------------------------------------------------------


def _run_workload():
    @ray_tpu.remote
    def mul(x):
        return x * 3

    @ray_tpu.remote
    class Holder:
        def __init__(self):
            self.ref = None

        def hold(self, ref):
            self.ref = ref  # worker-side borrow -> refpin traffic
            return True

        def read(self):
            return ray_tpu.get(self.ref)

    assert ray_tpu.get([mul.remote(i) for i in range(40)]) == \
        [3 * i for i in range(40)]
    h = Holder.remote()
    ref = ray_tpu.put(b"payload" * 2000)
    assert ray_tpu.get(h.hold.remote([ref])) is True
    assert ray_tpu.get(h.read.remote()) == [b"payload" * 2000]
    return h


def test_native_pipe_on_attaches_engine_and_counts(monkeypatch):
    monkeypatch.setenv("RTPU_NATIVE_PIPE", "1")
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    try:
        from ray_tpu.core.runtime import _get_runtime

        _run_workload()
        rt = _get_runtime()
        # only DIALED-BACK workers: the engine attaches in _serve_worker,
        # so a replenishment spawn still mid-boot legitimately has none
        live = [ws for ws in rt.workers.values()
                if ws.status != "dead" and ws.conn is not None]
        assert live and all(ws.npipe is not None for ws in live)
        totals = {}
        for ws in live:
            for k, v in ws.npipe.stats().items():
                totals[k] = totals.get(k, 0) + v
        assert totals["sent_msgs"] > 0 and totals["recv_msgs"] > 0
        # metric reconciliation: the rtpu_pipe_* counters advance from
        # the native counts at exposition time
        from ray_tpu.util.metrics import registry_records

        sent = recv = 0
        for rec in registry_records():
            if rec["name"] == "rtpu_pipe_messages_total":
                for key, v in rec["samples"]:
                    if dict(key).get("direction") == "sent":
                        sent += v
                    else:
                        recv += v
        assert sent > 0 and recv > 0
    finally:
        ray_tpu.shutdown()


def test_kill_switch_restores_python_path(monkeypatch):
    monkeypatch.setenv("RTPU_NATIVE_PIPE", "0")
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    try:
        from ray_tpu.core.runtime import _get_runtime

        _run_workload()
        rt = _get_runtime()
        live = [ws for ws in rt.workers.values() if ws.status != "dead"]
        assert live and all(ws.npipe is None for ws in live)
    finally:
        ray_tpu.shutdown()


def test_python_fallback_reader_parses_packed_refpins(monkeypatch):
    """Driver without the .so + workers shipping RTP1 frames: the
    Python reader's _apply_refpin_frame keeps borrow tracking exact
    (the two sides never need to agree on the engine)."""
    monkeypatch.setenv("RTPU_NATIVE_PIPE", "1")
    monkeypatch.setattr(_native, "pipe_engine_available", lambda: False)
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    try:
        from ray_tpu.core.runtime import _get_runtime

        rt = _get_runtime()
        h = _run_workload()
        live = [ws for ws in rt.workers.values() if ws.status != "dead"]
        assert live and all(ws.npipe is None for ws in live)
        # the holder's borrow arrived via a packed frame -> ws.pinned
        poll_until(
            lambda: any(ws.pinned for ws in rt.workers.values()),
            timeout=30, desc="packed refpin parsed by fallback reader")
        del h
    finally:
        ray_tpu.shutdown()


def test_worker_death_drains_native_borrow_table(monkeypatch):
    monkeypatch.setenv("RTPU_NATIVE_PIPE", "1")
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    try:
        from ray_tpu.core.runtime import _get_runtime

        rt = _get_runtime()

        @ray_tpu.remote
        class Holder:
            def hold(self, ref):
                self.ref = ref
                return True

        h = Holder.remote()
        ref = ray_tpu.put(b"x" * 50000)
        assert ray_tpu.get(h.hold.remote([ref])) is True
        oid = ref.id.binary()
        # driver ref + worker borrow
        poll_until(lambda: rt._pin_total.get(oid, 0) >= 2, timeout=30,
                   desc="borrow pin lands")
        ray_tpu.kill(h)
        # death drained the native table: only the driver's pin remains
        poll_until(lambda: rt._pin_total.get(oid, 0) == 1, timeout=30,
                   desc="borrow pin released on death")
        assert ray_tpu.get(ref) == b"x" * 50000
    finally:
        ray_tpu.shutdown()
