"""Data plane (r14): multi-threaded memcpy, compressed spill/restore,
chunk-parallel cross-node transfer."""

import os

import numpy as np
import pytest

from ray_tpu import _native
from ray_tpu.core import spill_codec
from ray_tpu.core.ids import ObjectID
from ray_tpu.core.object_store import StoreClient, _spill_path


# ---------------------------------------------------------------------------
# LZ4 codec + spill file format
# ---------------------------------------------------------------------------


@pytest.mark.skipif(not _native.pipe_engine_available(),
                    reason="native codec unavailable")
def test_lz4_roundtrip_shapes():
    import random

    random.seed(7)
    cases = [
        b"",
        b"a",
        b"abc" * 50_000,                     # highly repetitive
        os.urandom(200_000),                 # incompressible
        bytes(random.choices(b"abcd", k=300_000)),  # low entropy
        b"\x00" * 1_000_000,                 # RLE extreme
        os.urandom(13),                      # below the match minimum
    ]
    for data in cases:
        comp = _native.lz4_compress(data)
        assert comp is not None
        assert _native.lz4_decompress(comp, len(data)) == data
        out = bytearray(len(data))
        if data:
            assert _native.lz4_decompress_into(comp, out) == len(data)
            assert bytes(out) == data


def test_spill_file_roundtrip_and_ranges(tmp_path):
    payloads = [
        b"\x00" + b"ab" * 200_000,   # compressible (first byte 0x00 like
        b"\x00" + os.urandom(250_000),  # real serialized objects)
        b"",
    ]
    for i, payload in enumerate(payloads):
        p = str(tmp_path / f"s{i}")
        spill_codec.write_spill(p, payload)
        assert spill_codec.raw_size(p) == len(payload)
        assert spill_codec.read_bytes(p) == payload
        buf = bytearray(len(payload))
        assert spill_codec.read_into(p, buf, len(payload))
        assert bytes(buf) == payload
        if payload:
            assert spill_codec.read_range(p, 7, 1000) == payload[7:1007]
            assert spill_codec.read_range(p, len(payload) - 9, 50) == \
                payload[-9:]
            # block-crossing range (blocks are 4 MiB; small files are one
            # block, so also cover a multi-block file below)
    big = (b"\x00" + b"xy" * (3 << 20))  # > one 4 MiB block
    p = str(tmp_path / "multi")
    spill_codec.write_spill(p, big)
    off = (4 << 20) - 100
    assert spill_codec.read_range(p, off, 300) == big[off:off + 300]


def test_streaming_spill_write_matches_buffered_layout(tmp_path):
    """The spill path streams serialization.iter_serialized_blocks
    through the codec (peak extra heap = one block); the result must
    deserialize identically to the buffered write_into layout."""
    from ray_tpu.core import serialization

    value = {"a": np.arange(3 << 20, dtype=np.float64),  # 24 MiB buffer
             "b": b"tail" * 1000, "c": list(range(50))}
    data, buffers = serialization.serialize(value)
    size = serialization.serialized_size(data, buffers)
    # streamed chunks re-assemble to EXACTLY the write_into image
    ref = bytearray(size)
    serialization.write_into(memoryview(ref), data, buffers)
    streamed = b"".join(serialization.iter_serialized_blocks(
        data, buffers, spill_codec.BLOCK_RAW))
    assert streamed == bytes(ref)
    # and the codec file round-trips back to the value
    p = str(tmp_path / "stream")
    spill_codec.write_spill_stream(
        p, size, serialization.iter_serialized_blocks(
            data, buffers, spill_codec.BLOCK_RAW))
    assert spill_codec.raw_size(p) == size
    out = bytearray(size)
    assert spill_codec.read_into(p, out, size)
    got = serialization.read_from(memoryview(bytes(out)))
    assert np.array_equal(got["a"], value["a"])
    assert got["b"] == value["b"] and got["c"] == value["c"]


def test_legacy_raw_spill_files_still_read(tmp_path):
    payload = b"\x00" + os.urandom(50_000)
    p = str(tmp_path / "legacy")
    with open(p, "wb") as f:
        f.write(payload)  # headerless pre-r14 spill file
    assert not spill_codec.is_compressed(p)
    assert spill_codec.raw_size(p) == len(payload)
    assert spill_codec.read_bytes(p) == payload
    assert spill_codec.read_range(p, 5, 10) == payload[5:15]


def test_spill_compression_off_writes_raw(tmp_path, monkeypatch):
    monkeypatch.setenv("RTPU_SPILL_COMPRESSION", "off")
    payload = b"\x00" + b"zz" * 100_000
    p = str(tmp_path / "raw")
    n = spill_codec.write_spill(p, payload)
    assert n == len(payload)
    assert not spill_codec.is_compressed(p)
    assert spill_codec.read_bytes(p) == payload


def test_zlib_codec_fallback(tmp_path, monkeypatch):
    monkeypatch.setenv("RTPU_SPILL_COMPRESSION", "zlib")
    payload = b"\x00" + b"ab" * 100_000
    p = str(tmp_path / "z")
    n = spill_codec.write_spill(p, payload)
    assert n < len(payload) and spill_codec.is_compressed(p)
    assert spill_codec.read_bytes(p) == payload


# ---------------------------------------------------------------------------
# store-level: compressed spill -> read -> restore, metrics move
# ---------------------------------------------------------------------------


def _metric_total(name):
    from ray_tpu.util.metrics import registry_records

    total = 0.0
    for rec in registry_records():
        if rec["name"] == name:
            for _k, v in rec["samples"]:
                total += v if not isinstance(v, tuple) else v[2]
    return total


def test_compressed_spill_restore_roundtrip(monkeypatch):
    session = "dp-" + os.urandom(4).hex()
    monkeypatch.setenv("RTPU_SPILL_THRESHOLD", str(1 << 20))
    monkeypatch.setenv("RTPU_STORE_CAPACITY", str(1 << 20))
    monkeypatch.setenv("RTPU_STORE_PREFAULT_BYTES", "0")
    sc = StoreClient(session)
    try:
        oid = ObjectID.from_random()
        arr = np.tile(np.arange(512), 8192)  # 32 MiB, compressible
        comp0 = _metric_total(
            "rtpu_object_store_spill_compressed_bytes_total")
        inline, size = sc.put(oid, arr)
        assert inline is None
        path = _spill_path(session, oid)
        assert os.path.exists(path), "object did not spill"
        assert spill_codec.is_compressed(path)
        phys = os.stat(path).st_size
        assert phys < size // 4, "compression should win big here"
        assert _metric_total(
            "rtpu_object_store_spill_compressed_bytes_total") > comp0
        # bytes identical through every read path
        assert np.array_equal(sc.get(oid), arr)
        raw = sc.get_raw(oid)
        assert len(raw) == size
        assert sc.get_raw_chunk(oid, 123, 4567) == raw[123:123 + 4567]
        sc.release(oid)

        # restore: lift the shm pressure and promote back into the arena
        monkeypatch.setenv("RTPU_SPILL_THRESHOLD", str(4 << 30))
        sc2 = StoreClient(session)
        r0 = _metric_total("rtpu_object_store_restored_objects_total")
        assert sc2.restore_spilled(oid)
        assert not os.path.exists(path), "spill file kept after restore"
        assert _metric_total(
            "rtpu_object_store_restored_objects_total") > r0
        assert np.array_equal(sc2.get(oid), arr)
        sc2.release(oid)
    finally:
        sc.close()   # gives the arena's pages back
        StoreClient.cleanup_session(session)


def test_compressed_spill_served_without_restore_headroom(monkeypatch):
    """No shm headroom: the compressed spill is inflated to a HEAP pin
    and served, views staying valid until release."""
    session = "dp-" + os.urandom(4).hex()
    monkeypatch.setenv("RTPU_SPILL_THRESHOLD", str(1 << 20))
    monkeypatch.setenv("RTPU_STORE_CAPACITY", str(1 << 20))
    monkeypatch.setenv("RTPU_STORE_PREFAULT_BYTES", "0")
    sc = StoreClient(session)
    try:
        oid = ObjectID.from_random()
        arr = np.tile(np.arange(256), 4096)
        sc.put(oid, arr)
        assert spill_codec.is_compressed(_spill_path(session, oid))
        out = sc.get(oid)  # threshold still tiny: restore refused
        assert np.array_equal(out, arr)
        del out
        sc.release(oid)
    finally:
        sc.close()   # gives the arena's pages back
        StoreClient.cleanup_session(session)


# ---------------------------------------------------------------------------
# multi-threaded memcpy
# ---------------------------------------------------------------------------


@pytest.mark.skipif(not _native.pipe_engine_available(),
                    reason="native copy unavailable")
def test_parallel_copy_exact():
    for n in (1 << 10, (1 << 20) + 13, 8 << 20):
        src = os.urandom(n)
        dst = bytearray(n)
        assert _native.parallel_copy(dst, src) == n
        assert bytes(dst) == src


@pytest.mark.skipif(not _native.pipe_engine_available(),
                    reason="native copy unavailable")
def test_store_put_uses_parallel_copy(monkeypatch):
    monkeypatch.setenv("RTPU_STORE_PARALLEL_COPY_BYTES", str(1 << 20))
    monkeypatch.setenv("RTPU_STORE_PREFAULT_BYTES", "0")
    from ray_tpu.core import serialization

    # the threshold is cached; reset so the env override applies
    monkeypatch.setattr(serialization, "_pcopy_min", None)
    session = "dp-" + os.urandom(4).hex()
    sc = StoreClient(session)
    try:
        before = _metric_total(
            "rtpu_object_store_parallel_copy_bytes_total")
        oid = ObjectID.from_random()
        arr = np.random.default_rng(0).standard_normal(1 << 21)  # 16 MiB
        sc.put(oid, arr)
        assert np.array_equal(sc.get(oid), arr)
        sc.release(oid)
        assert _metric_total(
            "rtpu_object_store_parallel_copy_bytes_total") >= \
            before + arr.nbytes
    finally:
        sc.close()   # gives the arena's pages back
        StoreClient.cleanup_session(session)
        monkeypatch.setattr(serialization, "_pcopy_min", None)


# ---------------------------------------------------------------------------
# chunk-parallel cross-node transfer (standalone harness; the cluster
# suite covers the in-situ RPC path)
# ---------------------------------------------------------------------------


class _Writer:
    def __init__(self, n):
        self.buf = bytearray(n)

    def write(self, off, data):
        self.buf[off:off + len(data)] = data


def test_pull_chunks_parallel_exact():
    from ray_tpu.cluster.adapter import pull_chunks

    src = os.urandom(9_000_000)
    calls = []

    def call(method, oid_b, off, ln, timeout=None):
        assert method == "pull_chunk"
        calls.append(off)
        return src[off:off + ln]

    w = _Writer(len(src))
    assert pull_chunks(call, b"o" * 16, len(src), w,
                       chunk=1 << 20, parallel=3)
    assert bytes(w.buf) == src
    assert sorted(calls) == list(range(0, len(src), 1 << 20))


def test_pull_chunks_short_chunk_fails_closed():
    from ray_tpu.cluster.adapter import pull_chunks

    src = os.urandom(3_000_000)

    def call(method, oid_b, off, ln, timeout=None):
        blob = src[off:off + ln]
        return blob[:-1] if off else blob  # later chunks come up short

    w = _Writer(len(src))
    assert not pull_chunks(call, b"o" * 16, len(src), w,
                           chunk=1 << 20, parallel=2)


def test_pull_chunks_serial_matches_parallel():
    from ray_tpu.cluster.adapter import pull_chunks

    src = os.urandom(2_500_000)

    def call(method, oid_b, off, ln, timeout=None):
        return src[off:off + ln]

    w1, w2 = _Writer(len(src)), _Writer(len(src))
    assert pull_chunks(call, b"o" * 16, len(src), w1,
                       chunk=1 << 20, parallel=1)
    assert pull_chunks(call, b"o" * 16, len(src), w2,
                       chunk=1 << 20, parallel=4)
    assert bytes(w1.buf) == bytes(w2.buf) == src
