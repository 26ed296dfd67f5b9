"""KV-block shipping between prefill and decode pools (ISSUE 13).

The data plane of disaggregated serving — the TPU analog of the
reference's NCCL channels inside compiled DAGs (PAPER.md L4,
``dag/compiled_dag_node.py:278``): a prefill replica finishes a prompt,
gathers its KV blocks off the paged pool (:class:`~ray_tpu.serve.llm.
KVExport`), and ships them to the decode replica that will own the
stream. Two paths, picked per transfer by node identity:

- **channel** (both replicas share a host): the payload rides one slot
  of a multi-slot seq-numbered :class:`~ray_tpu.experimental.
  device_channel.DeviceChannel` ring as a
  :class:`~ray_tpu.experimental.device_channel.TensorWithMeta` — raw
  tensor body, 64B-aligned, no pickling; one memcpy into shm on the
  prefill side, one out on the decode side. One ring per
  (prefill replica, decode replica) pair, created lazily by the sender
  and demuxed by request id on the receiver (ring order is write order,
  not completion order). A full ring (decode replica wedged or dead)
  fails over to the store path instead of blocking prefill.
- **store** (cross-node): the payload is ``ray_tpu.put`` as ONE
  block-major array and the decode replica pulls it through the store's
  chunk-parallel transfer path; the block stride is registered as a
  pull-alignment hint (``util.state.hint_object_pull_align``) so every
  chunk carries whole KV blocks (block-batch framing on the existing
  chunked-pull path).

Payload layout is **block-major** ``[n_blocks, elements of one block]``:
a block's share of EVERY pool of the engine's cache laid end to end (K
``[L, bs, kvh, hd]``, V the same, and where the model has an indexer its
keys as their pool stores them, ``[L, *index_pool_shape(bs, index_dim)]``:
the same ``bs * index_dim`` values in the same order whichever it is; the
descriptor's ``pools`` names them with their shapes), so one block is one contiguous record — that is what makes
chunk alignment meaningful and keeps a torn transfer impossible to adopt
by construction: the decode engine scatters only a complete batch
delivered by a complete descriptor. A block shipped without one of its
pools would be adopted with garbage there: the engine's ``adopt`` refuses
a payload that lacks a pool its model caches.

Failure seam: ``failpoints.hit("serve.kv_transfer", <req_id>)`` fires
before anything is shipped — the chaos matrix kills a prefill replica
here and asserts the request re-routes with zero leaked blocks or ring
slots on any live replica.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, Optional, Tuple

import numpy as np

from ray_tpu.serve.llm import KVExport


class KVTransferError(RuntimeError):
    """A KV-block transfer could not be completed (payload never
    arrived, geometry mismatch, or the channel/store path failed)."""

    error_type = "kv_transfer"


_METRICS: Any = 0  # unresolved sentinel (None = resolved-unavailable)


def _metrics():
    global _METRICS
    if _METRICS == 0:  # resolve once, not per transfer (hot path)
        try:
            from ray_tpu.util import metric_defs as md

            _METRICS = {
                "bytes": md.get("rtpu_serve_kv_transfer_bytes_total"),
                "transfers": md.get("rtpu_serve_kv_transfers_total"),
                "seconds": md.get("rtpu_serve_kv_transfer_seconds"),
            }
        except Exception:  # metrics plane unavailable (bare unit tests)
            _METRICS = None
    return _METRICS


def _observe(path: str, nbytes: int, seconds: float) -> None:
    m = _metrics()
    if m:
        tags = {"path": path}
        m["bytes"].inc(nbytes, tags=tags)
        m["transfers"].inc(tags=tags)
        m["seconds"].observe(seconds, tags=tags)


def channel_name(src_id: str, dst_id: str) -> str:
    """Ring name for one (prefill, decode) pair. Prefixed with the
    creating runtime's session id so the owning runtime's shutdown
    sweep (``rtpu-chan-<session>-*``) reclaims the shm segment even
    when the replica dies without a graceful close — replicas are
    killed, never asked to clean up."""
    try:
        import ray_tpu

        session = ray_tpu.get_runtime_context().get_session_id()
    except Exception:
        session = "nosess"
    return f"{session}-kvx-{src_id}-{dst_id}"


def pack_export(export: KVExport) -> Tuple[Dict[str, Any], np.ndarray]:
    """(meta, block-major array) for one export. The array is
    ``[n_blocks, elements of one block]``: per block every pool's share
    ([L, bs, ...] each, in the order of ``meta["pools"]``), contiguous."""
    pools = [(name, np.moveaxis(np.asarray(a), 1, 0))     # [n, L, bs, ...]
             for name, a in export.kv.items()]
    n = pools[0][1].shape[0]
    arr = np.ascontiguousarray(np.concatenate(
        [a.reshape(n, -1) for _, a in pools], axis=1))
    meta = {
        "token": int(export.token),
        "prompt_len": int(export.prompt_len),
        "block_size": int(export.block_size),
        "n_blocks": int(n),
        "pools": [[name, list(a.shape[1:])] for name, a in pools],
    }
    return meta, arr


def unpack_payload(meta: Dict[str, Any],
                   arr: np.ndarray) -> Dict[str, np.ndarray]:
    """Invert :func:`pack_export` back to the engine's adopt layout
    ([L, n, bs, ...] per pool)."""
    width = sum(int(np.prod(shape)) for _, shape in meta.get("pools", ()))
    if arr.ndim != 2 or arr.shape != (meta["n_blocks"], width):
        raise KVTransferError(
            f"KV payload shape {arr.shape} does not match descriptor "
            f"({meta.get('n_blocks')} blocks of {width} elements)")
    kv, at = {}, 0
    for name, shape in meta["pools"]:
        size = int(np.prod(shape))
        kv[name] = np.moveaxis(
            arr[:, at:at + size].reshape(arr.shape[0], *shape), 0, 1)
        at += size
    return kv


class KVSender:
    """Prefill-side shipper: one DeviceChannel ring per decode peer on
    the same host (lazily created, cached), store put for remote peers.
    ``ship`` returns the transfer DESCRIPTOR the router forwards to the
    decode replica — the payload itself never touches the router."""

    def __init__(self, src_id: str, *, max_payload_bytes: int,
                 slots: int = 4):
        self.src_id = src_id
        self.max_payload_bytes = int(max_payload_bytes)
        self.slots = slots
        self._chans: Dict[str, Any] = {}
        # the ring is SINGLE-writer: a replica's concurrent request
        # threads must serialize their writes per channel (two threads
        # racing write() would claim the same seq and clobber one
        # payload)
        self._wlocks: Dict[str, threading.Lock] = {}
        self._lock = threading.Lock()

    def _channel(self, dst_id: str):
        from ray_tpu.experimental.device_channel import DeviceChannel

        with self._lock:
            ch = self._chans.get(dst_id)
            if ch is None:
                # slot must hold payload + pickled meta header + padding
                ch = DeviceChannel(channel_name(self.src_id, dst_id),
                                   capacity=self.max_payload_bytes + 4096,
                                   create=True, slots=self.slots)
                self._chans[dst_id] = ch
                self._wlocks[dst_id] = threading.Lock()
            return ch, self._wlocks[dst_id]

    def ship(self, export: KVExport, *, req_id: str, dst_id: str,
             same_host: bool, timeout: float = 10.0) -> Dict[str, Any]:
        """Move one export toward ``dst_id``; returns the descriptor to
        hand to the decode replica's adopt call."""
        from ray_tpu.util import failpoints

        failpoints.hit("serve.kv_transfer", req_id)
        meta, arr = pack_export(export)
        meta["req"] = req_id
        t0 = time.perf_counter()
        if same_host:
            from ray_tpu.experimental.channel import (ChannelFullError,
                                                      ChannelTimeoutError)
            from ray_tpu.experimental.device_channel import TensorWithMeta

            try:
                ch, wlock = self._channel(dst_id)
                with wlock:
                    ch.write(TensorWithMeta(meta, arr), timeout=timeout)
                _observe("channel", arr.nbytes, time.perf_counter() - t0)
                return {"kind": "channel", "channel": ch.name,
                        "meta": meta}
            except (ChannelFullError, ChannelTimeoutError):
                # decode side wedged or slow to drain: the store path has
                # no ring bound — degrade rather than stall prefill
                pass
        import ray_tpu

        try:
            ref = ray_tpu.put(arr)
        except Exception:
            if not same_host:
                raise
            # no object store (in-process harness, no runtime): the
            # only degrade left is to BLOCK on the ring until the
            # decode side drains a slot — still bounded, and a typed
            # error beats a RuntimeError out of ray_tpu.put
            from ray_tpu.experimental.channel import (ChannelFullError,
                                                      ChannelTimeoutError)
            from ray_tpu.experimental.device_channel import \
                TensorWithMeta

            ch, wlock = self._channel(dst_id)
            try:
                with wlock:
                    ch.write(TensorWithMeta(meta, arr), timeout=60.0)
            except (ChannelFullError, ChannelTimeoutError) as e:
                raise KVTransferError(
                    f"KV ring to {dst_id} stayed full and no object "
                    "store is available") from e
            _observe("channel", arr.nbytes, time.perf_counter() - t0)
            return {"kind": "channel", "channel": ch.name, "meta": meta}
        _observe("store", arr.nbytes, time.perf_counter() - t0)
        return {"kind": "ref", "ref": ref, "meta": meta,
                "stride": arr.nbytes // max(arr.shape[0], 1),
                # records start AFTER the serialized header: the puller
                # anchors chunk boundaries at size - payload_bytes
                "payload_bytes": arr.nbytes}

    def close(self) -> None:
        with self._lock:
            chans, self._chans = list(self._chans.values()), {}
        for ch in chans:
            try:
                ch.unlink()
            except Exception:
                pass


class KVReceiver:
    """Decode-side fetcher. Channel payloads arrive in WRITE order on a
    per-sender ring while adopt calls arrive in routing order — so reads
    demux by request id: each fetch drains the ring under the channel's
    lock, parking batches for other requests until their fetch comes.
    Parked entries expire (their request died with its prefill replica)
    so an abandoned payload can never pin host memory forever."""

    _PARK_TTL_S = 60.0

    def __init__(self):
        self._chans: Dict[str, Any] = {}
        self._locks: Dict[str, threading.Lock] = {}
        self._parked: Dict[str, Tuple[float, Dict[str, Any],
                                      np.ndarray]] = {}
        self._lock = threading.Lock()

    def _attach(self, name: str):
        from ray_tpu.experimental.device_channel import DeviceChannel

        with self._lock:
            ch = self._chans.get(name)
            if ch is None:
                ch = DeviceChannel(name, create=False)
                self._chans[name] = ch
                self._locks[name] = threading.Lock()
            return ch, self._locks[name]

    def _prune_parked(self, now: float) -> None:
        with self._lock:
            dead = [k for k, (ts, _m, _a) in self._parked.items()
                    if now - ts > self._PARK_TTL_S]
            for k in dead:
                self._parked.pop(k, None)

    def fetch(self, desc: Dict[str, Any], *, timeout: float = 30.0
              ) -> Tuple[Dict[str, Any], Dict[str, np.ndarray]]:
        """Block until this descriptor's payload is in hand; returns
        ``(meta, kv)`` in the engine's adopt layout."""
        t0 = time.perf_counter()
        self._prune_parked(time.monotonic())  # orphan TTL: every fetch
        meta = desc["meta"]
        req = meta.get("req")
        if desc["kind"] == "ref":
            import ray_tpu
            from ray_tpu.util import state

            state.hint_object_pull_align(desc["ref"].binary()
                                         if hasattr(desc["ref"], "binary")
                                         else desc["ref"],
                                         desc.get("stride", 1),
                                         desc.get("payload_bytes", 0))
            arr = ray_tpu.get(desc["ref"], timeout=timeout)
            kv = unpack_payload(meta, np.asarray(arr))
            _observe("store", arr.nbytes, time.perf_counter() - t0)
            return meta, kv
        if desc["kind"] != "channel":
            raise KVTransferError(f"unknown transfer kind {desc['kind']!r}")
        from ray_tpu.experimental.channel import ChannelTimeoutError

        ch, lock = self._attach(desc["channel"])
        deadline = time.monotonic() + timeout
        while True:
            now = time.monotonic()
            with self._lock:
                parked = self._parked.pop(req, None)
            if parked is not None:
                _ts, pmeta, arr = parked
                kv = unpack_payload(pmeta, arr)
                _observe("channel", arr.nbytes, time.perf_counter() - t0)
                return pmeta, kv
            if now > deadline:
                raise KVTransferError(
                    f"KV payload for request {req!r} never arrived on "
                    f"{desc['channel']} within {timeout}s (prefill "
                    "replica died mid-transfer?)")
            with lock:
                try:
                    val = ch.read(timeout=min(0.5, deadline - now))
                except ChannelTimeoutError:
                    val = None
            if val is None:
                # a long wait must still expire orphans it parked
                self._prune_parked(time.monotonic())
                continue
            got_meta = dict(val.meta)
            if got_meta.get("req") == req:
                kv = unpack_payload(got_meta, val.tensor)
                _observe("channel", val.tensor.nbytes,
                         time.perf_counter() - t0)
                return got_meta, kv
            with self._lock:
                self._parked[got_meta.get("req")] = (
                    time.monotonic(), got_meta, val.tensor)
            self._prune_parked(time.monotonic())

    def close(self) -> None:
        with self._lock:
            chans, self._chans = list(self._chans.values()), {}
            self._locks.clear()
            self._parked.clear()
        for ch in chans:
            try:
                ch.close()
            except Exception:
                pass
