"""Device-tensor channels: the compiled-DAG accelerator data plane.

Role analog: the reference's NCCL channels for DAG edges
(``python/ray/experimental/channel/torch_tensor_nccl_channel.py:29``,
``nccl_group.py:18``) typed by ``TorchTensorType``
(``torch_tensor_type.py``). TPU-native shape of the idea:

- an edge annotated :class:`DeviceTensorType` carries ONE jax array whose
  payload bytes move through the channel's ring slot RAW (dtype/shape in
  a tiny header) instead of the generic pickle path;
- the reader materializes a ``jax.Array`` straight from the mapped slot:
  zero-copy via dlpack on host-mapped backends (CPU — the consumer array
  aliases the slot memory, no copy at all), one H2D DMA on TPU
  (``jax.device_put``; cross-process device memory can't be shared through
  host shm, so one hop is the floor — the reference pays the same in NCCL
  as a D2D hop);
- non-tensor control values (teardown/error sentinels) fall back to the
  pickle path transparently.

Zero-copy safety under pipelining (r13 ring rewrite): the ring's
backpressure means a slot is only overwritten ``nslots`` values later,
and the compiled DAG sizes every channel ``max_in_flight + 1`` slots —
so a stage that consumes its input before the pipeline admits another
``max_in_flight`` invocations (which FIFO result delivery enforces) can
never observe its aliased array being clobbered.

True chip-to-chip movement with NO host involvement belongs INSIDE a jit
program over a mesh (ppermute/collectives — see ray_tpu.parallel); that is
the TPU-idiomatic fast path the reference's NCCL channels approximate from
the outside.
"""

from __future__ import annotations

import pickle
import struct
from typing import Any, Optional

from ray_tpu.experimental.channel import Channel

_KIND_PICKLE = 0
_KIND_TENSOR = 1
_KIND_META_TENSOR = 2
_PREFIX = struct.Struct("<BIH")  # (kind, header_size, body_pad)
_ALIGN = 64  # body alignment: unaligned buffers force jax to copy on import


class TensorWithMeta:
    """Channel payload pairing a small picklable ``meta`` dict with ONE
    host tensor whose bytes ride the ring slot RAW (64B-aligned body,
    like the bare-tensor kind) — the KV-block shipping shape (ISSUE 13):
    meta carries request identity/geometry, the tensor carries the block
    batch, and neither side ever pickles the tensor body. The reader
    gets the array as a COPY (ring backpressure protects aliased reads
    only while the value is being consumed in-stage; KV adoption defers
    the device scatter to the decode engine's loop thread, which may run
    after this reader advances past ``nslots`` more values)."""

    __slots__ = ("meta", "tensor")

    def __init__(self, meta: dict, tensor):
        self.meta = meta
        self.tensor = tensor


class DeviceTensorType:
    """Edge type hint: the value is a jax array to ship device-to-device
    (reference ``TorchTensorType`` role)."""

    def __init__(self, device: Optional[str] = None):
        self.device = device  # None -> consumer's default device

    def __repr__(self):
        return f"DeviceTensorType(device={self.device!r})"


def _is_jax_array(value) -> bool:
    import sys

    jnp_mod = sys.modules.get("jax")
    return jnp_mod is not None and isinstance(value, jnp_mod.Array)


class DeviceChannel(Channel):
    """Channel whose payloads are jax arrays moved as raw device bytes."""

    def _encode(self, value: Any):
        if isinstance(value, TensorWithMeta):
            import numpy as np

            host = np.asarray(value.tensor)
            # the dtype OBJECT, not dtype.str: extension dtypes
            # (ml_dtypes bfloat16 — the KV payload dtype) stringify to
            # an opaque void ("|V2") that cannot round-trip
            header = pickle.dumps((value.meta, host.dtype, host.shape))
            body = (host if host.flags["C_CONTIGUOUS"] else host.tobytes())
            return self._encode_parts(_KIND_META_TENSOR, header, body,
                                      host.nbytes)
        if not _is_jax_array(value):
            body = pickle.dumps(value)
            return self._encode_parts(_KIND_PICKLE, b"", body, len(body))
        import numpy as np

        host = np.asarray(value)  # D2H (CPU backend: view, no copy)
        header = pickle.dumps((host.dtype.str, host.shape))
        body = (host if host.flags["C_CONTIGUOUS"] else host.tobytes())
        return self._encode_parts(_KIND_TENSOR, header, body, host.nbytes)

    def _encode_parts(self, kind: int, header: bytes, body, nbytes: int):
        # pad so the body lands 64B-aligned in the mapped file regardless
        # of which slot it goes to (slot payload offsets are themselves
        # multiples of the slot stride; align relative to the file start
        # by padding to the next _ALIGN boundary past the headers)
        pad = (-(_PREFIX.size + len(header))) % _ALIGN
        total = _PREFIX.size + len(header) + pad + nbytes

        def fill(mm, off):
            import numpy as np

            _PREFIX.pack_into(mm, off, kind, len(header), pad)
            o = off + _PREFIX.size
            mm[o:o + len(header)] = header
            o += len(header) + pad
            view = np.frombuffer(mm, np.uint8, nbytes, o)
            if isinstance(body, (bytes, bytearray)):
                view[:] = np.frombuffer(body, np.uint8)
            else:
                view[:] = np.asarray(body, order="C").reshape(-1).view(
                    np.uint8)
            del view

        return total, fill

    def read(self, timeout: Optional[float] = None) -> Any:
        off, size = self._wait_slot(timeout)
        value = self._decode(off, size)
        self._advance()
        return value

    def _decode(self, off: int, size: int):
        import numpy as np

        kind, hsize, pad = _PREFIX.unpack_from(self._mm, off)
        o = off + _PREFIX.size
        header = bytes(self._mm[o:o + hsize])
        o += hsize + pad
        body_size = size - _PREFIX.size - hsize - pad
        if kind == _KIND_PICKLE:
            return pickle.loads(bytes(self._mm[o:o + body_size]))
        if kind == _KIND_META_TENSOR:
            meta, dtype_obj, shape = pickle.loads(header)
            dt = np.dtype(dtype_obj)
            view = np.frombuffer(self._mm, dt, body_size // dt.itemsize,
                                 o).reshape(shape)
            # copy out of the mapped slot: the consumer (KV adoption)
            # uses the array after this reader's cursor moves on
            return TensorWithMeta(meta, np.array(view))
        dtype_str, shape = pickle.loads(header)
        dtype = np.dtype(dtype_str)
        host = np.frombuffer(self._mm, dtype, body_size // dtype.itemsize,
                             o).reshape(shape)
        import jax

        if jax.default_backend() == "cpu":
            # zero-copy: the consumer jax array aliases the slot memory
            # (ring backpressure + FIFO-bounded admission mean the writer
            # cannot clobber this slot while a correctly-driven DAG stage
            # still uses the value — see module docstring)
            try:
                return jax.dlpack.from_dlpack(host)
            except Exception:
                pass
        return jax.device_put(host)  # one H2D DMA on accelerators

    def __reduce__(self):
        return (_attach_device_channel, (self.name,))


def _attach_device_channel(name: str) -> "DeviceChannel":
    return DeviceChannel(name, create=False)
