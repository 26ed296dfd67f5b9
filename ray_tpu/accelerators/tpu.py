"""TPU topology detection and visibility control.

Behavior modeled on the reference's ``python/ray/_private/accelerators/
tpu.py:75`` (``TPUAcceleratorManager``): chip autodetection via
``/dev/accel*`` or ``/dev/vfio`` (:100-120), ``TPU_VISIBLE_CHIPS`` +
``TPU_CHIPS_PER_HOST_BOUNDS`` + ``TPU_HOST_BOUNDS`` for 1/2/4-chip subsets
(:157-196), pod-type detection from GKE env vars or the GCE metadata server
(:198-229), and pod-slice head resources (:335-398). All environment probes
go through an injectable provider so pod logic is unit-testable on CPU
(mirrors the reference's mock strategy in ``tests/accelerators/test_tpu.py``).
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional


TPU_RESOURCE_NAME = "TPU"
NOSET_TPU_VISIBLE_CHIPS_ENV = "RTPU_EXPERIMENTAL_NOSET_TPU_VISIBLE_CHIPS"
TPU_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
TPU_CHIPS_PER_HOST_BOUNDS_ENV = "TPU_CHIPS_PER_HOST_BOUNDS"
TPU_HOST_BOUNDS_ENV = "TPU_HOST_BOUNDS"

# Valid chip-subset sizes per host (reference tpu.py:13).
TPU_VALID_CHIP_OPTIONS = (1, 2, 4)

_BOUNDS_FOR_CHIPS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1"}
_SINGLE_HOST_BOUNDS = "1,1,1"

GKE_TPU_ACCELERATOR_ENV = "TPU_ACCELERATOR_TYPE"
GKE_TPU_WORKER_ID_ENV = "TPU_WORKER_ID"
GCE_METADATA_URL = "http://metadata.google.internal/computeMetadata/v1/instance/attributes"


class TpuTopologyProvider:
    """Injectable environment probe (fake it in tests)."""

    def list_accel_devices(self) -> List[str]:
        # /dev/vfio holds one numbered group per chip next to the "vfio"
        # control node, which is not a chip
        return glob.glob("/dev/accel*") or [
            p for p in glob.glob("/dev/vfio/*")
            if os.path.basename(p).isdigit()]

    def jax_local_chip_count(self) -> int:
        # Only trust a live jax backend if the process ALREADY initialized
        # one — calling jax.devices() here would cold-start the TPU runtime
        # (tens of seconds) as a side effect of ray_tpu.init().
        import sys

        xb = sys.modules.get("jax._src.xla_bridge")
        if xb is None or not getattr(xb, "_backends", None):
            return 0
        try:
            import jax

            devs = [d for d in jax.devices() if "tpu" in d.platform.lower() or "TPU" in str(d)]
            return len(devs)
        except Exception:
            return 0

    def gke_accelerator_type(self) -> Optional[str]:
        return os.environ.get(GKE_TPU_ACCELERATOR_ENV)

    def gce_metadata(self, key: str) -> Optional[str]:
        if os.environ.get("TPU_SKIP_MDS_QUERY"):
            return None  # libtpu's own switch: this host has no metadata server
        try:
            import urllib.request

            req = urllib.request.Request(
                f"{GCE_METADATA_URL}/{key}", headers={"Metadata-Flavor": "Google"}
            )
            with urllib.request.urlopen(req, timeout=1) as resp:
                return resp.read().decode()
        except Exception:
            return None

    def worker_id(self) -> int:
        wid = os.environ.get(GKE_TPU_WORKER_ID_ENV)
        if wid is not None:
            return int(wid)
        v = self.gce_metadata("agent-worker-number")
        return int(v) if v is not None else 0


_default_provider = TpuTopologyProvider()


def detect_num_tpu_chips(provider: Optional[TpuTopologyProvider] = None) -> int:
    """Number of TPU chips attached to this host (0 if none)."""
    p = provider or _default_provider
    visible = os.environ.get(TPU_VISIBLE_CHIPS_ENV)
    if visible is not None:
        return len([c for c in visible.split(",") if c])
    n = len(p.list_accel_devices())
    if n:
        return n
    return p.jax_local_chip_count()


def is_valid_chip_count(n: int) -> bool:
    return n in TPU_VALID_CHIP_OPTIONS


class TPUAcceleratorManager:
    """Accelerator plugin for TPU (reference ABC:
    ``_private/accelerators/accelerator.py``)."""

    def __init__(self, provider: Optional[TpuTopologyProvider] = None):
        self.provider = provider or _default_provider

    @staticmethod
    def get_resource_name() -> str:
        return TPU_RESOURCE_NAME

    def get_current_node_num_accelerators(self) -> int:
        return detect_num_tpu_chips(self.provider)

    def get_current_node_accelerator_type(self) -> Optional[str]:
        """Pod type like ``v5litepod-16`` (reference tpu.py:198-229)."""
        accel = self.provider.gke_accelerator_type()
        if accel is None:
            accel = self.provider.gce_metadata("accelerator-type")
        if accel is None:
            return None
        accel = accel.strip()
        if self._is_valid_pod_type(accel):
            return accel
        return None

    @staticmethod
    def _is_valid_pod_type(s: str) -> bool:
        return re.fullmatch(r"v\d+[a-z]*(pod)?-\d+", s) is not None

    def set_current_process_visible_accelerator_ids(self, ids: List[str]) -> None:
        """Restrict this process to a chip subset via env vars
        (reference tpu.py:157-196)."""
        if os.environ.get(NOSET_TPU_VISIBLE_CHIPS_ENV):
            return
        n = len(ids)
        if not is_valid_chip_count(n):
            raise ValueError(
                f"TPU chip subset size {n} invalid (must be one of "
                f"{TPU_VALID_CHIP_OPTIONS})")
        os.environ[TPU_VISIBLE_CHIPS_ENV] = ",".join(str(i) for i in ids)
        if n in (1, 2):
            os.environ[TPU_CHIPS_PER_HOST_BOUNDS_ENV] = _BOUNDS_FOR_CHIPS[n]
            os.environ[TPU_HOST_BOUNDS_ENV] = _SINGLE_HOST_BOUNDS
        elif n == 4:
            # A whole host's worth of chips: clear subset bounds.
            os.environ[TPU_CHIPS_PER_HOST_BOUNDS_ENV] = _BOUNDS_FOR_CHIPS[4]
            os.environ[TPU_HOST_BOUNDS_ENV] = _SINGLE_HOST_BOUNDS

    def get_current_pod_name(self) -> Optional[str]:
        """Unique name of the TPU pod slice this host belongs to."""
        name = os.environ.get("TPU_NAME")
        if name is None:
            name = self.provider.gce_metadata("instance-id")
        return name

    def get_current_pod_worker_count(self) -> Optional[int]:
        """Hosts in this pod slice (reference tpu.py:274-287):
        v2-v4: 8 cores per host → chips = cores/2, 4 chips/host;
        v5e/v5p/v6e+: count directly in chips, 4 (v5e) or 8 chips/host."""
        pod_type = self.get_current_node_accelerator_type()
        if pod_type is None:
            return None
        gen, size = self._parse_pod_type(pod_type)
        if gen is None:
            return None
        if gen in ("v2", "v3", "v4"):
            chips = size // 2  # size counts TensorCores
            return max(1, chips // 4)
        # v5e and later: size counts chips. v5litepod (v5e) = 4 chips/host;
        # v5p/v6e = 8 chips/host (note: v5e pod types are spelled
        # "v5litepod-N", so gen is "v5" with "lite" in the pod type).
        chips_per_host = 4 if "lite" in pod_type else 8
        return max(1, size // chips_per_host)

    @staticmethod
    def _parse_pod_type(pod_type: str):
        m = re.fullmatch(r"(v\d+[a-z]*?)(?:pod|litepod)?-(\d+)", pod_type)
        if not m:
            return None, None
        return m.group(1), int(m.group(2))

    def get_extra_resources(self) -> Dict[str, float]:
        """Pod-slice resources (reference tpu.py:335-398): every host in a
        slice carries ``{pod_name: 1}``; worker 0 additionally carries
        ``{"TPU-<pod_type>-head": 1}`` so a driver can target the head and
        fan one task out per host."""
        out: Dict[str, float] = {}
        pod_type = self.get_current_node_accelerator_type()
        pod_name = self.get_current_pod_name()
        if pod_name:
            out[pod_name] = 1.0
        if pod_type and self.provider.worker_id() == 0:
            out[pod_head_resource(pod_type)] = 1.0
        return out


def pod_head_resource(pod_type: str) -> str:
    """The head-marker resource name for a pod type (single source of the
    string both the advertiser and schedulers target)."""
    return f"TPU-{pod_type}-head"
