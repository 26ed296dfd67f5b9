"""TPU accelerator layer with a fake topology provider (no hardware).

Mirrors the reference's mock strategy
(``python/ray/tests/accelerators/test_tpu.py``): fake device listings, GKE
env vars, and metadata lookups; assert env-var effects of visibility
restriction and pod-slice resource derivation.
"""

import os

import pytest

from ray_tpu.accelerators.tpu import (
    TPU_CHIPS_PER_HOST_BOUNDS_ENV,
    TPU_HOST_BOUNDS_ENV,
    TPU_VISIBLE_CHIPS_ENV,
    TPUAcceleratorManager,
    TpuTopologyProvider,
    detect_num_tpu_chips,
)


class FakeProvider(TpuTopologyProvider):
    def __init__(self, devices=(), accel_type=None, metadata=None,
                 worker=0):
        self._devices = list(devices)
        self._accel_type = accel_type
        self._metadata = metadata or {}
        self._worker = worker

    def list_accel_devices(self):
        return self._devices

    def jax_local_chip_count(self):
        return 0

    def gke_accelerator_type(self):
        return self._accel_type

    def gce_metadata(self, key):
        return self._metadata.get(key)

    def worker_id(self):
        return self._worker


def test_detect_chips_from_devices(monkeypatch):
    monkeypatch.delenv(TPU_VISIBLE_CHIPS_ENV, raising=False)
    p = FakeProvider(devices=["/dev/accel0", "/dev/accel1", "/dev/accel2",
                              "/dev/accel3"])
    assert detect_num_tpu_chips(p) == 4


def test_detect_chips_respects_visibility(monkeypatch):
    monkeypatch.setenv(TPU_VISIBLE_CHIPS_ENV, "0,1")
    assert detect_num_tpu_chips(FakeProvider(devices=["/dev/accel0"] * 4)) == 2


@pytest.mark.parametrize("ids,chip_bounds,host_bounds", [
    (["0"], "1,1,1", "1,1,1"),
    (["0", "1"], "1,2,1", "1,1,1"),
    (["0", "1", "2", "3"], "2,2,1", "1,1,1"),
])
def test_visibility_env_vars(monkeypatch, ids, chip_bounds, host_bounds):
    for var in (TPU_VISIBLE_CHIPS_ENV, TPU_CHIPS_PER_HOST_BOUNDS_ENV,
                TPU_HOST_BOUNDS_ENV):
        monkeypatch.delenv(var, raising=False)
    mgr = TPUAcceleratorManager(FakeProvider())
    mgr.set_current_process_visible_accelerator_ids(ids)
    assert os.environ[TPU_VISIBLE_CHIPS_ENV] == ",".join(ids)
    assert os.environ[TPU_CHIPS_PER_HOST_BOUNDS_ENV] == chip_bounds
    assert os.environ[TPU_HOST_BOUNDS_ENV] == host_bounds


def test_invalid_chip_subset_raises(monkeypatch):
    monkeypatch.delenv(TPU_VISIBLE_CHIPS_ENV, raising=False)
    mgr = TPUAcceleratorManager(FakeProvider())
    with pytest.raises(ValueError, match="subset size 3"):
        mgr.set_current_process_visible_accelerator_ids(["0", "1", "2"])
    assert TPU_VISIBLE_CHIPS_ENV not in os.environ


def test_pod_type_from_gke_env():
    mgr = TPUAcceleratorManager(FakeProvider(accel_type="v5litepod-16"))
    assert mgr.get_current_node_accelerator_type() == "v5litepod-16"


def test_pod_type_from_metadata():
    mgr = TPUAcceleratorManager(FakeProvider(
        metadata={"accelerator-type": "v4-16"}))
    assert mgr.get_current_node_accelerator_type() == "v4-16"


def test_pod_type_invalid_rejected():
    mgr = TPUAcceleratorManager(FakeProvider(accel_type="tpu-weird-3"))
    assert mgr.get_current_node_accelerator_type() is None


@pytest.mark.parametrize("pod_type,workers", [
    ("v4-16", 2),          # 16 cores = 8 chips / 4 per host
    ("v4-8", 1),
    ("v5litepod-16", 4),   # 16 chips / 4 per host
    ("v5litepod-256", 64),
    ("v5p-16", 2),         # 16 chips / 8 per host
])
def test_pod_worker_count(pod_type, workers):
    mgr = TPUAcceleratorManager(FakeProvider(accel_type=pod_type))
    assert mgr.get_current_pod_worker_count() == workers


def test_public_helpers_and_fan_out():
    import ray_tpu
    from ray_tpu.util.accelerators import fan_out_per_host, \
        pod_head_resource

    assert pod_head_resource("v5litepod-16") == "TPU-v5litepod-16-head"
    ray_tpu.shutdown()   # a leaked runtime would lack the custom resource
    ray_tpu.init(num_cpus=4, resources={"my-slice": 4})
    try:
        def hostname_task():
            import os as _os

            return _os.getpid()

        refs = fan_out_per_host(hostname_task, "my-slice", 4)
        pids = ray_tpu.get(refs, timeout=60)
        assert len(pids) == 4
    finally:
        ray_tpu.shutdown()


def test_pod_slice_head_resources(monkeypatch):
    monkeypatch.setenv("TPU_NAME", "my-slice")
    head = TPUAcceleratorManager(FakeProvider(accel_type="v5litepod-16",
                                              worker=0))
    res = head.get_extra_resources()
    assert res == {"my-slice": 1.0, "TPU-v5litepod-16-head": 1.0}

    worker = TPUAcceleratorManager(FakeProvider(accel_type="v5litepod-16",
                                                worker=3))
    res = worker.get_extra_resources()
    assert res == {"my-slice": 1.0}


def test_vfio_control_node_is_not_a_chip(monkeypatch):
    """/dev/vfio holds one numbered group per chip beside the ``vfio``
    control node; the one-chip machine listed ``2`` and ``vfio``."""
    import glob as _glob

    listing = {"/dev/accel*": [],
               "/dev/vfio/*": ["/dev/vfio/2", "/dev/vfio/vfio"]}
    monkeypatch.setattr(_glob, "glob", lambda pat: listing[pat])
    assert TpuTopologyProvider().list_accel_devices() == ["/dev/vfio/2"]


@pytest.mark.parametrize("kind,peak", [("TPU v5 lite", 197e12),
                                       ("TPU v4", 275e12)])
def test_peak_flops_known_device_kinds(kind, peak):
    from ray_tpu.util.tpu_info import peak_flops_per_chip

    assert peak_flops_per_chip(kind) == peak


def test_peak_flops_unknown_device_kind_raises():
    from ray_tpu.util.tpu_info import peak_flops_per_chip

    with pytest.raises(KeyError, match="TPU v99"):
        peak_flops_per_chip("TPU v99")
    with pytest.raises(KeyError, match="cpu"):
        peak_flops_per_chip()    # the attached device here is a CPU


def test_tpu_reservation_owns_the_chip_pool_worker_stays_on_cpu(monkeypatch):
    """An actor that reserves TPU runs in a worker spawned on the
    accelerator (platform tpu, its chip subset set before jax could be
    imported); a pool worker stays pinned to cpu. Two 1-chip reservations
    on a 4-chip host get distinct chips; a fractional one raises."""
    import ray_tpu
    from ray_tpu.accelerators import tpu as tpu_mod

    for var in (TPU_VISIBLE_CHIPS_ENV, TPU_CHIPS_PER_HOST_BOUNDS_ENV,
                TPU_HOST_BOUNDS_ENV):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setattr(tpu_mod, "_default_provider", FakeProvider(
        devices=[f"/dev/accel{i}" for i in range(4)]))

    def env_probe():
        import os as _os
        import sys as _sys

        return {"platform": _os.environ.get("JAX_PLATFORMS"),
                "chips": _os.environ.get(TPU_VISIBLE_CHIPS_ENV),
                "bounds": _os.environ.get(TPU_CHIPS_PER_HOST_BOUNDS_ENV),
                "jax_imported": "jax" in _sys.modules}

    class Probe:
        def env(self):
            return env_probe()

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    try:
        assert ray_tpu.cluster_resources().get("TPU") == 4.0
        owner = ray_tpu.remote(Probe).options(num_tpus=1, num_cpus=0)
        a, b = owner.remote(), owner.remote()
        ea, eb = ray_tpu.get([a.env.remote(), b.env.remote()], timeout=60)
        for e in (ea, eb):
            assert e["platform"] == "tpu" and e["bounds"] == "1,1,1"
            assert not e["jax_imported"]
        assert {ea["chips"], eb["chips"]} == {"0", "1"}
        plain = ray_tpu.remote(Probe).remote()
        assert ray_tpu.get(plain.env.remote(), timeout=60)["platform"] == "cpu"
        pool = ray_tpu.get(ray_tpu.remote(env_probe).remote(), timeout=60)
        assert pool["platform"] == "cpu" and pool["chips"] is None
        task = ray_tpu.get(
            ray_tpu.remote(env_probe).options(num_tpus=2).remote(),
            timeout=60)
        assert task["platform"] == "tpu" and task["chips"] == "2,3"
        with pytest.raises(ValueError, match="whole chips"):
            ray_tpu.remote(env_probe).options(num_tpus=0.5).remote()
    finally:
        ray_tpu.shutdown()
