"""WorkerGroup: the gang of train-worker actors.

Role analog: ``python/ray/train/_internal/worker_group.py`` (``WorkerGroup``
:102, ``RayTrainWorker`` :19). Each worker is one host process owning that
host's accelerator devices through a single jax runtime.
"""

from __future__ import annotations

import os
import socket
from typing import Any, Callable, Dict, List, Optional

import ray_tpu
from ray_tpu.train.checkpoint import Checkpoint
from ray_tpu.train.session import TrainContext, _Session, _init_session, \
    _shutdown_session, get_session


class RayTrainWorker:
    """Actor running on each host of the worker group."""

    def __init__(self):
        self._session: Optional[_Session] = None

    # -- environment / metadata ------------------------------------------

    def get_metadata(self) -> Dict[str, Any]:
        return {
            "hostname": socket.gethostname(),
            "ip": socket.gethostbyname(socket.gethostname()),
            "pid": os.getpid(),
        }

    def get_device_info(self) -> Dict[str, Any]:
        import jax

        devs = jax.local_devices()
        return {
            "backend": jax.default_backend(),
            "local_device_count": len(devs),
            "global_device_count": jax.device_count(),
            "process_index": jax.process_index(),
        }

    def execute(self, fn: Callable, *args, **kwargs):
        """Run an arbitrary function in the worker process."""
        return fn(*args, **kwargs)

    # -- training session -------------------------------------------------

    def start_session(
        self,
        train_fn: Callable,
        context: TrainContext,
        starting_checkpoint_path: Optional[str] = None,
    ) -> None:
        from ray_tpu.util.tpu_info import ensure_compile_cache

        ensure_compile_cache()  # before the loop's first compile
        ckpt = (Checkpoint(starting_checkpoint_path)
                if starting_checkpoint_path else None)
        os.makedirs(context.trial_dir, exist_ok=True)
        session = _Session(lambda: train_fn(context.loop_config)
                           if _fn_wants_config(train_fn) else train_fn(),
                           context, ckpt)
        self._session = session
        _init_session(session)
        session.start()

    def next_result(self, timeout: Optional[float] = 60.0):
        assert self._session is not None, "no session running"
        kind, payload, ckpt = self._session.next_result(timeout=timeout)
        if kind == "error":
            raise payload
        return (kind, payload, ckpt)

    def shutdown_session(self) -> None:
        self._session = None
        _shutdown_session()


def _fn_wants_config(fn: Callable) -> bool:
    import inspect

    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return False
    return len(sig.parameters) >= 1


class WorkerGroup:
    """Spawns and addresses N RayTrainWorker actors."""

    def __init__(
        self,
        num_workers: int,
        resources_per_worker: Dict[str, float],
        placement_group=None,
    ):
        cls = ray_tpu.remote(RayTrainWorker)
        self.workers: List[Any] = []
        for i in range(num_workers):
            opts: Dict[str, Any] = {
                "num_cpus": resources_per_worker.get("CPU", 1.0),
                "resources": {k: v for k, v in resources_per_worker.items()
                              if k != "CPU"},
            }
            if placement_group is not None:
                opts["placement_group"] = placement_group
                opts["placement_group_bundle_index"] = i
            self.workers.append(cls.options(**opts).remote())

    def __len__(self):
        return len(self.workers)

    def execute(self, fn: Callable, *args, **kwargs) -> List[Any]:
        """Run fn on every worker, return all results (ordered by rank)."""
        return ray_tpu.get([w.execute.remote(fn, *args, **kwargs)
                            for w in self.workers])

    def execute_async(self, fn: Callable, *args, **kwargs):
        return [w.execute.remote(fn, *args, **kwargs) for w in self.workers]

    def execute_single(self, rank: int, fn: Callable, *args, **kwargs) -> Any:
        return ray_tpu.get(self.workers[rank].execute.remote(fn, *args, **kwargs))

    def shutdown(self):
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
        self.workers = []
