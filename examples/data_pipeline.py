"""Streaming data pipeline: lazy plan -> optimizer -> overlapped execution
-> mesh-sharded jax.Array batches (the Ray Data role, TPU-first ingest).

Run (8-device CPU mesh):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/data_pipeline.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import numpy as np

    import ray_tpu
    from ray_tpu import data

    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)

    # Lazy plan: map stages fuse into one task per block (rule-based
    # optimizer); execution streams block REFS through the driver while
    # consumers overlap producers.
    ds = (data.range(4096, parallelism=8)
          .map_batches(lambda b: {"x": b["id"] * 2})
          .map_batches(lambda b: {"x": b["x"] + 1}))
    print("plan:", ds.stats() if hasattr(ds, "stats") else ds)

    total = 0
    for batch in ds.iter_batches(batch_size=512):
        total += int(np.asarray(batch["x"]).sum())
    print("sum over stream:", total)

    # groupby/aggregate runs as distributed shuffle tasks
    agg = (data.range(1000, parallelism=4)
           .map_batches(lambda b: {"k": b["id"] % 10, "v": b["id"]})
           .groupby("k").sum("v"))
    rows = {int(r["k"]): int(r["sum(v)"]) for r in agg.take_all()}
    print("groupby sums:", dict(sorted(rows.items())))

    # TPU ingest: shard a global batch over the ambient mesh's data axes
    import jax

    from ray_tpu.parallel import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(dp=1, fsdp=-1, tp=1, sp=1))
    with jax.set_mesh(mesh):
        it = ds.iterator().iter_jax_batches(batch_size=256, mesh=mesh)
        batch = next(iter(it))
        arr = batch["x"]
        print("sharded batch:", arr.shape, "on",
              len(arr.sharding.device_set), "devices")

    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
