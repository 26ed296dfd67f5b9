"""LLM pretraining with JaxTrainer: mesh-sharded Llama on synthetic data.

Run (CPU mesh): JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/llm_pretrain.py --preset llama-debug --steps 20
On a TPU host, drop the env vars and pick a real preset
(``--preset llama-1b``); the mesh config maps fsdp over all chips.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import jax
import numpy as np
import optax

from ray_tpu import models
from ray_tpu.parallel import MeshConfig
from ray_tpu.train import TrainLoopHelper


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="llama-debug")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--sp", type=int, default=1)
    args = ap.parse_args()

    config = models.get_config(args.preset)
    helper = TrainLoopHelper.create(
        lambda: models.init_params(jax.random.PRNGKey(0), config),
        models.param_axes(config),
        lambda p, b: models.loss_and_metrics(p, b, config),
        optax.adamw(3e-4, weight_decay=0.01),
        mesh_config=MeshConfig(dp=1, fsdp=-1, tp=args.tp, sp=args.sp),
    )
    print(f"mesh: {dict(helper.mesh.shape)}  "
          f"params: {config.num_params() / 1e6:.1f}M")

    rng = np.random.default_rng(0)
    for step in range(args.steps):
        toks = rng.integers(0, config.vocab_size,
                            (args.batch, args.seq + 1), dtype=np.int32)
        batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:]}
        metrics = helper.run_step(batch)
        if step % 5 == 0 or step == args.steps - 1:
            loss = float(jax.device_get(metrics["loss"]))
            print(f"step {step:4d}  loss {loss:.4f}")


if __name__ == "__main__":
    main()
