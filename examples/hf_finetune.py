"""Fine-tune an imported HuggingFace checkpoint, then sample from it.

End-to-end: transformers Llama weights -> ray_tpu param pytree
(``models/import_hf.py``, exact-parity mapping) -> a few training steps
with ``TrainLoopHelper`` (pjit over an fsdp mesh, scanned inner loop) ->
greedy generation through the KV-cache decode path.

Uses a tiny randomly initialized HF model so the example runs offline in
seconds; point ``load_hf_llama("<local checkpoint dir>")`` at real
weights on a machine that has them.

Run: JAX_PLATFORMS=cpu python examples/hf_finetune.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import jax
import numpy as np
import optax
import torch
from transformers import LlamaConfig, LlamaForCausalLM

from ray_tpu import models
from ray_tpu.parallel import MeshConfig
from ray_tpu.train import TrainLoopHelper

# 1. a "checkpoint" (tiny + random so the example is self-contained)
torch.manual_seed(0)
hf = LlamaForCausalLM(LlamaConfig(
    vocab_size=256, hidden_size=128, intermediate_size=192,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    max_position_embeddings=128, rms_norm_eps=1e-5)).eval()

# 2. import: config + weights (exact logits parity with transformers)
config = models.config_from_hf(hf.config).replace(remat=False)
params = models.import_hf_llama(hf.state_dict(), config)
print(f"imported {config.num_params():,} params "
      f"(d={config.d_model}, L={config.n_layers})")

# 3. fine-tune on a toy corpus (learn to repeat a phrase)
phrase = np.tile(np.arange(17, 49, dtype=np.int32), 5)[:65]
batch = {"inputs": np.tile(phrase[:-1], (4, 1)),
         "targets": np.tile(phrase[1:], (4, 1))}
helper = TrainLoopHelper.create(
    lambda: params,
    models.param_axes(config),
    lambda p, b: models.loss_and_metrics(p, b, config),
    optax.adamw(1e-3),
    mesh_config=MeshConfig(dp=1, fsdp=-1, tp=1, sp=1),
)
for step in range(5):
    metrics = helper.run_steps(batch, 10)
    print(f"step {(step + 1) * 10:3d}  "
          f"loss {float(jax.device_get(metrics['loss'])):.4f}")

# 4. sample with the fine-tuned weights (KV-cache greedy decode)
tuned = jax.tree.map(jax.numpy.asarray, helper.state["params"])
out = models.generate(tuned, jax.numpy.asarray(phrase[None, :8]),
                      config, max_new_tokens=16)
print("prompt ", phrase[:8].tolist())
print("sampled", np.asarray(out)[0, 8:].tolist())
print("target ", phrase[8:24].tolist())
