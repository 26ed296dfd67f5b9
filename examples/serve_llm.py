"""Serve a (tiny) LLM with dynamic batching over replica actors.

Run: JAX_PLATFORMS=cpu python examples/serve_llm.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import numpy as np

import ray_tpu
from ray_tpu import serve


@serve.deployment(num_replicas=1)
class LLMReplica:
    """Loads a jitted model once; every request hits the compiled fn."""

    def __init__(self, preset="gpt2-debug"):
        import jax

        from ray_tpu import models

        self.config = models.get_config(preset)
        self.params = models.init_params(jax.random.PRNGKey(0), self.config)
        self.models = models

    def __call__(self, prompt_tokens):
        import jax.numpy as jnp

        prompt = jnp.asarray([prompt_tokens], jnp.int32)
        out = self.models.generate(self.params, prompt, self.config,
                                   max_new_tokens=8)
        return np.asarray(out)[0].tolist()


def main():
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    handle = serve.run(LLMReplica.bind())
    out = handle.remote([1, 2, 3, 4]).result(timeout_s=120)
    print("generated tokens:", out)
    serve.shutdown()
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
