"""Pipeline parallelism: GPipe schedule correctness vs sequential, grads."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.train.pipeline import (
    merge_microbatches,
    pipeline_apply,
    split_microbatches,
)

PP = 4
LAYERS = 8  # 2 per stage
DIM = 16


def _layer_fn(lp, h):
    return jnp.tanh(h @ lp["w"] + lp["b"])


def _make_params(key):
    ks = jax.random.split(key, LAYERS)
    return {
        "w": jnp.stack([jax.random.normal(k, (DIM, DIM)) * 0.3 for k in ks]),
        "b": jnp.zeros((LAYERS, DIM)),
    }


def _sequential(params, x):
    def body(h, lp):
        return _layer_fn(lp, h), None

    out, _ = jax.lax.scan(body, x, params)
    return out


@pytest.fixture
def pp_mesh():
    devs = np.array(jax.devices()[:PP])
    return Mesh(devs, ("pp",))


def test_pipeline_matches_sequential(pp_mesh):
    params = _make_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, DIM))
    micro = split_microbatches(x, 4)

    ref = _sequential(params, x)

    fn = shard_map(
        lambda p, m: pipeline_apply(_layer_fn, p, m, axis="pp"),
        mesh=pp_mesh,
        in_specs=({"w": P("pp"), "b": P("pp")}, P()),
        out_specs=P(),
        check_vma=False,
    )
    out = merge_microbatches(fn(params, micro))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_pipeline_grads_match_sequential(pp_mesh):
    params = _make_params(jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (8, DIM))
    micro = split_microbatches(x, 4)

    def seq_loss(p):
        return jnp.sum(_sequential(p, x) ** 2)

    def pp_loss(p):
        fn = shard_map(
            lambda pp_, m: pipeline_apply(_layer_fn, pp_, m, axis="pp"),
            mesh=pp_mesh,
            in_specs=({"w": P("pp"), "b": P("pp")}, P()),
            out_specs=P(),
            check_vma=False,
        )
        return jnp.sum(merge_microbatches(fn(p, micro)) ** 2)

    g_ref = jax.grad(seq_loss)(params)
    g_pp = jax.jit(jax.grad(pp_loss))(params)
    for k in g_ref:
        np.testing.assert_allclose(np.asarray(g_pp[k]), np.asarray(g_ref[k]),
                                   atol=1e-4, rtol=1e-4)


def test_microbatch_split_merge_roundtrip():
    x = jnp.arange(24).reshape(12, 2)
    micro = split_microbatches(x, 3)
    assert micro.shape == (3, 4, 2)
    np.testing.assert_array_equal(np.asarray(merge_microbatches(micro)),
                                  np.asarray(x))
    with pytest.raises(ValueError):
        split_microbatches(x, 5)


def test_transformer_pipelined_forward_matches_scan():
    """The pp>1 pipelined transformer (partial-auto shard_map over the pp
    axis composing with fsdp/tp GSPMD) must match the pp=1 scanned forward
    loss exactly in float32."""
    import numpy as np
    import optax

    import jax
    from ray_tpu import models
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.train import TrainLoopHelper

    config = models.llama_debug().replace(pp_microbatches=2, remat=False,
                                          dtype="float32")
    rng = np.random.default_rng(0)
    toks = rng.integers(0, config.vocab_size, size=(4, 65), dtype=np.int32)
    batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:]}

    losses = {}
    for name, mc in (("scan", MeshConfig(dp=1, fsdp=-1, tp=2, sp=2, pp=1)),
                     ("pp", MeshConfig(dp=1, fsdp=-1, tp=2, sp=1, pp=2))):
        mesh = make_mesh(mc, devices=jax.devices()[:8])
        helper = TrainLoopHelper.create(
            lambda: models.init_params(jax.random.PRNGKey(0), config),
            models.param_axes(config),
            lambda p, b: models.loss_and_metrics(p, b, config),
            optax.adamw(1e-3),
            mesh=mesh,
        )
        losses[name] = float(jax.device_get(helper.run_step(batch)["loss"]))
    assert abs(losses["scan"] - losses["pp"]) < 1e-4, losses
