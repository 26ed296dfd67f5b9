"""Pipeline parallelism: microbatched GPipe schedule over the ``pp`` axis.

Absent from the reference (SURVEY §2.4: pipeline parallel = "absent").
TPU-native formulation: stages are the ``pp`` mesh axis; the layer stack is
sharded over it so each device group holds L/pp layers; activations rotate
stage-to-stage with ``lax.ppermute`` (one ICI hop); the whole schedule is a
``lax.scan`` inside ``shard_map``, so XLA overlaps the permute of tick t+1
with stage compute of tick t. Autodiff through the scan replays the
schedule in reverse, which IS the backward pipeline (collective-permute
transposes to the opposite rotation) — no hand-written 1F1B needed for
correctness; the bubble is the standard GPipe (S-1)/(M+S-1) fraction.

Use: stack per-layer params on a leading dim, map that dim's logical axis
to ``pp`` (``ShardingRules({"layers": "pp", ...})``), and call
:func:`pipeline_apply` inside ``shard_map`` over the mesh.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax


def pipeline_apply(
    layer_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    microbatches: jax.Array,
    *,
    axis: str = "pp",
) -> jax.Array:
    """Run microbatches through all pipeline stages.

    Called inside ``shard_map`` over ``axis``:

    - ``stage_params``: THIS stage's layer stack, leading dim = layers
      owned by the stage (global stack sharded over ``axis``).
    - ``microbatches``: [M, mb, ...] — every stage receives the same
      value; only stage 0 actually consumes it.

    Returns [M, mb, ...] of final-stage outputs, valid on every stage
    (broadcast at the end so downstream loss code is SPMD-uniform).
    """
    num_stages = lax.axis_size(axis)
    stage_id = lax.axis_index(axis)
    num_micro = microbatches.shape[0]
    ticks = num_micro + num_stages - 1

    def stage_compute(x):
        # apply this stage's layers sequentially (scan over local stack)
        def body(h, lp):
            return layer_fn(lp, h), None

        out, _ = lax.scan(body, x, stage_params)
        return out

    mb_shape = microbatches.shape[1:]
    state = jnp.zeros(mb_shape, microbatches.dtype)      # in-flight act
    outputs = jnp.zeros((num_micro,) + mb_shape, microbatches.dtype)
    # the carry is per-stage data from the first rotation on: mark it
    # varying over the pipeline axis up front or the scan's VMA check
    # rejects the unvarying->varying promotion (partial-auto shard_map)
    state = lax.pcast(state, (axis,), to="varying")
    outputs = lax.pcast(outputs, (axis,), to="varying")

    fwd_perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]

    def tick(carry, t):
        state, outputs = carry
        # stage 0 ingests microbatch t (while t < M); others keep the
        # activation that just arrived from the previous stage
        ingest = microbatches[jnp.minimum(t, num_micro - 1)]
        x = jnp.where(stage_id == 0,
                      jnp.where(t < num_micro, ingest, state), state)
        y = stage_compute(x)
        # last stage emits microbatch (t - (S-1)) when it's valid. A
        # where-gated unconditional update, not lax.cond: both are
        # correct, but cond+dynamic_update in a partial-auto shard_map
        # scan tripped an XLA CPU lowering CHECK ("invalid binary
        # instruction opcode copy"); the select formulation lowers clean
        # and costs one masked write per tick.
        emit_idx = t - (num_stages - 1)
        valid = jnp.logical_and(stage_id == num_stages - 1, emit_idx >= 0)
        updated = lax.dynamic_update_index_in_dim(
            outputs, y, jnp.maximum(emit_idx, 0), 0)
        outputs = jnp.where(valid, updated, outputs)
        # rotate activations to the next stage
        state = lax.ppermute(y, axis, fwd_perm)
        return (state, outputs), None

    (state, outputs), _ = lax.scan(tick, (state, outputs),
                                   jnp.arange(ticks))
    # broadcast final-stage outputs to all stages (loss is SPMD-uniform)
    outputs = _select_from_stage(outputs, num_stages - 1, axis)
    return outputs


def _select_from_stage(x: jax.Array, src: int, axis: str) -> jax.Array:
    """All stages receive stage ``src``'s value (masked psum broadcast)."""
    stage_id = lax.axis_index(axis)
    masked = jnp.where(stage_id == src, x, jnp.zeros_like(x))
    return lax.psum(masked, axis)


def split_microbatches(batch: jax.Array, num_micro: int) -> jax.Array:
    """[B, ...] -> [M, B/M, ...]."""
    b = batch.shape[0]
    if b % num_micro:
        raise ValueError(f"batch {b} not divisible by {num_micro} microbatches")
    return batch.reshape((num_micro, b // num_micro) + batch.shape[1:])


def merge_microbatches(micro: jax.Array) -> jax.Array:
    """[M, mb, ...] -> [B, ...]."""
    return micro.reshape((-1,) + micro.shape[2:])
