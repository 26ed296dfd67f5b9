"""The routed experts' gated MLP of the serve step's dropless expert layer in
ONE Pallas TPU kernel (``expert_mlp_fwd``) that walks the experts HIT and
their own rows.

    down[pair] = (act(x[token] W_gate[e]) * (x[token] W_up[e])) W_down[e]

``act`` is a static choice by name (:data:`ACTIVATIONS`): ``"silu"`` (SwiGLU,
every model before SmallThinker) or ``"relu"`` (ReGLU).

The ``jax.numpy`` form (:func:`ray_tpu.ops.moe.moe_layer_dropless` on three
``lax.ragged_dot`` calls) gathers every (token, expert) pair's row into a
sorted ``[T * k, D]`` operand, writes ``gate`` and ``up`` as float32 ``[T *
k, F]`` to HBM between the calls and gathers ``down`` back, whether a pair's
expert is held here or not. The kernel takes the pairs' sorted ORDER and the
held experts' counts and does the rest by index:

- a ROW TILE is up to :data:`ROWS_PER_TILE` rows of one expert, cut from
  that expert's first row (``tile_*``: scalar-prefetch tables made from the
  counts), so a tile never straddles two experts and work follows
  ``sum(counts)``: a grid step past the last tile names the blocks it already
  holds (no copy) and multiplies nothing;
- a tile's rows are copied in by DMA from ``x`` by token index, and its
  ``down`` rows out to ``[T * k, D]`` at their PAIR's index, so the caller
  sums a token's ``k`` pairs in the order the router gave them, beside
  whatever other rows share the step; a pair whose expert is not held, and
  a padding position's, is never written (the caller masks it);
- an expert's ``W_gate``, ``W_up`` and ``W_down`` are read from the stacks
  in place, ``f_tile`` of ``F`` a grid step through the pipeline's double
  buffers, once a row tile (once for all its rows unless they pass 128);
- ``gate`` and ``up`` live in VMEM only: one step computes ``act(x W_gate)
  * (x W_up)`` for the tile in float32, casts it to the weights' type (the
  ``mid`` of the ``jax.numpy`` form) and adds ``mid W_down`` to the tile's
  float32 accumulator.

bfloat16 operands, float32 accumulators, float32 ``down``. Tile sizes come
from ``D``, ``F`` and the dtype alone, so a row's sums run in one order at
every step width and beside any other rows.

A single row of a ``[T, D]`` array is not a DMA the chip's compiler takes
(a slice of the sublane dimension must be whole tiles), so rows travel as
``[8, Dp / 8]`` float32 slabs (one whole tile row each: ``x`` is cast and
reshaped once a call) and are turned to and from ``[rows, D]`` in VMEM with
eight strided copies. ``Dp`` is ``D`` where ``D`` is whole ``[8, 128]`` tiles
(a multiple of 1024). A ``D`` of whole lanes that is not (2560: two and a
half tiles) travels padded to the next whole tile row (:func:`slab_width`:
``[8, 384]`` for 2560) in that same cast-and-reshape; the ``[rows, D]``
operand and the accumulator stay ``D`` wide (every cut falls on a lane
boundary), a ``down`` row's padding is never written and the caller cuts it
off. The WEIGHTS are read as they are stored: nothing of them is padded or
copied for any ``D``.

Pallas is imported where the kernel is traced (``ray_tpu.models`` imports
this module's parent).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import resolve_attention_impl
from ray_tpu.ops.latent_attention import LANES, VMEM_LIMIT
from ray_tpu.ops.ssd_step import SUBLANES

F32 = jnp.float32
#: rows of one expert a grid step multiplies: the MXU's height. Up to here
#: a step's time is the weights' copy; past it a group takes a second tile
#: and its weights a second read
ROWS_PER_TILE = 128
#: bytes of ONE weight tile (``[D, f_tile]``) at most: three matrices, two
#: buffers each, beside the row buffers under :data:`VMEM_LIMIT`
WEIGHT_TILE_BYTES = 4 * 1024 * 1024
#: the gate's activation by name: the kernel and the ``ragged_dot`` form of
#: ``ops/moe.py`` take the same static choice
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def slab_width(d: int) -> int:
    """Lanes of one of the eight slabs a ``D``-wide float32 row travels as:
    ``D / 8`` rounded up to whole lanes (``D`` itself is whole lanes)."""
    return -(-d // (SUBLANES * LANES)) * LANES


def expert_mlp_impl(dtype, d: int, f: int) -> str:
    """``"pallas"`` when the kernel takes experts of ``[D, F]`` in ``dtype``
    on this backend, else ``"jnp"`` (the three ``ragged_dot`` calls). The
    kernel wants bfloat16 weights and ``D`` and ``F`` whole lanes (a row
    travels as one slab of whole ``[8, 128]`` float32 tiles, padded where
    ``D`` is not a multiple of 1024: :func:`slab_width`)."""
    if (resolve_attention_impl() == "pallas"
            and jnp.dtype(dtype) == jnp.bfloat16
            and d % LANES == 0 and f % LANES == 0):
        return "pallas"
    return "jnp"


def impl_for(w_gate) -> str:
    """:func:`expert_mlp_impl` of the experts' gate matrices ``[..., D, F]``
    (an array, or a shape and dtype: the form is asked before a weight is
    made)."""
    return expert_mlp_impl(w_gate.dtype, w_gate.shape[-2], w_gate.shape[-1])


def f_tile(d: int, f: int, itemsize: int = 2) -> int:
    """Columns of ``F`` a grid step takes: the widest whole-lane divisor of
    ``F`` whose ``[D, f_tile]`` tile is within :data:`WEIGHT_TILE_BYTES`."""
    fits = [c for c in range(LANES, f + 1, LANES)
            if f % c == 0 and d * c * itemsize <= WEIGHT_TILE_BYTES]
    return max(fits, default=LANES)


def tile_tables(counts, n_tiles_max: int, rows: int = ROWS_PER_TILE):
    """The row tiles of groups of ``counts [E]`` rows that lie one after
    another: ``(group, first row, rows)`` of each tile ``[n_tiles_max]`` and
    their number. A tile past the last names the last tile's group (the
    kernel's block index then does not move) and has no rows."""
    tiles = (counts + rows - 1) // rows
    ends = jnp.cumsum(tiles)
    n_tiles = ends[-1]
    ids = jnp.arange(n_tiles_max, dtype=jnp.int32)
    group = jnp.searchsorted(ends, jnp.minimum(ids, n_tiles - 1),
                             side="right",
                             method="compare_all").astype(jnp.int32)
    group = jnp.clip(group, 0, counts.shape[0] - 1)
    within = ids - (ends - tiles)[group]
    start = (jnp.cumsum(counts) - counts)[group] + within * rows
    live = ids < n_tiles
    n_rows = jnp.where(live, jnp.minimum(counts[group] - within * rows, rows),
                       0)
    return (group, jnp.where(live, start, 0).astype(jnp.int32),
            n_rows.astype(jnp.int32), n_tiles.astype(jnp.int32))


def _expert_mlp_kernel(order_ref, group_ref, start_ref, rows_ref, meta_ref,
                       x_hbm, wg_ref, wu_ref, wd_ref,        # inputs
                       out_hbm,                              # output
                       xbuf, xb, acc, obuf, sems,
                       *, k: int, n_f: int, multiply: bool, act: str):
    """``meta_ref``: (tiles, the layer's first group). ``x_hbm [T, 8, Dp /
    8]`` float32; ``w*_ref``: this step's tiles of the tile's expert;
    ``out_hbm [T * k, 8, Dp / 8]`` float32. Tile ``i`` starts tile ``i +
    1``'s rows on their way in, and awaits the copies out of tile ``i - 2``
    before it takes their buffer."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i, f = pl.program_id(0), pl.program_id(1)
    n_tiles = meta_ref[0]
    live = i < n_tiles
    slot = i % 2
    slabs, w = xbuf.shape[2:]
    d = xb.shape[1]
    # a slab's columns of the ``D``-wide row (``Dp > D``: the last slabs
    # hold fewer, or none)
    cuts = [(j, j * w, min((j + 1) * w, d)) for j in range(slabs)
            if j * w < d]

    def rows(tile, slot, out, act):
        """``start`` or ``wait`` for every row's copy of ``tile``: in from
        ``x`` by token, or ``out`` to its pair's row."""
        def one(r, carry):
            pair = order_ref[start_ref[tile] + r]
            copy = pltpu.make_async_copy(
                obuf.at[slot, r], out_hbm.at[pair], sems.at[1, slot]) \
                if out else pltpu.make_async_copy(
                    x_hbm.at[pair // k], xbuf.at[slot, r], sems.at[0, slot])
            getattr(copy, act)()
            return carry
        lax.fori_loop(0, rows_ref[tile], one, 0)

    @pl.when(live & (f == 0))
    def _take_rows():
        @pl.when(i == 0)
        def _first():
            rows(0, 0, False, "start")

        rows(i, slot, False, "wait")

        @pl.when(i + 1 < n_tiles)
        def _next():
            rows(i + 1, 1 - slot, False, "start")

        for j, lo, hi in cuts:
            xb[:, lo:hi] = xbuf[slot, :, j, :hi - lo].astype(xb.dtype)
        acc[...] = jnp.zeros(acc.shape, F32)

    @pl.when(live & multiply)
    def _multiply():
        x = xb[...]
        gate = jnp.dot(x, wg_ref[...], preferred_element_type=F32)
        up = jnp.dot(x, wu_ref[...], preferred_element_type=F32)
        mid = (ACTIVATIONS[act](gate) * up).astype(xb.dtype)
        acc[...] += jnp.dot(mid, wd_ref[...], preferred_element_type=F32)

    @pl.when(live & (f == n_f - 1))
    def _give_rows():
        @pl.when(i >= 2)
        def _buffer_free():
            rows(i - 2, slot, True, "wait")

        for j, lo, hi in cuts:
            obuf[slot, :, j, :hi - lo] = acc[:, lo:hi]
        rows(i, slot, True, "start")

    @pl.when((i == pl.num_programs(0) - 1) & (f == n_f - 1))
    def _last_copies():
        for back in (1, 2):
            @pl.when(n_tiles >= back)
            def _await():
                rows(n_tiles - back, (n_tiles - back) % 2, True, "wait")


@functools.partial(jax.jit,
                   static_argnames=("k", "multiply", "interpret", "act"))
def expert_mlp_pairs(x, order, counts, first_group, w_gate, w_up, w_down, *,
                     k: int, multiply: bool = True, interpret: bool = False,
                     act: str = "silu"):
    """``down`` of the pairs routed to the experts of ``counts [E]``: ``x
    [T, D]``; ``order [T * k]`` the pairs sorted by expert (a pair is ``token
    * k + choice``; the first ``sum(counts)`` are read, expert by expert);
    ``w_gate``, ``w_up [G, D, F]``, ``w_down [G, F, D]`` whole stacks of
    which groups ``first_group ..+ E`` are these experts. Returns ``[T * k,
    D]`` float32 with those pairs' rows written at the PAIR's index; every
    other row holds nothing anybody may read. ``act``: the gate's activation
    (:data:`ACTIVATIONS`). ``multiply=False`` leaves the
    arithmetic out and every copy in (``experiments/expert_mlp_bench.py``:
    what the copies alone cost)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, d = x.shape
    f = w_gate.shape[-1]
    w = slab_width(d)
    dp = SUBLANES * w
    tf = f_tile(d, f, w_gate.dtype.itemsize)
    n_f = f // tf
    rows = ROWS_PER_TILE
    n_tiles_max = counts.shape[0] + (t * k) // rows
    group, start, n_rows, n_tiles = tile_tables(counts, n_tiles_max, rows)

    def slabs(x):
        x = x.astype(F32)
        if dp != d:
            x = jnp.pad(x, ((0, 0), (0, dp - d)))
        return x.reshape(t, SUBLANES, w)

    def weight(gate_or_up):
        def index(i, j, order, group, start, n_rows, meta):
            # past the last tile: the block the last step left here
            j = jnp.where(i < meta[0], j, n_f - 1)
            return ((meta[1] + group[i], 0, j) if gate_or_up
                    else (meta[1] + group[i], j, 0))
        return pl.BlockSpec((None, d, tf) if gate_or_up else (None, tf, d),
                            index)

    out = pl.pallas_call(
        functools.partial(_expert_mlp_kernel, k=k, n_f=n_f,
                          multiply=multiply, act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n_tiles_max, n_f),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      weight(True), weight(True), weight(False)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((2, rows, SUBLANES, w), F32),
                pltpu.VMEM((rows, d), w_gate.dtype),
                pltpu.VMEM((rows, d), F32),
                pltpu.VMEM((2, rows, SUBLANES, w), F32),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((t * k, SUBLANES, w), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        name="expert_mlp_fwd",
        interpret=interpret,
    )(order.astype(jnp.int32), group, start, n_rows,
      jnp.stack([n_tiles, jnp.asarray(first_group, jnp.int32)]),
      slabs(x), w_gate, w_up, w_down)
    out = out.reshape(t * k, dp)
    return out if dp == d else out[:, :d]
