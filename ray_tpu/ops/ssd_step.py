"""ONE turn of Mamba-2's recurrence for the serve step's rows that feed one
position, over the state pool AS IT LIES, in a Pallas TPU kernel
(``ssd_step_fwd``).

    S'   = exp(D_t A) S + (D_t x_t) (x) B_t                  [P, N] a head
    y_t  = S' C_t                                            (+ D x_t outside)

The ``jax.numpy`` form (:func:`ray_tpu.ops.ssm.ssd_step` over a layer's share
of the pool) passes over EVERY slot of the layer, live or not, and over the
new states a second time for the read-out. The kernel walks the LIVE single
rows only, through an index the caller compacts (``live``, their count, the
layer's first pool row, the rows' ``fresh`` flags: scalar-prefetch operands),
and addresses pool row ``first + live[j]`` in the WHOLE pool ``[rows, H, P,
N]``: no layer's share is sliced out and a slot that is idle or prefilling
moves no byte. A row's state crosses VMEM once, :data:`HEADS_PER_TILE` heads
a copy (a head is ``[P, N]`` float32, ``N`` on the lanes; the copies in and
out run one tile ahead of and behind the arithmetic), and in that one visit
the tile is decayed, fed, written back to the SAME pool row
(``input_output_aliases``: rows the kernel does not visit keep their bytes
because they are the same buffer) and read out (``y = S' C`` reduced over the
lanes before the tile leaves). A step with no single row starts no copy.

Everything is float32: state, decay, products, the read-out's sum (the state
is carried over hundreds of tokens and read by every later one). What a row
gets depends on its own operands alone, never on which rows share the step.

A head's ``x`` and ``y`` lie along the LANES outside (``[H, P]``) and along
the SUBLANES beside its state (``[P, N]``): the kernel turns a tile's heads
with one 128 x 128 transpose each way, a head then being a lane of the
turned tile.

Pallas is imported where the kernel is traced (``ray_tpu.models`` imports
this module's parent).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import _pallas_interpret, resolve_attention_impl
from ray_tpu.ops.latent_attention import LANES, VMEM_LIMIT

F32 = jnp.float32
#: float32 rows of a vector register
SUBLANES = 8
#: heads a copy (and a turn of the kernel's loop) carries, at most: 8 heads
#: of 128 x 256 float32 are 1 MB, 4 MB with both directions double-buffered.
#: The kernel is bound by its copies: 4, 8 and 16 heads a tile, and two to
#: eight buffers a direction, all read 580-590 GB/s of the live bytes on a
#: v5e, and so does the loop with its arithmetic taken out
HEADS_PER_TILE = 8


def ssd_step_impl(pool_dtype, head_dim: int, states: int) -> str:
    """``"pallas"`` when the kernel takes this state pool on this backend,
    else ``"xla"`` (:func:`ray_tpu.ops.ssm.ssd_step` over the layer's
    slots). The kernel wants a float32 pool whose ``states`` are whole
    lanes and whose ``head_dim`` is whole sublanes (and at most one
    transpose wide), so that a head lands in VMEM as it lies in HBM."""
    if (resolve_attention_impl() == "pallas"
            and jnp.dtype(pool_dtype) == F32 and states % LANES == 0
            and head_dim % SUBLANES == 0 and head_dim <= LANES):
        return "pallas"
    return "xla"


def impl_for(pool) -> str:
    """:func:`ssd_step_impl` of a state pool ``[..., H, P, N]``: the layers'
    flattened as the op takes it, or the cache's stack of them."""
    return ssd_step_impl(pool.dtype, pool.shape[-2], pool.shape[-1])


def _ssd_step_kernel(live_ref, fresh_ref, meta_ref,          # scalar prefetch
                     dec_ref, dx_ref, b_ref, c_ref, pool_in,  # inputs
                     pool_out, y_ref,                         # outputs
                     sbuf, obuf, sems, turn_ref,
                     *, tiles: int, hpt: int, groups: int):
    """``meta_ref``: (live single rows, the layer's first pool row).
    ``dec_ref [B * H]`` (SMEM): a head's decay; ``dx_ref [B * H, P]``:
    ``delta x``; ``b_ref``, ``c_ref [B * G, N]``; ``pool_in`` / ``pool_out
    [rows, H, P, N]``: ONE buffer in HBM. A turn of the loop is one tile
    (``hpt`` heads of one group) of one live row: turn ``s`` starts turn ``s
    + 1``'s copy in, awaits its own, computes into ``obuf`` and starts its
    copy out, whose buffer turn ``s + 2`` awaits before it writes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_live, first = meta_ref[0], meta_ref[1]
    total = n_live * tiles
    p = dx_ref.shape[1]

    def copy_in(s, slot):
        row = first + live_ref[s // tiles]
        return pltpu.make_async_copy(
            pool_in.at[row, pl.ds((s % tiles) * hpt, hpt)], sbuf.at[slot],
            sems.at[0, slot])

    def copy_out(s, slot):
        row = first + live_ref[s // tiles]
        return pltpu.make_async_copy(
            obuf.at[slot], pool_out.at[row, pl.ds((s % tiles) * hpt, hpt)],
            sems.at[1, slot])

    @pl.when(total > 0)
    def _first_copy():
        copy_in(0, 0).start()

    lane = lax.broadcasted_iota(jnp.int32, (p, LANES), 1)

    def turn(s, carry):
        slot = s % 2
        r, t = live_ref[s // tiles], s % tiles

        @pl.when(s + 1 < total)
        def _next_copy():
            copy_in(s + 1, 1 - slot).start()

        copy_in(s, slot).wait()

        @pl.when(fresh_ref[r] != 0)
        def _from_zero():
            # a one-token prompt: whatever the slot held is not the row's
            sbuf[slot] = jnp.zeros(sbuf.shape[1:], F32)

        @pl.when(s >= 2)
        def _buffer_free():
            copy_out(s - 2, slot).wait()

        at = pl.multiple_of((r * tiles + t) * hpt, hpt)
        # the tile's heads' ``delta x`` along the sublanes: head i in lane i
        turn_ref[0:hpt, 0:p] = dx_ref[pl.ds(at, hpt), :]
        xt = turn_ref[...].T
        g = r * groups + t * groups // tiles
        b_row, c_row = b_ref[pl.ds(g, 1), :], c_ref[pl.ds(g, 1), :]
        yt = jnp.zeros((p, LANES), F32)
        for i in range(hpt):
            new = dec_ref[at + i] * sbuf[slot, i] + xt[0:p, i:i + 1] * b_row
            obuf[slot, i] = new
            yt = jnp.where(lane == i,
                           jnp.sum(new * c_row, axis=-1, keepdims=True), yt)
        copy_out(s, slot).start()
        # and the read-outs back along the lanes
        turn_ref[0:p, :] = yt
        y_ref[pl.ds(at, hpt), :] = turn_ref[...].T[0:hpt, 0:p]
        return carry

    lax.fori_loop(0, total, turn, 0)

    for back in (1, 2):
        @pl.when(total >= back)
        def _last_copies():
            copy_out(total - back, (total - back) % 2).wait()


@functools.partial(jax.jit, static_argnames=("heads_per_tile", "interpret"))
def ssd_step_rows(pool, first, live, n_live, fresh, decay, dx, bm, cm, *,
                  heads_per_tile: int = HEADS_PER_TILE,
                  interpret: bool = False):
    """One turn of the recurrence for rows ``live[:n_live]`` of a layer
    whose states are ``pool [rows, H, P, N]`` (float32) from row ``first``:
    ``live [B]`` int32 (entries past ``n_live`` are not read), ``fresh [B]``
    (a row that starts from zero whatever its slot held), ``decay [B, H]`` =
    ``exp(delta a)``, ``dx [B, H, P]`` = ``delta x``, ``bm``, ``cm [B, G,
    N]``, all float32 and indexed by ROW (slot of the layer). Returns
    ``(pool, y [B, H, P])``: the pool with those rows' states advanced in
    place, ``y = S' C`` for them; ``y`` of every other row is not written
    and holds nothing anybody may read."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, p = dx.shape
    g, n = bm.shape[1:]
    # a tile's heads share one group's B and C
    hpt = math.gcd(h // g, heads_per_tile)
    kernel = functools.partial(_ssd_step_kernel, tiles=h // hpt, hpt=hpt,
                               groups=g)
    whole = lambda shape: pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))
    new_pool, y = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                whole((b * h, p)),
                whole((b * g, n)),
                whole((b * g, n)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                whole((b * h, p)),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, hpt, p, n), F32),
                pltpu.VMEM((2, hpt, p, n), F32),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((LANES, LANES), F32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((b * h, p), F32)],
        # operand 7 (after the three scalar operands): the pool
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        name="ssd_step_fwd",
        interpret=interpret,
    )(live.astype(jnp.int32), fresh.astype(jnp.int32),
      jnp.stack([n_live, first]).astype(jnp.int32),
      decay.reshape(b * h), dx.reshape(b * h, p), bm.reshape(b * g, n),
      cm.reshape(b * g, n), pool)
    return new_pool, y.reshape(b, h, p)


def ssd_step_live(pool, first, single, fresh, x, bm, cm, delta, a, *,
                  heads_per_tile: int = HEADS_PER_TILE):
    """The kernel for the rows of ``single [B]`` (those that feed one
    position) with the operands :func:`ray_tpu.ops.ssm.ssd_step` takes:
    ``x [B, G, K, P]``, ``bm``, ``cm [B, G, N]``, ``delta [B, G, K]``, ``a
    [G, K]``. -> ``(pool, y [B, G, K, P])`` without the skip term."""
    b, g, k, p = x.shape
    # the live rows' slots, compacted to the front as the block rows' are
    live = jnp.argsort(~single, stable=True)
    new_pool, y = ssd_step_rows(
        pool, first, live, jnp.sum(single), fresh,
        jnp.exp(delta * a).reshape(b, g * k),
        (delta[..., None] * x).reshape(b, g * k, p), bm, cm,
        heads_per_tile=heads_per_tile, interpret=_pallas_interpret())
    return new_pool, y.reshape(b, g, k, p)
