"""Latent attention (MLA, DeepSeek-V2/V3) over the paged LATENT pool, in its
absorbed form, and the YaRN-scaled RoPE tables its rotated key takes.

What a token caches a layer is ONE vector ``[c_kv, k_r]``: the normed latent
(``rank`` values) and the rotated key (``rope`` values), read by every query
head. Unabsorbed, head ``h`` scores ``q_nope_h . (c_kv W_uk_h) + q_rope_h .
k_r`` and sums ``softmax * (c_kv W_uv_h)``. ``W_uk`` folds into the query and
``W_uv`` into the output (equal in exact arithmetic)::

    q'_h    = [q_nope_h W_uk_h^T, q_rope_h]          rank + rope values
    score_h = q'_h . [c_kv, k_r]
    u_h     = sum softmax * c_kv                     rank values
    o_h     = u_h W_uv_h

so attention is multi-query attention with ONE key of ``rank + rope`` whose
first ``rank`` values are its value too: a decoding row reads each live
token's vector once for all heads. :func:`paged_latent_attention` takes the
absorbed queries and returns ``u``; the caller holds both folds.

**Which form the chunk rows take.** A chunk of ``C`` queries over ``N`` cached
tokens, 64 heads, rank 512, rope 64, heads of 128: absorbed it is ``C * 64 *
N * (576 + 512) * 2`` FLOPs (17.8 MFLOP x N at ``C`` = 128); up-projecting
the cache first is ``N * 512 * 64 * 256 * 2`` (16.8 MFLOP x N) plus ``C * 64
* N * (192 + 128) * 2`` of scores and values (5.2 MFLOP x N at 128): 22.0
against 17.8 at a chunk of 128, 27.3 against 35.6 at 256. The serve step's
chunks are 128 wide or narrower, so every row takes the absorbed form, and
a request's first token comes out of the same arithmetic whether its prompt
arrived as chunks or all but one block of it as a prefix hit.

**The pool is as wide as whole lanes.** A row of ``rank + rope`` = 576
bf16 values is four and a half of the TPU's 128 lanes; for a pool declared
576 wide its compiler keeps the BLOCK axis innermost in HBM and relays all of
it around every step (3 GB in and 3 GB out at the benchmark's size, read off
the compile for a described v5e). So the pool's last axis is
:func:`pool_width` (640 there), which is what a row-major row of 576 takes in
tiled HBM anyway; the lanes past ``rank + rope`` hold zeros and the queries
are zero there.

**Two forms of one algorithm**, chosen from what the code can observe
(:func:`latent_attention_impl`: backend, pool dtype, lane width, block size),
never from a model's name or a setting:

- a Pallas TPU kernel (``latent_attention_fwd``) that reads the pool THROUGH
  the block table, as :mod:`ray_tpu.ops.paged_attention` does for heads of
  128: a row copies its LIVE pages (``ceil((pos + nvalid) / bs)`` of them,
  none past) from HBM into VMEM, :data:`KEYS_PER_STEP` keys a step; one copy
  of a page serves both matmuls (the value is the key's first ``rank``
  lanes); bf16 operands, float32 accumulation, the online softmax's running
  max and sum in float32 across key steps, so no score reaches HBM. A tile
  of queries puts (queries x heads) on the matmul's row axis: a token row
  reads each live latent once for all heads, a chunk row once a tile of
  :data:`ROWS_PER_TILE` rows (compute-bound by a factor of several). A row
  that feeds nothing copies nothing, a tile past ``nvalid`` is not computed;
- the ``jax.numpy`` form: the same mathematics over each live row's
  gathered table, a row at a time. It runs wherever the kernel does not
  (CPU, float32 pools, pools or blocks Mosaic does not tile) and is the
  reference the kernel is compared with.

In both, every row's LAST real query, the one whose logits are sampled, is
attended by a pass of its own whose arithmetic does not depend on what else
the row holds (``_one_query``; in the kernel the one-query tile, with the key
steps counted from the table's first block): a request served cold (chunks)
and warm (a prefix hit, then a short row) agrees with itself bit for bit.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import (NEG_INF, _pallas_interpret,
                                   resolve_attention_impl)

#: query heads a chunk row of the ``jax.numpy`` form scores at once: 8 x 128
#: queries x 43k keys of float32 scores is 0.18 GB
HEADS_PER_STEP = 8
#: the TPU's lanes: the pool's last axis is a multiple of them
LANES = 128
#: keys one inner step of the kernel copies and multiplies (pages x bs): on a
#: v5e twelve token rows over 33k-41k tokens read 489 GB/s at 512 and 565 at
#: 1024 (a plain pass reads 602), a chunk row 3 % faster
KEYS_PER_STEP = 1024
#: (queries x heads) rows of one tile of the kernel: its float32 scores over
#: a step's keys are 4 MB in VMEM, its float32 sums 2 MB; 2048 rows gain a
#: whole chunk nothing and cost a narrow one a wider last tile
ROWS_PER_TILE = 1024
#: rows of a bf16 sublane tile: a page and a query's heads are whole tiles
SUBLANES = 16
#: what the kernel may take of VMEM (v5e has 128 MiB; the default scope is
#: 16 MiB, which the query tile, its sums and its scores pass)
VMEM_LIMIT = 64 * 1024 * 1024


def pool_width(width: int) -> int:
    """The last axis of a pool that holds ``width`` values a token."""
    return -(-width // LANES) * LANES


def yarn_inv_freq(dim: int, theta: float, factor: float, beta_fast: float,
                  beta_slow: float, original_len: int):
    """Inverse frequencies ``[dim / 2]`` of YaRN-scaled RoPE (Peng et al.
    2023, as DeepSeek-V3 applies it): frequency ``k`` keeps its unscaled
    value ``theta^(-2k/dim)`` where it turns more than ``beta_fast`` times
    over ``original_len`` positions, takes that over ``factor`` where it
    turns fewer than ``beta_slow`` times, and a linear ramp over the index
    in between. ``factor`` 1 is plain RoPE."""
    k = jnp.arange(0, dim, 2, dtype=jnp.float32)
    plain = theta ** (-k / dim)
    if factor <= 1.0:
        return plain

    def index_of(turns: float) -> float:
        """The (fractional) frequency index that turns ``turns`` times."""
        return dim * math.log(original_len / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(index_of(beta_fast)), 0)
    high = min(math.ceil(index_of(beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return plain * (1.0 - ramp) + plain / factor * ramp


def yarn_rotary(positions, dim: int, *, theta: float, factor: float,
                beta_fast: float, beta_slow: float, original_len: int,
                mscale: float = 1.0, mscale_all_dim: float = 0.0):
    """cos/sin tables ``[..., L, dim / 2]`` for
    :func:`ray_tpu.ops.layers.apply_rotary` (pairs ``(k, k + dim / 2)``)."""
    inv = yarn_inv_freq(dim, theta, factor, beta_fast, beta_slow,
                        original_len)
    angles = positions.astype(jnp.float32)[..., None] * inv
    form = lambda m: 0.1 * m * math.log(factor) + 1.0 \
        if factor > 1.0 and m else 1.0
    amp = form(mscale) / form(mscale_all_dim)
    return jnp.cos(angles) * amp, jnp.sin(angles) * amp


def _one_query(q0, ctx, pos, rank: int, scale: float):
    """One query (``q0 [H, W]`` at position ``pos``) over a row's gathered
    vectors ``ctx [K, W]``: they are read once for all heads."""
    s = jnp.einsum("hd,kd->hk", q0, ctx,
                   preferred_element_type=jnp.float32) * scale
    seen = jnp.arange(ctx.shape[0]) <= pos
    p = jax.nn.softmax(jnp.where(seen[None], s, NEG_INF), axis=-1)
    return jnp.einsum("hk,kr->hr", p.astype(ctx.dtype), ctx[:, :rank],
                      preferred_element_type=jnp.float32)


def _chunk(q, ctx, pos, rank: int, scale: float):
    """A chunk of queries (``q [C, H, W]`` at positions ``pos + c``) over a
    row's gathered vectors, ``HEADS_PER_STEP`` heads at a time."""
    c, h, w = q.shape
    seen = jnp.arange(ctx.shape[0])[None, :] <= (pos + jnp.arange(c))[:, None]
    g = math.gcd(h, HEADS_PER_STEP)

    def heads(qg):                                   # [C, g, W]
        s = jnp.einsum("cgd,kd->cgk", qg, ctx,
                       preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(seen[:, None], s, NEG_INF), axis=-1)
        return jnp.einsum("cgk,kr->cgr", p.astype(ctx.dtype), ctx[:, :rank],
                          preferred_element_type=jnp.float32)

    qg = q.reshape(c, h // g, g, w).transpose(1, 0, 2, 3)
    return lax.map(heads, qg).transpose(1, 0, 2, 3).reshape(c, h, rank)


def latent_attention_impl(pool_dtype, width: int, block_size: int,
                          rank: int) -> str:
    """``"pallas"`` when the kernel takes this pool on this backend, else
    ``"xla"`` (the ``jax.numpy`` form). The kernel wants a bf16 pool whose
    last axis is whole lanes (:func:`pool_width`), of which the latent is
    whole lanes too, and whose blocks are whole sublane tiles, so that a
    page lands in VMEM as it lies in HBM."""
    if (resolve_attention_impl() == "pallas"
            and jnp.dtype(pool_dtype) == jnp.bfloat16
            and width % LANES == 0 and rank % LANES == 0
            and block_size % SUBLANES == 0):
        return "pallas"
    return "xla"


def impl_for(pool, rank: int) -> str:
    """:func:`latent_attention_impl` of a pool ``[..., bs, width]``: one
    layer's as the op takes it, or the cache's stack of them."""
    return latent_attention_impl(pool.dtype, pool.shape[-1], pool.shape[-2],
                                 rank)


def paged_latent_attention(q, pool, block_tables, pos, nvalid, *, rank: int,
                           scale: float):
    """Absorbed latent attention of ``q[B, C, H, rank + rope]`` (``W_uk``
    folded into its first ``rank`` values, the rotated query its last; or as
    wide as the pool, zero past them) over the latent pool ``pool[n_blocks,
    bs, pool_width(rank + rope)]`` through ``block_tables[B, M]``: query
    ``c`` of row ``b`` sits at position ``pos[b] + c`` and sees the tokens
    at positions up to its own; the caller has written the chunk's own
    vectors. Row ``b`` holds ``nvalid[b]`` real queries; queries past them
    return values nobody may read, and a row that holds none reads nothing.
    Returns ``u[B, C, H, rank]`` in ``q``'s dtype: per head the
    softmax-weighted sum of the latents, to be multiplied by ``W_uv``.

    Every row's LAST real query, the one whose logits are sampled, goes
    through the one-query form, in a chunk row too (as
    :func:`ray_tpu.ops.sparse_attention.paged_sparse_attention` has it):
    served twice, cold and warm, the engine agrees with itself."""
    w = pool.shape[-1]
    impl = impl_for(pool, rank)
    with jax.named_scope("mla_attention"):
        if q.shape[-1] < w:
            q = jnp.pad(q, ((0, 0),) * 3 + ((0, w - q.shape[-1]),))
        if impl == "pallas":
            return _latent_attention_pallas(
                q, pool, block_tables, pos, nvalid, rank=rank,
                scale=float(scale), keys=KEYS_PER_STEP,
                tile_rows=ROWS_PER_TILE, interpret=_pallas_interpret())
        return _latent_attention_xla(q, pool, block_tables, pos, nvalid,
                                     rank, scale)


def _latent_attention_xla(q, pool, block_tables, pos, nvalid, rank, scale):
    b, c, h, w = q.shape
    dtype, q = q.dtype, q.astype(pool.dtype)

    def row(args):
        qb, table, pb, n = args
        last = jnp.clip(n - 1, 0, c - 1)
        gathered = lambda: pool[table].reshape(-1, w)
        nothing = jnp.zeros((c, h, rank), jnp.float32)

        def single():
            return nothing.at[last].set(_one_query(
                qb[last], gathered(), pb + last, rank, scale))

        def chunk():
            ctx = gathered()
            return _chunk(qb, ctx, pb, rank, scale).at[last].set(
                _one_query(qb[last], ctx, pb + last, rank, scale))

        forms = [lambda: nothing, single] + ([chunk] if c > 1 else [])
        return lax.switch(jnp.minimum(n, len(forms) - 1), forms)

    return lax.map(row, (q, block_tables, pos, nvalid)).astype(dtype)


# ---------------------------------------------------------------------------
# the Pallas kernel
# ---------------------------------------------------------------------------

def _latent_kernel(tbl_ref, pos_ref, nv_ref,              # scalar prefetch
                   q_ref, q_last_ref, pool_hbm,           # inputs
                   o_ref,                                 # output
                   kbuf, sems, m_ref, l_ref, acc_ref,
                   *, pages: int, tbl_width: int, heads: int, rank: int,
                   scale: float):
    """Grid step ``(b, j)``: tile ``j`` of row ``b``'s queries, ``tile``
    positions x ``heads`` on the row axis (row ``r`` is query ``r // heads``
    of the tile). The row's last real query is attended again, alone, in
    the grid step of the tile that holds it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, j = pl.program_id(0), pl.program_id(1)
    keys = kbuf.shape[1]
    bs = keys // pages
    rows = q_ref.shape[1]
    tile = rows // heads
    pos, nv = pos_ref[b], nv_ref[b]
    last_q = jnp.maximum(nv - 1, 0)

    @pl.when((b == 0) & (j == 0))
    def _clean():
        # a step's dead pages are not copied: what the buffer holds there
        # is multiplied by a weight of 0, so it has to be a number
        kbuf[...] = jnp.zeros_like(kbuf)

    def copy(page, slot, p):
        return pltpu.make_async_copy(
            pool_hbm.at[page],
            kbuf.at[slot, pl.ds(pl.multiple_of(p * bs, bs), bs)],
            sems.at[slot])

    def attend(load_q, n_rows, first_pos, n_keys, store):
        """Queries ``load_q() [n_rows, W]``, row ``r`` at position
        ``first_pos + r // heads``, over the row's keys ``[0, n_keys)``;
        ``store(u [n_rows, rank])``. Key steps count from the table's first
        block, whole steps the tile's first query sees in full unmasked."""
        live_pages = (n_keys + bs - 1) // bs
        last = (n_keys + keys - 1) // keys
        full = jnp.minimum((first_pos + 1) // keys, last)

        def step_pages(step):
            return jnp.minimum(live_pages - step * pages, pages)

        def page(step, p):
            return tbl_ref[b * tbl_width + step * pages + p]

        def start_copies(step, slot):
            def one(p, carry):
                copy(page(step, p), slot, p).start()
                return carry
            lax.fori_loop(0, step_pages(step), one, 0)

        def wait_copies(step, slot):
            def one(p, carry):
                copy(0, slot, p).wait()     # a wait needs only the size
                return carry
            lax.fori_loop(0, step_pages(step), one, 0)

        m_ref[0:n_rows] = jnp.full((n_rows, LANES), NEG_INF, jnp.float32)
        l_ref[0:n_rows] = jnp.zeros((n_rows, LANES), jnp.float32)
        acc_ref[0:n_rows] = jnp.zeros((n_rows, rank), jnp.float32)
        start_copies(0, 0)

        def accumulate(step, slot, masked):
            """The step's keys, copied to ``slot``, into the running max,
            sum and weighted sum."""
            s = lax.dot_general(
                load_q(), kbuf[slot], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if masked:
                qpos = first_pos + lax.broadcasted_iota(
                    jnp.int32, (n_rows, 1), 0) // heads
                kpos = step * keys + lax.broadcasted_iota(
                    jnp.int32, (1, keys), 1)
                # every query sees key 0, so its running max is a number
                # from the first step on and exp() of a masked score is 0
                s = jnp.where(kpos <= qpos, s, NEG_INF)
            m_prev = m_ref[0:n_rows, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = l_ref[0:n_rows, 0:1] * alpha \
                + jnp.sum(p, axis=-1, keepdims=True)
            # the value is the key's first ``rank`` lanes
            acc_ref[0:n_rows] = acc_ref[0:n_rows] * alpha + lax.dot_general(
                p.astype(kbuf.dtype), kbuf[slot, :, 0:rank],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[0:n_rows] = jnp.broadcast_to(m_new, (n_rows, LANES))
            l_ref[0:n_rows] = jnp.broadcast_to(l_new, (n_rows, LANES))

        def whole_step(step, carry):
            """A step every query sees in full, before another such one:
            all its pages and the next step's are live, so the copies are
            started and awaited in the multiplications' own block, where
            the scalar work of 2 x ``pages`` descriptors hides under them
            (a token row's step 2.05 -> 1.33 us a 512 keys on a v5e)."""
            slot = step % 2
            for p in range(pages):
                copy(page(step + 1, p), 1 - slot, p).start()
            for p in range(pages):
                copy(0, slot, p).wait()
            accumulate(step, slot, False)
            return carry

        def any_step(step, carry):
            slot = step % 2

            @pl.when(step + 1 < last)
            def _next_copy():
                start_copies(step + 1, 1 - slot)

            wait_copies(step, slot)
            accumulate(step, slot, True)
            return carry

        # (a mask over a step seen in full changes no bit of it)
        whole = jnp.maximum(full - 1, 0)
        lax.fori_loop(0, whole, whole_step, 0)
        lax.fori_loop(whole, last, any_step, 0)
        store((acc_ref[0:n_rows] / l_ref[0:n_rows, 0:1]).astype(o_ref.dtype))

    @pl.when((j == 0) & (nv <= 1))
    def _nothing():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

    @pl.when((nv > 1) & (j * tile < nv))
    def _tile():
        def store(u):
            o_ref[0] = u
        attend(lambda: q_ref[0], rows, pos + j * tile,
               pos + jnp.minimum((j + 1) * tile, nv), store)

    @pl.when((nv > 0) & (j == last_q // tile))
    def _last_query():
        at = pl.multiple_of((last_q % tile) * heads, heads)

        def store(u):
            o_ref[0, pl.ds(at, heads), :] = u
        attend(lambda: q_last_ref[0], heads, pos + last_q, pos + nv, store)


@functools.partial(jax.jit, static_argnames=("rank", "scale", "keys",
                                             "tile_rows", "interpret"))
def _latent_attention_pallas(q, pool, block_tables, pos, nvalid, *,
                             rank: int, scale: float, keys: int,
                             tile_rows: int, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, c, h, w = q.shape
    bs = pool.shape[1]
    m = block_tables.shape[1]
    pages = min(max(1, keys // bs), m)
    if m % pages:
        # entries past the row's live range are never reached: any valid
        # id does
        block_tables = jnp.pad(block_tables, ((0, 0), (0, -m % pages)))
        m = block_tables.shape[1]
    # a query's heads are whole sublane tiles of the row axis
    heads = -(-h // SUBLANES) * SUBLANES
    qp = q.astype(pool.dtype)
    if heads != h:
        qp = jnp.pad(qp, ((0, 0), (0, 0), (0, heads - h), (0, 0)))
    # the widest tile of whole queries under ``tile_rows`` that divides C
    tile = max(t for t in range(1, c + 1)
               if c % t == 0 and t * heads <= max(tile_rows, heads))
    qp = qp.reshape(b, c * heads, w)
    # tiles past a row's last real query are neither fetched nor written:
    # their block index stays on the last live tile's
    live_tile = lambda b_, j_, nv: jnp.minimum(
        j_, jnp.maximum(nv[b_] - 1, 0) // tile)
    kernel = functools.partial(
        _latent_kernel, pages=pages, tbl_width=m, heads=heads, rank=rank,
        scale=scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, c // tile),
            in_specs=[
                pl.BlockSpec((1, tile * heads, w),
                             lambda b_, j_, tbl, pos_, nv:
                             (b_, live_tile(b_, j_, nv), 0)),
                pl.BlockSpec((1, heads, w),
                             lambda b_, j_, tbl, pos_, nv:
                             (b_, jnp.maximum(nv[b_] - 1, 0), 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, tile * heads, rank),
                                   lambda b_, j_, tbl, pos_, nv:
                                   (b_, live_tile(b_, j_, nv), 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pages * bs, w), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((tile * heads, LANES), jnp.float32),
                pltpu.VMEM((tile * heads, LANES), jnp.float32),
                pltpu.VMEM((tile * heads, rank), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, c * heads, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        name="latent_attention_fwd",
        interpret=interpret,
    )(block_tables.reshape(-1).astype(jnp.int32), pos.astype(jnp.int32),
      nvalid.astype(jnp.int32), qp, qp, pool)
    return out.reshape(b, c, heads, rank)[:, :, :h]
