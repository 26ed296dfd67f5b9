"""Paged attention: the serve step's attention, read THROUGH the block table.

One function, :func:`paged_attention`, with two forms of one algorithm:

- a Pallas TPU kernel (``paged_attention_fwd``) that walks each row's block
  table and does for a row what the row FEEDS, read from ``nvalid`` by scalar
  prefetch (a grid step is a row):

  - **one token**: the ``rep`` query heads of a KV head are one sublane tile
    of their own (from a small array cut from the rows' first queries), so
    the scores of a key step are ``[16, keys]`` a KV head, not
    ``[prefill_chunk x rep, keys]``; the output goes to an array as small;
  - **a chunk**: the whole ``prefill_chunk x rep`` tile, its queries placed
    in the kernel from the model's own ``[C, H, hd]`` block (one transpose
    in VMEM to head-major) and its output written as the model wants it;
    the chunk blocks of ``q`` and ``o`` stay on the block of a row that
    feeds a chunk, so no other row moves them;
  - **nothing**: nothing is copied, multiplied or initialised (the
    launcher's ``where`` hands such a row zeros).

  Either way the row copies its LIVE pages (window start to
  ``ceil((pos + nvalid) / bs)``, nothing past it) from the HBM pool into
  double-buffered VMEM, :func:`pages_per_step` pages a key step counted from
  the row's own first live page (so a row's result does not depend on which
  rows share the call), one key step AHEAD of the multiplications and across
  rows: a row's last step starts the first step of the next row that feeds
  anything. The KV heads stay grouped (all ``rep`` query heads of a group
  share one read of the page), bf16 operands, float32 accumulation, and the
  online softmax's running max / sum in float32: the loop
  :mod:`ray_tpu.ops.flash_pallas` has, over pages instead of a contiguous
  sequence;
- the grouped ``jax.numpy`` form: the same mathematics over the gathered
  table, in the pool's stored type, with no ``repeat_kv`` and no float32
  copy of keys or values. It runs wherever the kernel does not (CPU, float32
  pools, head sizes Mosaic does not tile) and is the reference the kernel is
  compared with.

Which one runs is decided from what the code can observe — backend, pool
dtype, shapes (:func:`paged_attention_impl`) — never from a model's name or
a user's setting.

Pool layout: ``[n_blocks, bs, kvh, hd]`` (a token's KV heads contiguous).
In VMEM two KV heads of a bf16 page share each 32-bit sublane word, so the
kernel reads head PAIRS with one strided 32-bit load and splits the halves.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import (NEG_INF, _pallas_interpret,
                                   _softcap_scores, resolve_attention_impl)

# Pallas is imported where the kernel is traced, not here: the import is a
# second of pure Python that only a process about to run the kernel owes
# (``models`` imports this module on every path, the CPU's included)
LANES = 128          # running max / sum stored broadcast over one lane tile
#: bytes of K (and as many of V) one key step copies and multiplies, whatever
#: the page's width: 1024 keys of 4 KV heads of 128, 512 of 8, 128 of 32, the
#: four page buffers 4 MiB. The ONE rule for the step (:func:`pages_per_step`).
#: On a v5e (my chip runs, PR 51, ``experiments/paged_attention_bench.py``,
#: live GB/s of token rows | chunk rows | a cell's mix): 4 KV heads (16 KB a
#: page) 277 | 155 | 243 at 512 keys and 334 | 205 | 309 at 1024; 8 KV heads
#: (32 KB) 540 | 150 | 341 at 512 and 629 | 223 | 390 at 1024, which is 2 MiB
#: a buffer and takes the call of a 64 x 6 tile to 16.7 MB of VMEM, past the
#: default scoped limit, for the chunk tile's sake (its reductions cost a key
#: step the same whatever its keys): left with the chunk tile. 32 KV heads
#: stay at 4 MiB of buffers inside the default limit (``ROADMAP.md`` D16)
STEP_BYTES = 1 << 20
#: a score tile of at most this many elements leaves the units waiting on one
#: head's chain, so the heads' loop unrolls and the chains interleave (every
#: token row's; a 64 x 128 chunk tile of a 32-head pool 286 GB/s unrolled, 167
#: as a loop); a larger one has the work inside it and stays a loop, which
#: lowers once (a 224 x 1024 tile 212 against 193-205)
UNROLL_TILE = 1 << 16


def _heads_tile(kv_heads: int) -> bool:
    """The pool's head axis, read as 32-bit pairs, fills whole sublane
    tiles (1, 2 or 4 pairs a token, or a multiple of 8)."""
    pairs = kv_heads // 2
    return kv_heads % 2 == 0 and (pairs in (1, 2, 4) or pairs % 8 == 0)


def pool_heads(kv_heads: int) -> int:
    """The head axis of a pool that holds ``kv_heads`` KV heads a token: the
    count itself where the kernel tiles it, else the next multiple of 16 (30
    heads lie in a pool of 32, 6.7 % more bytes; 15 pairs a token are not
    whole sublane tiles). The heads past ``kv_heads`` stay zero, and a layout
    that pads its pool pads its queries with heads nobody reads
    (:mod:`ray_tpu.models.linear_hybrid`). The same on every backend: the
    pool has one layout, whichever form reads it."""
    return kv_heads if _heads_tile(kv_heads) else -(-kv_heads // 16) * 16


def paged_attention_impl(pool_dtype, head_dim: int, kv_heads: int) -> str:
    """``"pallas"`` when the kernel takes this pool on this backend, else
    ``"xla"`` (the grouped ``jax.numpy`` form). The kernel wants a bf16 pool
    (two heads a 32-bit word), ``hd`` a multiple of the 128 lanes and a head
    count whose 32-bit pairs fill whole sublane tiles; ``kv_heads`` is the
    POOL's head axis, so a model whose own count does not tile (30 MHA
    heads, 15 pairs) reaches the kernel through a pool of
    :func:`pool_heads` heads."""
    if (resolve_attention_impl() == "pallas"
            and jnp.dtype(pool_dtype) == jnp.bfloat16
            and head_dim % LANES == 0 and _heads_tile(kv_heads)):
        return "pallas"
    return "xla"


def impl_for(k_pool) -> str:
    """:func:`paged_attention_impl` of a pool ``[..., kvh, hd]``: one layer's
    as the op takes it, or the cache's stack of them."""
    return paged_attention_impl(k_pool.dtype, k_pool.shape[-1],
                                k_pool.shape[-2])


def paged_attention(q, k_pool, v_pool, block_tables, pos, nvalid, *,
                    window, softcap: float = 0.0, scale: float,
                    first_block=None):
    """Attention of ``q[B, C, H, hd]`` over the block pool
    ``k_pool``/``v_pool[n_blocks, bs, kvh, hd]`` through
    ``block_tables[B, M]``: query ``c`` of row ``b`` sits at position
    ``pos[b] + c`` and sees keys at positions ``<=`` its own and ``>`` its
    own minus ``window`` (a traced int scalar; 2**30 = global). Keys are
    read from the pool as it stands, so the caller writes the chunk's own
    keys first. Rows with ``nvalid`` 0 and queries past ``nvalid`` return
    values nobody may read. Returns ``o[B, C, H, hd]`` in ``q``'s dtype.

    ``first_block`` ``[B]`` (``None``: zeros): a table that holds a row's
    live window only. Its entry 0 is logical block ``first_block[b]`` of the
    row's context, which must hold the first key the row's first query sees
    (``max(pos - window + 1, 0) // bs``) or lie before it. Both forms then
    walk the table in its own numbering: visibility depends on differences
    of positions alone, so the row's position is taken ``first_block * bs``
    lower and nothing else changes."""
    if first_block is not None:
        pos = pos - first_block * k_pool.shape[1]
    impl = impl_for(k_pool)
    with jax.named_scope("paged_attention"):
        if impl == "pallas":
            return _paged_attention_pallas(
                q, k_pool, v_pool, block_tables, pos, nvalid, window,
                softcap=float(softcap), scale=float(scale),
                pages=pages_per_step(*k_pool.shape[1:]),
                interpret=_pallas_interpret())
        return _paged_attention_xla(q, k_pool, v_pool, block_tables, pos,
                                    window, softcap, scale)


# ---------------------------------------------------------------------------
# the grouped jax.numpy form
# ---------------------------------------------------------------------------

def _paged_attention_xla(q, k_pool, v_pool, block_tables, pos, window,
                         softcap, scale):
    b, c, h, hd = q.shape
    bs, kvh = k_pool.shape[1], k_pool.shape[2]
    m = block_tables.shape[1]
    kctx = k_pool[block_tables].reshape(b, m * bs, kvh, hd)
    vctx = v_pool[block_tables].reshape(b, m * bs, kvh, hd)
    qg = q.reshape(b, c, kvh, h // kvh, hd).astype(kctx.dtype)
    s = jnp.einsum("bcgrd,bkgd->bgrck", qg, kctx,
                   preferred_element_type=jnp.float32) * scale
    s = _softcap_scores(s, softcap)
    qpos = (pos[:, None] + jnp.arange(c)[None, :])[:, :, None]   # [B, C, 1]
    kpos = jnp.arange(m * bs)[None, None, :]
    vis = (kpos <= qpos) & (kpos > qpos - window)                # [B, C, K]
    s = jnp.where(vis[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrck,bkgd->bcgrd", p.astype(vctx.dtype), vctx,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, c, h, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# the Pallas kernel
# ---------------------------------------------------------------------------

def _split_head_pair(words):
    """Two bf16 heads from one 32-bit load: the even head is the low half
    of each word, the odd head the high half. A bf16 is the top half of a
    float32, so each half becomes a float32 by bit placement alone."""
    from jax.experimental.pallas import tpu as pltpu

    even = pltpu.bitcast(words << 16, jnp.float32).astype(jnp.bfloat16)
    odd = pltpu.bitcast(words & jnp.uint32(0xFFFF0000),
                        jnp.float32).astype(jnp.bfloat16)
    return even, odd


@functools.partial(jax.jit, static_argnames=("scale", "softcap"))
def _head_step(q, k, v, vis, m_prev, l_prev, acc, *, scale: float,
               softcap: float):
    """One online-softmax update of one KV head: queries ``q [R, hd]`` over a
    step's keys ``k`` / values ``v [K, hd]``, visible where ``vis [R, K]``;
    running max and sum ``[R, 1]``, weighted sum ``acc [R, hd]``, float32.
    (A jitted function of values: both row kinds trace it once.)"""
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    s = _softcap_scores(s, softcap)
    s = jnp.where(vis, s, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # a query that sees no key of this step keeps m = NEG_INF: exp(s - m)
    # would read 1 there, so mask the weights too
    p = jnp.where(vis, jnp.exp(s - m_new), 0.0)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc * alpha + lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc


def each_page(n, one, group=8):
    """In a kernel: ``one(p)`` for the first ``n`` of a step's pages, ``group``
    at a time as straight-line code (the scalar core then runs a page's table
    read and descriptors under the page before's: one by one, a step's 64
    starts took a third of a 4.3 us key step; cell 7's token rows read 497
    GB/s, in eights 547), and what is left one by one."""
    def some(g, carry):
        for i in range(group):
            one(g * group + i)
        return carry

    def rest(p, carry):
        one(p)
        return carry
    lax.fori_loop(0, n // group, some, 0)
    lax.fori_loop(n // group * group, n, rest, 0)


def _paged_kernel(tbl_ref, pos_ref, nv_ref, win_ref, src_ref,   # prefetch
                  *refs, pages: int, tbl_width: int, rep: int, chunk: int,
                  scale: float, softcap: float):
    """Grid step ``b``: row ``b``, by what it feeds (``nv_ref[b]``).

    One token: its ``rep`` heads a KV head are one sublane tile of
    ``qt_ref [1, kvh, rep_t, hd]``, attended into ``ot_ref``. More: its
    ``chunk`` queries are placed from ``q_ref [1, chunk, H, hd]`` (the
    model's layout) into ``qs_ref [kvh, rep * chunk, hd]`` (head ``r`` of the
    group, query ``c`` in row ``r * chunk + c``) and ``o_ref`` is written as
    the model wants it; ``q_ref`` / ``o_ref`` stay on the block of a row
    that feeds a chunk (``src_ref[b]``), so other rows move neither.
    Nothing: nothing. ``chunk`` 0: the call has token rows only, and neither
    the chunk's refs nor its body.

    The copies run one key step AHEAD of the multiplications, across rows:
    a row's last step starts the first step of the next row that feeds
    anything (``plan_ref[B + b]``; the first such row, ``plan_ref[2 B]``,
    starts its own), into the buffer the row's own steps leave free
    (``plan_ref[b]``: the buffer of its first step). Row 0 writes that plan
    from the rows' positions. Key steps count from the row's own first live
    page: what a row multiplies, and in which order, is its own table's
    alone."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if chunk:
        (qt_ref, q_ref, k_hbm, v_hbm, ot_ref, o_ref,
         kbuf, vbuf, sems, plan_ref, qs_ref, m_ref, l_ref, acc_ref) = refs
    else:
        (qt_ref, k_hbm, v_hbm, ot_ref,
         kbuf, vbuf, sems, plan_ref, m_ref, l_ref, acc_ref) = refs
    b, n_grid = pl.program_id(0), pl.num_programs(0)
    _, bs, kvh, hd = kbuf.shape[1:]
    keys = pages * bs
    rep_t = qt_ref.shape[2]
    pos, nv, win = pos_ref[b], nv_ref[b], win_ref[0]

    def live_range(row):
        """(first live page, live pages) of ``row``'s table: from the page
        of the first key its FIRST query may see to the last that exists."""
        first = jnp.maximum(pos_ref[row] - win + 1, 0) // bs
        return first, (pos_ref[row] + nv_ref[row] + bs - 1) // bs - first

    @pl.when(b == 0)
    def _first_row():
        # a step's dead pages are not copied: what the buffers hold there
        # is multiplied by a weight of 0, so it has to be a number
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

        def buffers(row, slot):
            plan_ref[row] = slot
            steps = (live_range(row)[1] + pages - 1) // pages
            return jnp.where(nv_ref[row] > 0, (slot + steps) % 2, slot)
        lax.fori_loop(0, n_grid, buffers, 0)

        def feeds_next(i, then):
            row = n_grid - 1 - i
            plan_ref[n_grid + row] = then
            return jnp.where(nv_ref[row] > 0, row, then)
        plan_ref[2 * n_grid] = lax.fori_loop(0, n_grid, feeds_next, n_grid)

    def copies(page, slot, p):
        return (pltpu.make_async_copy(k_hbm.at[page], kbuf.at[slot, p],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[page], vbuf.at[slot, p],
                                      sems.at[1, slot]))

    def start_copies(row, step, slot, group=8):
        first, live = live_range(row)
        at = row * tbl_width + first + step * pages

        def one(p):
            for copy in copies(tbl_ref[at + p], slot, p):
                copy.start()
        each_page(jnp.minimum(live - step * pages, pages), one, group)

    def attend(load_q, n_rows, per_head):
        """Queries ``load_q(head) [n_rows, hd]`` of each KV head, row ``r``
        at position ``pos + r % per_head``, over the row's live keys; leaves
        the weighted sums in ``acc_ref[:, :n_rows]`` and their weights' sums
        in ``l_ref``."""
        first_page, live_pages = live_range(b)
        last = (live_pages + pages - 1) // pages
        slot0, then = plan_ref[b], plan_ref[n_grid + b]
        rows = slice(0, n_rows)
        m_ref[:, rows] = jnp.full((kvh, n_rows, LANES), NEG_INF, jnp.float32)
        l_ref[:, rows] = jnp.zeros((kvh, n_rows, LANES), jnp.float32)
        acc_ref[:, rows] = jnp.zeros((kvh, n_rows, hd), jnp.float32)

        @pl.when(b == plan_ref[2 * n_grid])
        def _first_copy():
            start_copies(b, 0, slot0, group=1)   # (once a call)

        qpos = pos
        if per_head > 1:
            qpos += lax.broadcasted_iota(
                jnp.int32, (n_rows, 1), 0) % per_head

        def key_step(step, carry):
            """Start the copies of the step after (the row's own, or after
            its last the next row's first), await this step's, and take its
            keys into every head's running max, sum and weighted sum."""
            slot = (slot0 + step) % 2
            own = step + 1 < last

            @pl.when(own | (then < n_grid))
            def _next_copy():
                start_copies(jnp.where(own, b, then),
                             jnp.where(own, step + 1, 0), 1 - slot)

            def wait(p):
                for copy in copies(0, slot, p):   # a wait needs only the size
                    copy.wait()
            each_page(jnp.minimum(live_pages - step * pages, pages), wait)

            kpos = (first_page + step * pages) * bs \
                + lax.broadcasted_iota(jnp.int32, (1, keys), 1)
            vis = jnp.broadcast_to((kpos <= qpos) & (kpos > qpos - win),
                                   (n_rows, keys))
            # [pages, bs, kvh, hd] bf16 -> 32-bit words [keys * kvh / 2, hd]:
            # word row ``key * kvh / 2 + pair`` holds heads 2 pair, 2 pair + 1
            k32 = kbuf.at[slot].reshape(keys * kvh, hd).bitcast(jnp.uint32)
            v32 = vbuf.at[slot].reshape(keys * kvh, hd).bitcast(jnp.uint32)

            def one_pair(pair, carry):
                of_pair = pl.ds(pair, keys, stride=kvh // 2)
                k_pair = _split_head_pair(k32[of_pair, :])
                v_pair = _split_head_pair(v32[of_pair, :])
                for g, (k, v) in enumerate(zip(k_pair, v_pair)):
                    head = 2 * pair + g
                    m_new, l_new, acc_ref[head, rows] = _head_step(
                        load_q(head), k, v, vis, m_ref[head, rows, 0:1],
                        l_ref[head, rows, 0:1], acc_ref[head, rows],
                        scale=scale, softcap=softcap)
                    m_ref[head, rows] = jnp.broadcast_to(m_new,
                                                         (n_rows, LANES))
                    l_ref[head, rows] = jnp.broadcast_to(l_new,
                                                         (n_rows, LANES))
                return carry
            lax.fori_loop(0, kvh // 2, one_pair, 0,
                          unroll=n_rows * keys <= UNROLL_TILE)
            return carry

        lax.fori_loop(0, last, key_step, 0)

    def result(heads, rows):
        l = l_ref[heads, rows, 0:1]
        l = jnp.where(l == 0.0, 1.0, l)     # rows that saw nothing -> 0
        return acc_ref[heads, rows] / l

    @pl.when(nv == 1)
    def _token_row():
        attend(lambda head: qt_ref[0, head], rep_t, 1)
        ot_ref[0] = result(slice(None), slice(0, rep_t)).astype(ot_ref.dtype)

    if not chunk:
        return

    @pl.when(nv > 1)
    def _chunk_row():
        # (query, head) -> (head, query): head ``h`` of the chunk in rows
        # ``[(h % rep) chunk, ...)`` of KV head ``h // rep``
        qs_ref[...] = jnp.swapaxes(q_ref[0], 0, 1).reshape(qs_ref.shape)
        attend(lambda head: qs_ref[head], rep * chunk, chunk)
        o = result(slice(None), slice(0, rep * chunk))
        o_ref[0] = jnp.swapaxes(o.reshape(kvh * rep, chunk, hd), 0, 1).astype(
            o_ref.dtype)


def pages_per_step(block_size: int, kv_heads: int, head_dim: int,
                   itemsize: int = 2) -> int:
    """Pages one key step copies and multiplies: :data:`STEP_BYTES` of K (and
    as many of V) whatever the page's width."""
    return max(1, STEP_BYTES // (block_size * kv_heads * head_dim * itemsize))


@functools.partial(jax.jit, static_argnames=("softcap", "scale", "pages",
                                             "interpret"))
def _paged_attention_pallas(q, k_pool, v_pool, block_tables, pos, nvalid,
                            window, *, softcap: float, scale: float,
                            pages: int, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, c, h, hd = q.shape
    bs, kvh = k_pool.shape[1], k_pool.shape[2]
    rep = h // kvh
    m = block_tables.shape[1]
    pages = min(pages, m)
    dtype, out_dtype = k_pool.dtype, q.dtype
    q = q.astype(dtype)
    nvalid = nvalid.astype(jnp.int32)
    # a token row's heads: the rep heads of a KV head in one bf16 sublane
    # tile, cut from the rows' first queries
    rep_t = -(-rep // 16) * 16
    qt = jnp.pad(q[:, 0].reshape(b, kvh, rep, hd),
                 ((0, 0), (0, 0), (0, rep_t - rep), (0, 0)))
    # a chunk row's queries as the model has them, the chunk whole sublane
    # tiles; a grid of one query a row has token rows only
    cp = -(-c // 16) * 16 if c > 1 else 0
    rows = max(rep * cp, rep_t)
    # the chunk blocks' row: the last row up to each that feeds a chunk,
    # before the first such row already its own (none at all: row 0)
    row = jnp.arange(b, dtype=jnp.int32)
    src = jnp.maximum(lax.cummax(jnp.where(nvalid > 1, row, 0)),
                      jnp.argmax(nvalid > 1).astype(jnp.int32))
    # VMEM the call holds: the token and chunk blocks of q and o
    # (double-buffered), the placed queries, the two K/V buffers, running
    # max / sum and the accumulator. A wide chunk under a large group (128
    # queries x 6 heads a KV head: 768 rows) passes the compiler's default
    # scoped limit of 16 MiB; such a call asks for twice its count (the
    # scores of a step live beside it). Smaller calls ask for nothing.
    h_tiles = -(-h // 16) * 16
    held = (4 * kvh * rep_t * hd * 2 + (4 * h_tiles + h) * cp * hd * 2
            + 4 * pages * bs * kvh * hd * 2
            + 2 * kvh * rows * LANES * 4 + kvh * rows * hd * 4)
    vmem_limit = 2 * held if held > 13 * 2 ** 20 else None
    row_block = lambda b_, *_: (b_, 0, 0, 0)
    chunk_block = lambda b_, tbl, pos_, nv, win, src_: (src_[b_], 0, 0, 0)
    tok_spec = pl.BlockSpec((1, kvh, rep_t, hd), row_block)
    chunk_spec = pl.BlockSpec((1, cp, h, hd), chunk_block)
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    qc = [jnp.pad(q, ((0, 0), (0, cp - c), (0, 0), (0, 0)))] if cp else []
    kernel = functools.partial(
        _paged_kernel, pages=pages, tbl_width=m, rep=rep, chunk=cp,
        scale=scale, softcap=softcap)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(b,),
            in_specs=[tok_spec] + [chunk_spec] * bool(cp) + [pool_spec] * 2,
            out_specs=[tok_spec] + [chunk_spec] * bool(cp),
            scratch_shapes=[
                pltpu.VMEM((2, pages, bs, kvh, hd), dtype),
                pltpu.VMEM((2, pages, bs, kvh, hd), dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((2 * b + 1,), jnp.int32),
            ] + [pltpu.VMEM((kvh, rep * cp, hd), dtype)] * bool(cp) + [
                pltpu.VMEM((kvh, rows, LANES), jnp.float32),
                pltpu.VMEM((kvh, rows, LANES), jnp.float32),
                pltpu.VMEM((kvh, rows, hd), jnp.float32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, kvh, rep_t, hd), out_dtype)]
        + [jax.ShapeDtypeStruct((b, cp, h, hd), out_dtype)] * bool(cp),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit),
        name="paged_attention_fwd",
        interpret=interpret,
    )(block_tables.reshape(-1).astype(jnp.int32), pos.astype(jnp.int32),
      nvalid, jnp.asarray(window, jnp.int32).reshape(1), src, qt, *qc,
      k_pool, v_pool)
    # by what the row fed: its chunk, its token, nothing (a dead row's
    # blocks hold what the call found there)
    feeds = lambda n: (nvalid >= n)[:, None, None, None]
    tok = out[0][:, :, :rep].reshape(b, 1, h, hd)
    tok = jnp.where(feeds(1), tok, jnp.zeros_like(tok))
    if not cp:
        return tok
    return jnp.where(feeds(2), out[1][:, :c],
                     jnp.pad(tok, ((0, 0), (0, c - 1), (0, 0), (0, 0))))
