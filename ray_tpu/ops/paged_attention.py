"""Paged attention: the serve step's attention, read THROUGH the block table.

One function, :func:`paged_attention`, with two forms of one algorithm:

- a Pallas TPU kernel (``paged_attention_fwd``) that walks each row's block
  table: per slot it copies the row's LIVE pages (window start to
  ``ceil((pos + nvalid) / bs)``, nothing past it) from the HBM pool into
  VMEM, keeps the KV heads grouped (all ``rep`` query heads of a group share
  one read of the page), multiplies bf16 operands with float32 accumulation
  and carries the online softmax (running max / sum) in float32 — the loop
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
KEYS_PER_STEP = 512  # keys one inner step copies and multiplies (pages x bs)
#: ... and the most bytes of K (and as many of V) such a step holds: 512 keys
#: of 8 KV heads of 128. A wider pool (32 heads: 128 KB a page of 16) takes
#: fewer keys a step, so the four page buffers stay 4 MiB and the call inside
#: the default scoped VMEM as every narrower model's is (at 32 heads, 512 keys
#: a step were 16.8 MiB of buffers and read no faster: 0.602 against 0.586 ms,
#: my chip run, PR 50). The kernel at 32 heads COMPILES for a described v5e
#: with the cap and without it (a limit of 44 MiB asked for and granted: my
#: CPU compile, PR 50), so no compile tells the two apart; what the cap buys
#: is a call that asks the compiler for nothing
BYTES_PER_STEP = 1 << 20


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


def _paged_kernel(tbl_ref, pos_ref, nv_ref, win_ref,      # scalar prefetch
                  q_ref, k_hbm, v_hbm,                    # inputs
                  o_ref,                                  # output
                  kbuf, vbuf, sems, m_ref, l_ref, acc_ref,
                  *, pages: int, tbl_width: int, rep: int, scale: float,
                  softcap: float):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    _, bs, kvh, hd = kbuf.shape[1:]
    keys = pages * bs
    rows = q_ref.shape[2]
    pos, nv, win = pos_ref[b], nv_ref[b], win_ref[0]
    # live steps of this row's table: from the first key the row's FIRST
    # query may see (its window's start) to the last key that exists
    first = jnp.maximum(pos - win + 1, 0) // keys
    last = jnp.where(nv > 0, (pos + nv + keys - 1) // keys, first)

    def copy(page, slot, p):
        return (pltpu.make_async_copy(k_hbm.at[page], kbuf.at[slot, p],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[page], vbuf.at[slot, p],
                                      sems.at[1, slot]))

    def start_copies(step, slot):
        def one(p, carry):
            for cp in copy(tbl_ref[b * tbl_width + step * pages + p],
                           slot, p):
                cp.start()
            return carry
        lax.fori_loop(0, pages, one, 0)

    def wait_copies(slot):
        def one(p, carry):
            for cp in copy(0, slot, p):     # a wait needs only the size
                cp.wait()
            return carry
        lax.fori_loop(0, pages, one, 0)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(last > first)
    def _first_copy():
        start_copies(first, 0)

    # query of row r (chunk-major: r = c * rep + g) sits at pos + r // rep
    qpos = pos + lax.broadcasted_iota(jnp.int32, (rows, keys), 0) // rep

    def body(step, carry):
        slot = (step - first) % 2

        @pl.when(step + 1 < last)
        def _next_copy():
            start_copies(step + 1, 1 - slot)

        wait_copies(slot)
        kpos = step * keys + lax.broadcasted_iota(
            jnp.int32, (rows, keys), 1)
        vis = (kpos <= qpos) & (kpos > qpos - win)
        # [pages, bs, kvh, hd] bf16 -> 32-bit words [pages * bs * kvh/2, hd]
        k32 = kbuf.at[slot].reshape(keys * kvh, hd).bitcast(jnp.uint32)
        v32 = vbuf.at[slot].reshape(keys * kvh, hd).bitcast(jnp.uint32)
        for pair in range(kvh // 2):
            k_pair = _split_head_pair(k32[pair::kvh // 2, :])
            v_pair = _split_head_pair(v32[pair::kvh // 2, :])
            for g, (k, v) in enumerate(zip(k_pair, v_pair)):
                head = 2 * pair + g
                s = lax.dot_general(
                    q_ref[0, head], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                s = _softcap_scores(s, softcap)
                s = jnp.where(vis, s, NEG_INF)
                m_prev = m_ref[head, :, 0:1]
                l_prev = l_ref[head, :, 0:1]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1,
                                                    keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                # a query that sees no key of this step keeps m = NEG_INF:
                # exp(s - m) would read 1 there, so mask the weights too
                p = jnp.where(vis, jnp.exp(s - m_new), 0.0)
                l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
                acc_ref[head] = acc_ref[head] * alpha + lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_ref[head] = jnp.broadcast_to(m_new, (rows, LANES))
                l_ref[head] = jnp.broadcast_to(l_new, (rows, LANES))
        return carry

    lax.fori_loop(first, last, body, 0)
    for head in range(kvh):
        l = l_ref[head, :, 0:1]
        l = jnp.where(l == 0.0, 1.0, l)     # rows that saw nothing -> 0
        o_ref[0, head] = (acc_ref[head] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("softcap", "scale", "interpret"))
def _paged_attention_pallas(q, k_pool, v_pool, block_tables, pos, nvalid,
                            window, *, softcap: float, scale: float,
                            interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, c, h, hd = q.shape
    bs, kvh = k_pool.shape[1], k_pool.shape[2]
    rep = h // kvh
    rows = -(-c * rep // 16) * 16     # whole bf16 sublane tiles
    m = block_tables.shape[1]
    page_bytes = bs * kvh * hd * k_pool.dtype.itemsize
    pages = min(max(1, min(KEYS_PER_STEP // bs,
                           BYTES_PER_STEP // page_bytes)), m)
    if m % pages:
        # entries past the row's live range are never reached: any valid
        # id does
        block_tables = jnp.pad(block_tables, ((0, 0), (0, -m % pages)))
        m = block_tables.shape[1]
    # rows of one KV group side by side, chunk-major: [B, kvh, C * rep, hd]
    qg = q.reshape(b, c, kvh, rep, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(b, kvh, c * rep, hd).astype(k_pool.dtype)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rows - c * rep), (0, 0)))
    # VMEM the call holds: q and o blocks (double-buffered), the two K/V
    # buffers, running max / sum and the accumulator. A wide chunk under a
    # large group (128 queries x 6 heads a KV head: 768 rows) passes the
    # compiler's default scoped limit of 16 MiB; such a call asks for twice
    # its count (the scores of a step live beside it). Smaller calls ask
    # for nothing and compile as they always did.
    held = (4 * kvh * rows * hd * 2 + 4 * pages * bs * kvh * hd * 2
            + 2 * kvh * rows * LANES * 4 + kvh * rows * hd * 4)
    vmem_limit = 2 * held if held > 12 * 2 ** 20 else None
    kernel = functools.partial(
        _paged_kernel, pages=pages, tbl_width=m, rep=rep,
        scale=scale, softcap=softcap)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, kvh, rows, hd),
                             lambda b_, *_: (b_, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, kvh, rows, hd),
                                   lambda b_, *_: (b_, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pages, bs, kvh, hd), k_pool.dtype),
                pltpu.VMEM((2, pages, bs, kvh, hd), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((kvh, rows, LANES), jnp.float32),
                pltpu.VMEM((kvh, rows, LANES), jnp.float32),
                pltpu.VMEM((kvh, rows, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kvh, rows, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit),
        name="paged_attention_fwd",
        interpret=interpret,
    )(block_tables.reshape(-1).astype(jnp.int32), pos.astype(jnp.int32),
      nvalid.astype(jnp.int32),
      jnp.asarray(window, jnp.int32).reshape(1), qg, k_pool, v_pool)
    return out[:, :, :c * rep].reshape(b, kvh, c, rep, hd) \
        .transpose(0, 2, 1, 3, 4).reshape(b, c, h, hd)
