"""Differential attention of the serve step over a row's gathered context
(Ye et al., arXiv:2410.05258), for the hybrid decoder's window, full and
cross layers.

Heads come in pairs. Query heads ``(2i, 2i + 1)`` are pair ``i``; KV heads
``(2j, 2j + 1)`` are pair ``j``; query pair ``i`` reads KV pair ``i // g``
(``g`` query pairs a KV pair). With ``V_j = [v_2j | v_2j+1]`` (the pair's two
value heads side by side, ``2 hd`` wide):

    out_i = subln(softmax(q_2i k_2j^T / sqrt(hd)) V_j
                  - lam softmax(q_2i+1 k_2j+1^T / sqrt(hd)) V_j) (1 - lam_init)

``subln`` an RMSNorm with gain over the ``2 hd``. The pool's rows hold a
token's KV heads side by side (``kvh * hd`` wide), so a KV PAIR is ``2 hd``
contiguous lanes and no ``hd``-wide array is ever formed: a query head is
placed in its own columns of a wider vector (zeros elsewhere), one product
against the keys gives its scores and one against the values an output as
wide, of which the pair's ``2 hd`` columns are kept.

The context arrives GATHERED (``gather_context``): eight layers of the
hybrid decoder read one pool through one table, and gather it once. Rows
come in two shapes, as the paged step has them: every row's FIRST query in
one batched product over the context as it was gathered
(:func:`_attend_first`; a decoding row has no other query), and the rows that
feed a chunk one at a time under a ``lax.cond`` (:func:`_attend`, the context
of ONE row split into pairs), so that a step of decoding rows pays for one
query a row and not for ``C``. All ``jax.numpy``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import NEG_INF

F32 = jnp.float32


def gather_context(pool, tables):
    """``pool [n_blocks, bs, w]`` through ``tables [B, M]`` ->
    ``[B, M * bs, w]``: key ``e * bs + o`` of a row is offset ``o`` of its
    table's entry ``e``."""
    b, m = tables.shape
    return pool[tables].reshape(b, m * pool.shape[1], pool.shape[2])


def _attend(q, kctx, vctx, qpos, *, window, heads, kv_heads, hd):
    """``q [R, Q, heads * hd]`` at positions ``qpos [R, Q]`` (in the
    context's own numbering) over ``kctx``/``vctx [R, K, kv_heads * hd]``;
    a query sees keys ``<=`` its position and, with a ``window``, ``>`` its
    position minus the window. Returns the two softmax outputs of every
    query pair ``[R, Q, kv_pairs, g, 2, 2 * hd]`` in float32."""
    r, nq = q.shape[:2]
    nk = kctx.shape[1]
    j, g = kv_heads // 2, heads // kv_heads
    q = q.reshape(r, nq, j, g, 2, 1, hd).astype(kctx.dtype)
    # query head s of a pair into half s of a 2 * hd-wide vector
    half = jnp.eye(2, dtype=q.dtype)[None, None, None, None, :, :, None]
    q = (q * half).reshape(r, nq, j, g, 2, 2 * hd)
    k = kctx.reshape(r, nk, j, 2 * hd)
    v = vctx.reshape(r, nk, j, 2 * hd)
    s = jnp.einsum("rqjgsd,rkjd->rjgsqk", q, k,
                   preferred_element_type=F32) * hd ** -0.5
    kpos = jnp.arange(nk)[None, None, :]
    vis = kpos <= qpos[:, :, None]
    if window:
        vis = vis & (kpos > qpos[:, :, None] - window)
    s = jnp.where(vis[:, None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("rjgsqk,rkjd->rqjgsd", p.astype(v.dtype), v,
                      preferred_element_type=F32)


def _attend_first(q, kctx, vctx, qpos, *, window, heads, kv_heads, hd):
    """``_attend`` for ONE query a row (``q [R, heads * hd]`` at ``qpos
    [R]``), without forming the context again: the context stays ``[R, K,
    kv_heads * hd]`` as it was gathered and is contracted over its whole
    width. A query head is placed in its own KV head's ``hd`` columns of a
    ``kv_heads * hd``-wide vector (zeros elsewhere), so one product with the
    keys gives its scores; the product of its weights with the values is
    as wide, and the head's pair is cut out of it. That multiplies by
    zeros ``kv_heads`` times over, which one query a row can afford (a
    chunk's queries cannot: :func:`_attend`); splitting the context's last
    axis into pairs instead had the TPU lay all of it out again, K and V,
    every step (3 ms for a 4096-key context of 32 rows)."""
    r, nk, width = kctx.shape
    j, g = kv_heads // 2, heads // kv_heads
    q = q.reshape(r, j, g, 2, 1, hd).astype(kctx.dtype)
    # head (j, g, s) reads KV head 2 j + s
    own = (jnp.arange(kv_heads)[None, None, :]
           == (2 * jnp.arange(j)[:, None, None]
               + jnp.arange(2)[None, :, None]))                 # [j, s, kvh]
    wide = (q * own[None, :, None, :, :, None].astype(q.dtype)).reshape(
        r, heads, width)
    s = jnp.einsum("rhw,rkw->rhk", wide, kctx,
                   preferred_element_type=F32) * hd ** -0.5
    kpos = jnp.arange(nk)[None, :]
    vis = kpos <= qpos[:, None]
    if window:
        vis = vis & (kpos > qpos[:, None] - window)
    p = jax.nn.softmax(jnp.where(vis[:, None], s, NEG_INF), axis=-1)
    o = jnp.einsum("rhk,rkw->rhw", p.astype(vctx.dtype), vctx,
                   preferred_element_type=F32)
    # of head (j, g, s)'s kv_heads * hd-wide output, pair j's 2 hd columns
    o = o.reshape(r, j, g * 2, j, 2 * hd)
    return jnp.einsum("rjxjd->rjxd", o).reshape(r, 1, j, g, 2, 2 * hd)


def paged_diff_attention(q, kctx, vctx, pos, nvalid, lam, lam_init, subln,
                         *, window: int, heads: int, kv_heads: int,
                         eps: float = 1e-5):
    """``q [B, C, heads * hd]``: query ``c`` of row ``b`` sits at position
    ``pos[b] + c`` of the row's gathered context ``kctx``/``vctx [B, K,
    kv_heads * hd]``; ``nvalid [B]`` real queries a row (rows with 0 and
    queries past it return values nobody may read). ``lam``, ``lam_init``:
    float32 scalars; ``subln [2 * hd]``. Returns ``[B, C, heads * hd]`` in
    ``q``'s dtype (pair ``i``'s output in columns ``[2 hd i, 2 hd (i + 1))``).
    """
    b, c, width = q.shape
    hd = width // heads
    kw = dict(window=window, heads=heads, kv_heads=kv_heads, hd=hd)
    pair_shape = (kv_heads // 2, heads // kv_heads, 2, 2 * hd)
    out = jnp.zeros((b, c) + pair_shape, F32)
    # every row's first query, all rows at once
    out = out.at[:, :1].set(_attend_first(q[:, 0], kctx, vctx, pos, **kw))

    def chunk_rows(out):
        def one(i, out):
            def row(out):
                take = lambda a: lax.dynamic_index_in_dim(a, i, 0, True)
                o = _attend(take(q), take(kctx), take(vctx),
                            take(pos)[:, None] + jnp.arange(c)[None], **kw)
                return lax.dynamic_update_index_in_dim(out, o[0], i, 0)
            return lax.cond(nvalid[i] > 1, row, lambda out: out, out)
        return lax.fori_loop(0, b, one, out)

    if c > 1:
        out = lax.cond(jnp.any(nvalid > 1), chunk_rows, lambda out: out, out)
    o = out[..., 0, :] - lam * out[..., 1, :]          # [B, C, j, g, 2 hd]
    var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
    o = o * lax.rsqrt(var + eps) * subln.astype(F32) * (1.0 - lam_init)
    return o.reshape(b, c, width).astype(q.dtype)
