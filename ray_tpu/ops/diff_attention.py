"""Differential attention of the serve step over a row's paged context
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
placed in its own half of a ``2 hd``-wide vector (zeros in the other), one
product against the pair's keys gives its scores and one against the pair's
values an output as wide.

:func:`paged_diff_attention` takes the POOLS and a block table (eight layers
of the hybrid decoder read one pool through one table). **Two forms of one
algorithm**, chosen from what the code can observe
(:func:`diff_attention_impl`: backend, pool dtype, lane width of a KV pair,
block size), never from a model's name or a setting:

- a Pallas TPU kernel (``diff_attention_fwd``) that reads both pools THROUGH
  the table: a grid step is a row; the row copies its LIVE pages
  (``ceil((pos + nvalid) / bs)`` of them, a window layer from the page of
  its first query's window start; none past) of K and of V from HBM into a
  double-buffered VMEM scratch, :data:`KEYS_PER_STEP` keys a step; per KV
  pair a lane-aligned slice of the step's keys, the pair's query heads on
  the matmul's row axis (a row that feeds ONE token: its ``2 g`` heads in a
  sublane tile; a row that feeds a chunk: ``2 g`` heads x ``C`` queries),
  bf16 operands, float32 accumulation, the online softmax's running max and
  sum in float32 across key steps, so no score reaches HBM; the
  subtraction, the sub-norm and ``(1 - lam_init)`` in the kernel's
  epilogue, in float32. A row that feeds nothing copies nothing. Key steps
  count from the row's own first live page, so a row's result does not
  depend on which other rows share the step;
- the ``jax.numpy`` form: the context GATHERED through the table
  (:func:`gather_context`), every row's FIRST query in one batched product
  over it (:func:`_attend_first`; a decoding row has no other query), and
  the rows that feed a chunk one at a time under a ``lax.cond``
  (:func:`_attend`). It runs wherever the kernel does not (CPU, float32
  pools, pairs narrower than the lanes) and is the reference the kernel is
  compared with.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import (NEG_INF, _pallas_interpret,
                                   resolve_attention_impl)
from ray_tpu.ops.latent_attention import LANES, SUBLANES, VMEM_LIMIT

F32 = jnp.float32
#: keys one inner step of the kernel copies and multiplies (pages x bs): on a
#: v5e 22 token rows at contexts of 230-1765 read their live K and V at 401
#: GB/s at 512 and 442-453 at 1024 (0.242-0.248 ms a layer; the ``jax.numpy``
#: form 1.05 and an eighth of a 2.2 ms gather), the window layers' 560 keys
#: at 251 and 258-271
KEYS_PER_STEP = 1024


def gather_context(pool, tables):
    """``pool [n_blocks, bs, w]`` through ``tables [B, M]`` ->
    ``[B, M * bs, w]``: key ``e * bs + o`` of a row is offset ``o`` of its
    table's entry ``e``."""
    b, m = tables.shape
    return pool[tables].reshape(b, m * pool.shape[1], pool.shape[2])


def _own_half(q):
    """``q [..., 2, hd]``, the two heads of a query pair -> ``[..., 2, 2
    hd]``: head ``s`` in half ``s`` of a ``2 hd``-wide vector, zeros in the
    other half."""
    half = jnp.eye(2, dtype=q.dtype)[:, :, None]
    return (q[..., None, :] * half).reshape(*q.shape[:-1], 2 * q.shape[-1])


def _attend(q, kctx, vctx, qpos, *, window, heads, kv_heads, hd):
    """``q [R, Q, heads * hd]`` at positions ``qpos [R, Q]`` (in the
    context's own numbering) over ``kctx``/``vctx [R, K, kv_heads * hd]``;
    a query sees keys ``<=`` its position and, with a ``window``, ``>`` its
    position minus the window. Returns the two softmax outputs of every
    query pair ``[R, Q, kv_pairs, g, 2, 2 * hd]`` in float32."""
    r, nq = q.shape[:2]
    nk = kctx.shape[1]
    j, g = kv_heads // 2, heads // kv_heads
    q = _own_half(q.reshape(r, nq, j, g, 2, hd).astype(kctx.dtype))
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


def diff_attention_impl(pool_dtype, pair_width: int, block_size: int) -> str:
    """``"pallas"`` when the kernel takes these pools on this backend, else
    ``"xla"`` (the ``jax.numpy`` form). The kernel wants bf16 pools in which
    a KV pair (``pair_width`` = ``2 hd`` values) is whole lanes and whose
    blocks are whole sublane tiles, so that a page lands in VMEM as it lies
    in HBM and a pair is a lane-aligned slice of it."""
    if (resolve_attention_impl() == "pallas"
            and jnp.dtype(pool_dtype) == jnp.bfloat16
            and pair_width % LANES == 0 and block_size % SUBLANES == 0):
        return "pallas"
    return "xla"


def impl_for(k_pool, head_dim: int) -> str:
    """:func:`diff_attention_impl` of a pool ``[..., bs, kv_heads * hd]``:
    one layer's as the op takes it, or the cache's stack of them."""
    return diff_attention_impl(k_pool.dtype, 2 * head_dim, k_pool.shape[-2])


def paged_diff_attention(q, k_pool, v_pool, tables, pos, nvalid, lam,
                         lam_init, subln, *, window: int, heads: int,
                         kv_heads: int, eps: float = 1e-5):
    """``q [B, C, heads * hd]`` over the pools ``k_pool`` / ``v_pool
    [n_blocks, bs, kv_heads * hd]`` through ``tables [B, M]`` (the layer's
    first block added): key ``e * bs + o`` of a row is offset ``o`` of its
    table's entry ``e``, and query ``c`` of row ``b`` sits at position
    ``pos[b] + c`` of that numbering; it sees the keys up to its own and,
    with a ``window``, the last ``window`` of them; the caller has written
    the step's own keys. ``nvalid [B]`` real queries a row (rows with 0 and
    queries past it return values nobody may read). ``lam``, ``lam_init``:
    float32 scalars; ``subln [2 * hd]``. Returns ``[B, C, heads * hd]`` in
    ``q``'s dtype (pair ``i``'s output in columns ``[2 hd i, 2 hd (i + 1))``).
    """
    hd = q.shape[-1] // heads
    if impl_for(k_pool, hd) == "pallas":
        return _diff_attention_pallas(
            q, k_pool, v_pool, tables, pos, nvalid, lam, lam_init, subln,
            window=window, heads=heads, kv_heads=kv_heads, eps=eps,
            keys=KEYS_PER_STEP, interpret=_pallas_interpret())
    return _diff_attention_xla(
        q, gather_context(k_pool, tables), gather_context(v_pool, tables),
        pos, nvalid, lam, lam_init, subln, window=window, heads=heads,
        kv_heads=kv_heads, eps=eps)


def _diff_attention_xla(q, kctx, vctx, pos, nvalid, lam, lam_init, subln, *,
                        window, heads, kv_heads, eps):
    """Over a row's gathered context ``kctx``/``vctx [B, K, kv_heads *
    hd]``."""
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


# ---------------------------------------------------------------------------
# the Pallas kernel
# ---------------------------------------------------------------------------

# The kernel's arithmetic a KV pair, as jitted functions of values, and its
# ten pairs as ``fori_loop``s UNROLLED AT LOWERING (``unroll=True``): the
# step program is traced and lowered in every process that serves, a cache
# hit or not, and the benchmark's ``setup_s`` has a bound. A body written
# once is traced once; unrolled in Python it is traced ten times over in
# four places. Left as loops on the chip the pairs run one after another
# (0.33 ms a layer where unrolled 0.25: the scheduler interleaves the ten
# chains), so the token rows' loops unroll when they are lowered; the chunk
# rows' placement and epilogue stay loops (one step in eight has such a row,
# and their forty copies doubled the lowering). On the chip's host a warm
# engine's first step takes 4.7 s like this and 7.0 s unrolled in Python.

@functools.partial(jax.jit, static_argnames=("scale",))
def _pair_step(q, k, v, seen, m_prev, l_prev, acc, *, scale: float):
    """One online-softmax update: queries ``q [R, 2 hd]`` over a step's keys
    ``k`` / values ``v [K, 2 hd]`` of one KV pair, visible where ``seen [R,
    K]``; running max and sum ``[R, 1]``, weighted sum ``acc [R, 2 hd]``."""
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=F32) * scale
    # a query whose first visible key lies in a later step sums this one
    # under a max of NEG_INF, and the first real max wipes it (alpha = 0)
    s = jnp.where(seen, s, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc * alpha + lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=F32)
    return m_new, l_new, acc


@functools.partial(jax.jit, static_argnames=("eps",))
def _sub_norm(o1, o2, consts, *, eps: float):
    """``subln(o1 - lam o2) (1 - lam_init)``, float32; ``consts`` rows:
    the sub-norm's gain, ``lam``, ``1 - lam_init``."""
    o = o1 - consts[1:2] * o2
    var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
    return o * lax.rsqrt(var + eps) * consts[0:1] * consts[2:3]


@jax.jit
def _halves(q):
    """``q [C, 2 hd]``, a query pair's two heads side by side -> each head
    in its own half of the lanes, zeros in the other."""
    lower = lax.broadcasted_iota(jnp.int32, q.shape, 1) < q.shape[1] // 2
    zero = jnp.zeros_like(q)
    return jnp.where(lower, q, zero), jnp.where(lower, zero, q)


def _diff_kernel(tbl_ref, pos_ref, nv_ref, plan_ref,       # scalar prefetch
                 qt_ref, q_ref, c_ref, k_hbm, v_hbm,       # inputs
                 ot_ref, o_ref,                            # outputs
                 kbuf, vbuf, sems, qs_ref, m_ref, l_ref, acc_ref,
                 *, pages: int, tbl_width: int, g: int, hd: int, window: int,
                 eps: float):
    """Grid step ``b``: row ``b``. A row that feeds one token attends its
    ``2 g`` heads a KV pair from ``qt_ref`` (head ``(s, gi)`` in row ``s *
    G + gi`` of the pair's sublane tiles, ``G`` = ``g`` rounded up to 8; in
    its own ``hd`` half of the lanes) into ``ot_ref`` (query pair ``gi`` in
    row ``gi``); a row that feeds a chunk places its ``C`` queries from
    ``q_ref`` (as the model has them: query pair ``(j, gi)`` in lanes ``[(j
    g + gi) 2 hd, ...)``) into ``qs_ref`` (head ``(s, gi)`` of query ``c``
    in row ``(s g + gi) C + c``) and writes ``o_ref`` as the model wants
    it. ``q_ref`` / ``o_ref`` stay on the block of the last row that fed a
    chunk (``plan_ref[b]``): a step of token rows moves neither.

    The copies run one key step AHEAD of the multiplications, across rows:
    a row's last step starts the first step of the next row that feeds
    anything (``plan_ref[2 B + b]``; the first such row, ``plan_ref[3 B]``,
    starts its own), into the buffer the row's own steps leave free
    (``plan_ref[B + b]``: the buffer of its first step). What a row
    multiplies, and in which order, is its own table's alone."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, n_rows_grid = pl.program_id(0), pl.num_programs(0)
    keys = kbuf.shape[1]
    bs = keys // pages
    c = q_ref.shape[1]
    pw = 2 * hd
    pairs = kbuf.shape[2] // pw
    g8 = ot_ref.shape[2]
    pos, nv = pos_ref[b], nv_ref[b]
    scale = hd ** -0.5

    @pl.when(b == 0)
    def _clean():
        # a step's dead pages are not copied: what the buffers hold there
        # is multiplied by a weight of 0, so it has to be a number; and a
        # step of token rows alone hands ``o_ref``'s block back untouched
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        o_ref[...] = jnp.zeros_like(o_ref)

    def copies(page, slot, p):
        at = pl.ds(pl.multiple_of(p * bs, bs), bs)
        return (pltpu.make_async_copy(k_hbm.at[page], kbuf.at[slot, at],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[page], vbuf.at[slot, at],
                                      sems.at[1, slot]))

    def lanes(i):
        """The ``2 hd`` lanes of KV pair (or query pair) ``i``."""
        return pl.ds(pl.multiple_of(i * pw, pw), pw)

    def epilogue(j, rows1, rows2):
        """The output of the query pairs whose two heads lie in ``rows1``
        and ``rows2`` of KV pair ``j``."""
        return _sub_norm(acc_ref[j, rows1] / l_ref[j, rows1, 0:1],
                         acc_ref[j, rows2] / l_ref[j, rows2, 0:1],
                         c_ref[...], eps=eps)

    def live_range(row):
        """(first live page, live pages) of ``row``'s table."""
        first = jnp.maximum(pos_ref[row] - window + 1, 0) // bs \
            if window else 0
        return first, (pos_ref[row] + nv_ref[row] + bs - 1) // bs - first

    def start_copies(row, step, slot):
        first, live = live_range(row)

        def one(p, carry):
            page = tbl_ref[row * tbl_width + first + step * pages + p]
            for copy in copies(page, slot, p):
                copy.start()
            return carry
        lax.fori_loop(0, jnp.minimum(live - step * pages, pages), one, 0)

    def attend(load_q, n_rows, per_head):
        """Queries ``load_q(j) [n_rows, 2 hd]`` of KV pair ``j``, row ``r``
        at position ``pos + r % per_head``, over the row's keys up to ``pos
        + nv``; leaves the weighted sums in ``acc_ref[:, :n_rows]`` and
        their weights' sums in ``l_ref``. Key steps count from the row's
        first live page."""
        first_page, live_pages = live_range(b)
        last = (live_pages + pages - 1) // pages
        slot0, then = plan_ref[n_rows_grid + b], plan_ref[2 * n_rows_grid + b]

        rows = slice(0, n_rows)
        m_ref[:, rows] = jnp.full((pairs, n_rows, LANES), NEG_INF, F32)
        l_ref[:, rows] = jnp.zeros((pairs, n_rows, LANES), F32)
        acc_ref[:, rows] = jnp.zeros((pairs, n_rows, pw), F32)

        @pl.when(b == plan_ref[3 * n_rows_grid])
        def _first_copy():
            start_copies(b, 0, slot0)

        def key_step(step, carry):
            """Start the copies of the step after (the row's own, or after
            its last the next row's first), await this step's, and take its
            keys into every pair's running max, sum and weighted sum."""
            slot = (slot0 + step) % 2

            @pl.when(step + 1 < last)
            def _next_copy():
                start_copies(b, step + 1, 1 - slot)

            @pl.when((step + 1 == last) & (then < n_rows_grid))
            def _next_rows_copy():
                start_copies(then, 0, 1 - slot)

            def wait(p, carry):
                for copy in copies(0, slot, p):   # a wait needs only the size
                    copy.wait()
                return carry
            lax.fori_loop(
                0, jnp.minimum(live_pages - step * pages, pages), wait, 0)

            qpos = pos
            if per_head > 1:
                qpos += lax.broadcasted_iota(
                    jnp.int32, (n_rows, 1), 0) % per_head
            kpos = (first_page + step * pages) * bs \
                + lax.broadcasted_iota(jnp.int32, (1, keys), 1)
            seen = kpos <= qpos
            if window:
                seen = seen & (kpos > qpos - window)
            seen = jnp.broadcast_to(seen, (n_rows, keys))

            def one_pair(j, carry):
                pair = lanes(j)
                m_new, l_new, acc_ref[j, rows] = _pair_step(
                    load_q(j), kbuf[slot, :, pair], vbuf[slot, :, pair], seen,
                    m_ref[j, rows, 0:1], l_ref[j, rows, 0:1],
                    acc_ref[j, rows], scale=scale)
                m_ref[j, rows] = jnp.broadcast_to(m_new, (n_rows, LANES))
                l_ref[j, rows] = jnp.broadcast_to(l_new, (n_rows, LANES))
                return carry
            lax.fori_loop(0, pairs, one_pair, 0, unroll=True)
            return carry

        lax.fori_loop(0, last, key_step, 0)

    @pl.when(nv != 1)
    def _no_token():
        ot_ref[...] = jnp.zeros_like(ot_ref)

    @pl.when(nv == 1)
    def _token_row():
        attend(lambda j: qt_ref[0, j], 2 * g8, 1)

        def write(j, carry):
            ot_ref[0, j] = epilogue(j, slice(0, g8), slice(g8, 2 * g8))
            return carry
        lax.fori_loop(0, pairs, write, 0, unroll=True)

    @pl.when(nv > 1)
    def _chunk_row():
        def head_rows(i, s):
            """Where head ``s`` of query pair ``i`` lies in its KV pair's
            rows of ``qs_ref`` / ``acc_ref``."""
            return pl.ds(pl.multiple_of((s * g + i % g) * c, c), c)

        def place(i, carry):
            halves = _halves(q_ref[0, :, lanes(i)])
            for s in range(2):
                qs_ref[i // g, head_rows(i, s), :] = halves[s]
            return carry
        lax.fori_loop(0, pairs * g, place, 0)
        attend(lambda j: qs_ref[j], 2 * g * c, c)

        def write(i, carry):
            o_ref[0, :, lanes(i)] = epilogue(
                i // g, head_rows(i, 0), head_rows(i, 1)).astype(o_ref.dtype)
            return carry
        # (these two loops stay loops: a row that feeds a chunk is one step
        # in eight, and unrolled they double the kernel's lowering)
        lax.fori_loop(0, pairs * g, write, 0)


@functools.partial(jax.jit, static_argnames=(
    "window", "heads", "kv_heads", "eps", "keys", "interpret"))
def _diff_attention_pallas(q, k_pool, v_pool, tables, pos, nvalid, lam,
                           lam_init, subln, *, window: int, heads: int,
                           kv_heads: int, eps: float, keys: int,
                           interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, c, width = q.shape
    hd = width // heads
    pw, pairs, g = 2 * hd, kv_heads // 2, heads // kv_heads
    bs, kvw = k_pool.shape[1:]
    m = tables.shape[1]
    pages = min(max(1, keys // bs), m)
    dtype = k_pool.dtype
    # a chunk's queries are whole sublane tiles of the row axis
    cp = -(-c // SUBLANES) * SUBLANES
    qc = jnp.pad(q.astype(dtype), ((0, 0), (0, cp - c), (0, 0)))
    # a token row's heads: head (j, gi, s) in row s * g8 + gi of pair j, in
    # half s of the lanes
    g8 = -(-g // 8) * 8
    qt = _own_half(q[:, 0].astype(dtype).reshape(b, pairs, g, 2, hd))
    qt = jnp.pad(qt.transpose(0, 1, 3, 2, 4),           # [B, j, s, g, 2 hd]
                 ((0, 0), (0, 0), (0, 0), (0, g8 - g), (0, 0)))
    qt = qt.reshape(b, pairs, 2 * g8, pw)
    consts = jnp.zeros((8, pw), F32).at[0].set(subln.astype(F32)) \
        .at[1].set(lam).at[2].set(1.0 - lam_init)
    pos, nvalid = pos.astype(jnp.int32), nvalid.astype(jnp.int32)
    row = jnp.arange(b, dtype=jnp.int32)
    # the last row up to each that feeds a chunk (none yet: row 0)
    src = lax.cummax(jnp.where(nvalid > 1, row, 0))
    # the copies' plan: the buffer of a row's first key step (its steps
    # alternate from there), the next row that feeds anything (none: B),
    # and the first
    first = jnp.maximum(pos - window + 1, 0) // bs if window else 0
    steps = jnp.where(nvalid > 0,
                      -(-(-(-(pos + nvalid) // bs) - first) // pages), 0)
    feeds = lax.cummin(jnp.where(nvalid > 0, row, b), reverse=True)
    plan = jnp.concatenate([src, (jnp.cumsum(steps) - steps) % 2,
                            feeds[1:], jnp.full((1,), b, jnp.int32),
                            feeds[:1]]).astype(jnp.int32)
    rows = max(2 * g * cp, 2 * g8)
    row_block = lambda b_, *_: (b_, 0, 0, 0)
    chunk_block = lambda b_, tbl, pos_, nv, plan_: (plan_[b_], 0, 0)
    kernel = functools.partial(
        _diff_kernel, pages=pages, tbl_width=m, g=g, hd=hd, window=window,
        eps=eps)
    tok, chunk = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, pairs, 2 * g8, pw), row_block),
                pl.BlockSpec((1, cp, width), chunk_block),
                pl.BlockSpec((8, pw), lambda b_, *_: (0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec((1, pairs, g8, pw), row_block),
                pl.BlockSpec((1, cp, width), chunk_block),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, pages * bs, kvw), dtype),
                pltpu.VMEM((2, pages * bs, kvw), dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((pairs, 2 * g * cp, pw), dtype),
                pltpu.VMEM((pairs, rows, LANES), F32),
                pltpu.VMEM((pairs, rows, LANES), F32),
                pltpu.VMEM((pairs, rows, pw), F32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, pairs, g8, pw), F32),
                   jax.ShapeDtypeStruct((b, cp, width), q.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        name="diff_attention_fwd",
        interpret=interpret,
    )(tables.reshape(-1).astype(jnp.int32), pos, nvalid, plan, qt, qc, consts,
      k_pool, v_pool)
    tok = tok[:, :, :g].reshape(b, 1, width).astype(q.dtype)
    return jnp.where((nvalid > 1)[:, None, None], chunk[:, :c],
                     jnp.pad(tok, ((0, 0), (0, c - 1), (0, 0))))
