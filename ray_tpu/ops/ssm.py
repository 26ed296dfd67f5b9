"""State-space mixers of the serve step (Mamba-1 and Mamba-2: a depthwise
causal conv and a selective scan) over the step's ROWS as they are, and the
gated memory unit that reads Mamba-1's output.

A row of the paged step is one request: a decoding row feeds one position, a
prefilling row a chunk, an idle row none. :func:`ssm_rows` takes each row's
state from its slot of the state pool (the last ``k - 1`` conv inputs and the
scan's ``h``, float32), runs the row's real positions through conv and scan,
and hands back the state after the row's LAST REAL position: padding
positions leave it untouched, a row that feeds nothing gets back what it had,
and a row at position 0 (a request's first chunk) starts from zero whatever
its slot held before.

    u'  = SiLU(conv1d(u) + b_conv)                     causal, depthwise
    r, B, C = u' W_x                                   [r | n | n]
    D_t = softplus(r W_dt + b_dt)
    h_t = exp(D_t A) * h_{t-1} + (D_t u'_t) (x) B_t    A = -exp(A_log)
    y_t = h_t . C_t + D * u'_t

The state is laid out ``[n, d_inner]`` (``d_inner`` on the lanes): sixteen
states on the lanes would leave seven eighths of every vector register
empty. All ``jax.numpy``: the scan is a loop over the chunk's positions that
stops at the longest row's last real one (one turn in a step of decoding
rows), every row in parallel.

WHICH RECURRENCE IS WHICH. :func:`ssm_rows` is **Mamba-1** (the SambaY
layout, :mod:`ray_tpu.models.hybrid`): a decay a CHANNEL and state
(``A [n, d_inner]``), ``B``, ``C`` and the step from a projection of the
conv's output, a small state (16 a channel) walked position by position.
:func:`mamba2_rows` is **Mamba-2** (the parallel layout,
:mod:`ray_tpu.models.parallel_hybrid`): a scalar decay a HEAD, ``B`` and
``C`` shared by the heads of a GROUP, the conv over ``x | B | C`` together,
and a state of ``P x N`` a head (128 x 256: 4 MB a layer a request in
float32) that a position-by-position loop cannot carry, because a turn reads
and writes all of it. It has two forms in one program, chosen a ROW:

    one step (a row that feeds one position)
      S    = exp(D_t A) S + D_t x_t (x) B_t                 [P, N] a head
      y_t  = S C_t + D x_t
    block form, SSD (a row that feeds more), l_t = sum_{s<=t} D_s A
      y_t  = exp(l_t) S_0 C_t
             + sum_{s<=t} exp(l_t - l_s) D_s (C_t . B_s) x_s + D x_t
      S_T  = exp(l_T) S_0 + sum_s exp(l_T - l_s) D_s x_s (x) B_s

The block form is the recurrence over the block, written as matrix products:
the state crosses HBM once a row a layer a step whatever the block's length
(``tests/test_parallel_hybrid_serve.py`` holds the two together). On a TPU
the one step is :mod:`ray_tpu.ops.ssd_step`'s kernel, which walks the live
rows' states in the pool and reads out before a tile leaves VMEM. Both keep
:func:`ssm_rows`'s contract. The Mamba-2 state is laid out ``[H, P, N]``
with the ``N`` = 256 states on the lanes and a head's ``P`` = 128 channels
on the sublanes: whole lanes and whole tiles, the contraction of the
read-out (over ``N``) along the lanes and that of the update (over the
block's positions) outside the state. :func:`gated_rms_norm` is the norm
after Mamba-2's scan.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.ssd_step import impl_for as ssd_step_impl_for
from ray_tpu.ops.ssd_step import ssd_step_live

F32 = jnp.float32


def ssm_rows(u, conv_state, h, lp, nvalid, fresh):
    """``u [B, C, di]``: the rows' conv inputs; ``conv_state [B, k - 1,
    di]`` and ``h [B, n, di]``: their state (float32); ``lp``: the layer's
    ``conv_w [k, di]``, ``conv_b``, ``w_x [di, r + 2n]``, ``w_dt [r, di]``,
    ``b_dt``, ``A_log [n, di]``, ``D``; ``nvalid [B]``: real positions a
    row (0: the row keeps its state); ``fresh [B]``: rows that start from
    zero. Returns ``(y [B, C, di] float32, conv_state, h)``."""
    with jax.named_scope("ssm_scan"):
        b, c, di = u.shape
        k = lp["conv_w"].shape[0]
        n = lp["A_log"].shape[0]
        r = lp["w_dt"].shape[0]
        dt = u.dtype
        keep_old = ~fresh[:, None, None]
        conv_state = jnp.where(keep_old, conv_state, 0.0)
        h = jnp.where(keep_old, h, 0.0)
        seq = jnp.concatenate([conv_state, u.astype(F32)], axis=1)
        conv = lp["conv_b"].astype(F32) + sum(
            seq[:, j:j + c] * lp["conv_w"][j].astype(F32) for j in range(k))
        up = jax.nn.silu(conv)                                # [B, C, di]
        # the k - 1 inputs before the row's next position
        idx = nvalid[:, None] + jnp.arange(k - 1)[None, :]
        new_conv = jnp.take_along_axis(seq, idx[:, :, None], axis=1)
        xp = jnp.einsum("bcd,de->bce", up.astype(dt), lp["w_x"].astype(dt),
                        preferred_element_type=F32)
        rr, bm, cm = xp[..., :r], xp[..., r:r + n], xp[..., r + n:]
        delta = jax.nn.softplus(
            jnp.einsum("bcr,rd->bcd", rr.astype(dt), lp["w_dt"].astype(dt),
                       preferred_element_type=F32) + lp["b_dt"].astype(F32))
        a = -jnp.exp(lp["A_log"].astype(F32))                 # [n, di]
        # the loop's arrays position-major: a position's slice is then one
        # contiguous piece (sliced along axis 1 the update of ``y`` rewrote
        # the whole array every turn)
        steps = tuple(jnp.swapaxes(x, 0, 1)
                      for x in (delta, delta * up, bm, cm))

        def one(t, carry):
            h, y = carry
            d_t, du_t, b_t, c_t = (
                lax.dynamic_index_in_dim(x, t, 0, keepdims=False)
                for x in steps)
            new = jnp.exp(d_t[:, None, :] * a[None]) * h \
                + du_t[:, None, :] * b_t[:, :, None]
            yt = jnp.sum(new * c_t[:, :, None], axis=1)
            h = jnp.where((t < nvalid)[:, None, None], new, h)
            return h, lax.dynamic_update_index_in_dim(y, yt, t, 0)

        h, y = lax.fori_loop(0, jnp.max(nvalid), one,
                             (h, jnp.zeros((c, b, di), F32)))
        y = jnp.swapaxes(y, 0, 1)
        y = y + lp["D"].astype(F32) * up
    return y, new_conv, h


def gmu(x, m, w1, w2):
    """Gated memory unit: ``(m * SiLU(x W_1)) W_2``, ``m`` the memory (the
    last state-space layer's scan output of the same token)."""
    with jax.named_scope("gmu"):
        dt = x.dtype
        g = jnp.einsum("bld,de->ble", x, w1.astype(dt),
                       preferred_element_type=F32)
        gated = (m.astype(F32) * jax.nn.silu(g)).astype(dt)
        return jnp.einsum("ble,ed->bld", gated, w2.astype(dt))


# -- Mamba-2 -------------------------------------------------------------------

#: the block form's small products run at full float32 precision (the MXU's
#: default rounds float32 operands to bfloat16): the state is carried over
#: hundreds of tokens and read by every later one
_EXACT = lax.Precision.HIGHEST


def _conv_rows(u, conv_state, conv_w, conv_b, nvalid):
    """Depthwise causal conv of ``u [B, C, W]`` behind each row's carried
    ``k - 1`` inputs. -> (SiLU(conv) float32, the ``k - 1`` inputs before the
    row's next position)."""
    c = u.shape[1]
    k = conv_w.shape[0]
    seq = jnp.concatenate([conv_state, u.astype(F32)], axis=1)
    conv = conv_b.astype(F32) + sum(
        seq[:, j:j + c] * conv_w[j].astype(F32) for j in range(k))
    idx = nvalid[:, None] + jnp.arange(k - 1)[None, :]
    return jax.nn.silu(conv), jnp.take_along_axis(seq, idx[:, :, None],
                                                  axis=1)


def ssd_step(s, x, bm, cm, delta, a, d_skip):
    """ONE turn of Mamba-2's recurrence for every row: ``s [B, G, K, P, N]``
    (``K`` heads a group), ``x [B, G, K, P]``, ``bm``, ``cm [B, G, N]``,
    ``delta [B, G, K]``, ``a``, ``d_skip [G, K]``. -> (y [B, G, K, P], s)."""
    decay = jnp.exp(delta * a)[..., None, None]
    s = decay * s + (delta[..., None] * x)[..., None] \
        * bm[:, :, None, None, :]
    y = jnp.sum(s * cm[:, :, None, None, :], axis=-1)
    return y + d_skip[..., None] * x, s


def ssd_step_slots(pool, first, single, fresh, x, bm, cm, delta, a, d_skip):
    """One turn for the rows of ``single [B]`` by ONE pass of
    :func:`ssd_step` over all ``B`` slots of the layer whose states are
    ``pool [rows, H, P, N]`` from row ``first`` (a row that feeds nothing,
    or a block, keeps what the pass finds; a ``fresh`` single row starts from
    zero): the form that runs wherever
    :func:`ray_tpu.ops.ssd_step.ssd_step_live` does not, and the reference
    it is compared with. -> (pool, y [B, G, K, P])."""
    b, g, k, p = x.shape
    s = lax.dynamic_slice_in_dim(pool, first, b, 0).reshape(b, g, k, p, -1)
    y, s1 = ssd_step(
        jnp.where((fresh & single)[:, None, None, None, None], 0.0, s),
        x, bm, cm, delta, a, d_skip)
    s = jnp.where(single[:, None, None, None, None], s1, s)
    return lax.dynamic_update_slice_in_dim(
        pool, s.reshape(b, *pool.shape[1:]), first, 0), y


def ssd_block(s0, x, bm, cm, delta, a, d_skip):
    """Mamba-2's block (SSD) form over ONE row's block of ``T`` positions
    with the state ``s0 [G, K, P, N]`` carried in: ``x [T, G, K, P]``,
    ``bm``, ``cm [T, G, N]``, ``delta [T, G, K]`` (0 at a position that is
    padding: it then neither decays nor feeds the state), ``a``, ``d_skip
    [G, K]``. Equals ``T`` turns of :func:`ssd_step`; the state is read once
    and written once. -> (y [T, G, K, P], s_T)."""
    t = x.shape[0]
    ell = jnp.cumsum(delta * a, axis=0)                        # [T, G, K]
    # within the block: position t reads every s <= t of the block
    cb = jnp.einsum("tgn,sgn->gts", cm, bm, precision=_EXACT)  # [G, T, T]
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    gap = ell[:, None] - ell[None, :]                          # [T, S, G, K]
    weight = jnp.exp(jnp.where(causal[:, :, None, None], gap, -jnp.inf)) \
        * delta[None] * jnp.moveaxis(cb, 0, -1)[..., None]
    y = jnp.einsum("tsgk,sgkp->tgkp", weight, x, precision=_EXACT)
    # the state carried in, decayed to each position
    y = y + jnp.exp(ell)[..., None] * jnp.einsum(
        "tgn,gkpn->tgkp", cm, s0, precision=_EXACT)
    # and the state handed on
    to_end = jnp.exp(ell[-1][None] - ell) * delta               # [T, G, K]
    s = jnp.exp(ell[-1])[..., None, None] * s0 + jnp.einsum(
        "tgkp,tgn->gkpn", to_end[..., None] * x, bm, precision=_EXACT)
    return y + d_skip[..., None] * x, s


def mamba2_rows(xbc, dt, conv_state, pool, first, lp, nvalid, fresh, *,
                heads: int, head_dim: int, groups: int, states: int):
    """Mamba-2's conv and scan over the step's rows. ``xbc [B, C, W]`` (``W
    = H P + 2 G N``: the conv's channels, ``x | B | C``) and ``dt [B, C,
    H]`` from the in-projection; ``conv_state [B, k - 1, W]`` float32;
    ``pool [rows, H, P, N]`` float32: the state pool of every layer, this
    layer's ``B`` rows from row ``first`` (it is updated in place: a layer's
    share is never sliced out whole); ``lp``: ``conv_w [k, W]``, ``conv_b``,
    ``dt_bias``, ``A_log``, ``D [H]``; ``nvalid``, ``fresh`` as
    :func:`ssm_rows` takes them. A row that feeds ONE position takes one turn
    of the recurrence; a row that feeds more takes the block form over the
    chunk, a row a turn of a loop as long as there are such rows. The one
    turn has two forms, chosen from what the code can observe
    (:func:`ray_tpu.ops.ssd_step.ssd_step_impl`: backend, the pool's dtype,
    whole lanes of states), never from a model's name or a setting: a Pallas
    kernel that walks the LIVE single rows' states in the pool, each read
    and written once, and elsewhere (CPU, the tests' toy widths)
    :func:`ssd_step` in one pass over all of the layer's slots, which is also
    the reference the kernel is compared with. Returns ``(y [B, C, H P]
    float32, conv_state, pool)``."""
    b, c, _ = xbc.shape
    h, p, g, n = heads, head_dim, groups, states
    k = h // g
    with jax.named_scope("ssd_conv"):
        conv_state = jnp.where(fresh[:, None, None], 0.0, conv_state)
        act, new_conv = _conv_rows(xbc, conv_state, lp["conv_w"],
                                   lp["conv_b"], nvalid)
        x = act[..., :h * p].reshape(b, c, g, k, p)
        bm = act[..., h * p:h * p + g * n].reshape(b, c, g, n)
        cm = act[..., h * p + g * n:].reshape(b, c, g, n)
    with jax.named_scope("ssd_scan"):
        a = -jnp.exp(lp["A_log"].astype(F32)).reshape(g, k)
        d_skip = lp["D"].astype(F32).reshape(g, k)
        real = jnp.arange(c)[None, :] < nvalid[:, None]
        delta = jnp.where(
            real[..., None],
            jax.nn.softplus(dt.astype(F32) + lp["dt_bias"].astype(F32)),
            0.0).reshape(b, c, g, k)
        # rows that feed more than one position, first: the block form, one
        # row a turn, its state read from and written to the pool's row
        blocks = nvalid > 1
        order = jnp.argsort(~blocks, stable=True)

        def one_block(j, carry):
            pool, y = carry
            r = order[j]
            at = lambda v: lax.dynamic_index_in_dim(v, r, 0, keepdims=False)
            s0 = jnp.where(at(fresh), 0.0, lax.dynamic_index_in_dim(
                pool, first + r, 0, keepdims=False).reshape(g, k, p, n))
            yr, s = ssd_block(s0, at(x), at(bm), at(cm), at(delta), a,
                              d_skip)
            pool = lax.dynamic_update_index_in_dim(
                pool, s.reshape(h, p, n), first + r, 0)
            return pool, lax.dynamic_update_index_in_dim(
                y, yr.reshape(c, h * p), r, 0)

        pool, y = lax.fori_loop(
            0, jnp.sum(blocks), one_block,
            (pool, jnp.zeros((b, c, h * p), F32)))
        # rows that feed one position: one turn, in the kernel that walks
        # the live ones in the pool as it lies, or in one pass over all of
        # the layer's slots
        single = nvalid == 1
        turn = (x[:, 0], bm[:, 0], cm[:, 0], delta[:, 0], a)
        if ssd_step_impl_for(pool) == "pallas":
            pool, y1 = ssd_step_live(pool, first, single, fresh, *turn)
            y1 = y1 + d_skip[..., None] * x[:, 0]
        else:
            pool, y1 = ssd_step_slots(pool, first, single, fresh, *turn,
                                      d_skip)
        y = y.at[:, 0].set(jnp.where(single[:, None], y1.reshape(b, h * p),
                                     y[:, 0]))
    return y, new_conv, pool


def gated_rms_norm(y, z, gain, *, groups: int, eps: float):
    """Mamba-2's norm after the scan: ``RMSNorm(y * SiLU(z); gain)`` with
    the mean square taken a GROUP (``groups`` equal parts of the last axis),
    the gate before the norm. Position-wise: ``y``, ``z [.., H P]``."""
    with jax.named_scope("ssd_gated_norm"):
        gated = y.astype(F32) * jax.nn.silu(z.astype(F32))
        parts = gated.reshape(*gated.shape[:-1], groups, -1)
        parts = parts * lax.rsqrt(
            jnp.mean(jnp.square(parts), axis=-1, keepdims=True) + eps)
        return parts.reshape(gated.shape) * gain.astype(F32)
