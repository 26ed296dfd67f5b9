"""State-space mixer of the serve step (Mamba-1: a depthwise causal conv and
a selective scan) over the step's ROWS as they are, and the gated memory
unit that reads its output.

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
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

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
