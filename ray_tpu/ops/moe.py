"""Mixture-of-experts: top-k routing + GShard-style dense dispatch.

Absent from the reference (SURVEY §2.4: expert parallel = "absent"). The
TPU-native formulation keeps everything as static-shape einsums so the MXU
does the dispatch: tokens are routed into a [experts, capacity] buffer with
one-hot dispatch/combine tensors (Switch/GShard style) rather than gather/
scatter, and expert parallelism is one ``all_to_all`` over the ``ep`` mesh
axis when the expert dim is sharded.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import _pallas_interpret
from ray_tpu.ops.expert_mlp import ACTIVATIONS, expert_mlp_pairs
from ray_tpu.ops.expert_mlp import impl_for as expert_mlp_impl_for
from ray_tpu.parallel.sharding import constrain


class RouterOutput(NamedTuple):
    dispatch: jax.Array      # [tokens, experts, capacity] one-hot-ish f32
    combine: jax.Array       # [tokens, experts, capacity] weights
    aux_loss: jax.Array      # load-balancing loss (scalar)


def top_k_router(
    logits: jax.Array,
    *,
    num_experts: int,
    k: int = 2,
    capacity_factor: float = 1.25,
) -> RouterOutput:
    """Route tokens to top-k experts with a fixed per-expert capacity.

    ``logits``: [tokens, experts]. Tokens over capacity are dropped (their
    combine weight is zero) — standard Switch behavior, keeps shapes static.
    """
    tokens = logits.shape[0]
    capacity = max(1, int(capacity_factor * tokens * k / num_experts))

    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    # load-balancing aux loss (Switch eq. 4): E * sum_e f_e * p_e
    _, top_idx = jax.lax.top_k(probs, k)  # [tokens, k]

    dispatch = jnp.zeros((tokens, num_experts, capacity), jnp.float32)
    combine = jnp.zeros((tokens, num_experts, capacity), jnp.float32)
    # Fill choices sequentially so earlier-choice tokens win capacity slots.
    position_in_expert = jnp.zeros((num_experts,), jnp.int32)
    for choice in range(k):
        idx = top_idx[:, choice]                       # [tokens]
        onehot = jax.nn.one_hot(idx, num_experts)      # [tokens, experts]
        # position of each token within its expert's queue for this choice
        pos = jnp.cumsum(onehot, axis=0) - 1 + position_in_expert[None, :]
        position_in_expert = position_in_expert + jnp.sum(
            onehot, axis=0
        ).astype(jnp.int32)
        pos_tok = jnp.sum(pos * onehot, axis=1).astype(jnp.int32)  # [tokens]
        in_cap = pos_tok < capacity
        gate = jnp.sum(probs * onehot, axis=1) * in_cap            # [tokens]
        slot = jax.nn.one_hot(pos_tok, capacity) * in_cap[:, None]
        dispatch = dispatch + onehot[:, :, None] * slot[:, None, :]
        combine = combine + gate[:, None, None] * onehot[:, :, None] * slot[:, None, :]

    frac_routed = jnp.mean(
        jax.nn.one_hot(top_idx[:, 0], num_experts), axis=0
    )
    mean_prob = jnp.mean(probs, axis=0)
    aux_loss = num_experts * jnp.sum(frac_routed * mean_prob)
    return RouterOutput(dispatch, combine, aux_loss)


def moe_layer_dense(
    x: jax.Array,
    router_w: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    k: int = 2,
    capacity_factor: float = 1.25,
) -> Tuple[jax.Array, jax.Array]:
    """MoE SwiGLU block. x: [B, L, D]; expert weights: [E, D, F] / [E, F, D].

    Returns (output [B, L, D], aux_loss). Einsum-only dispatch — with the E
    dim sharded on the ``ep`` mesh axis, XLA inserts the all_to_all pair.
    """
    b, l, d = x.shape
    e = w_gate.shape[0]
    # Pin the flattened token dim to "tokens" = (dp, fsdp, sp). Without
    # this, the combine output inherits D:fsdp from w_down and the caller's
    # activation-layout constraint forces the SPMD partitioner into an
    # involuntary full rematerialization (MULTICHIP_r02). The layout
    # matches (batch, seq) exactly when sp == 1 or the per-device batch
    # block is 1; otherwise entry/exit cost one all-to-all — still far
    # cheaper than replicating the tensor, and of a piece with the
    # all-to-alls MoE dispatch does anyway under real expert parallelism.
    xt = constrain(x.reshape(b * l, d), ("tokens", None))
    logits = xt.astype(jnp.float32) @ router_w.astype(jnp.float32)  # [T, E]
    route = top_k_router(logits, num_experts=e, k=k, capacity_factor=capacity_factor)
    # [T, E, C] x [T, D] -> [E, C, D]
    expert_in = jnp.einsum("tec,td->ecd", route.dispatch, xt.astype(jnp.float32))
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, w_gate.astype(jnp.float32)))
    up = jnp.einsum("ecd,edf->ecf", expert_in, w_up.astype(jnp.float32))
    expert_out = jnp.einsum("ecf,efd->ecd", gate * up, w_down.astype(jnp.float32))
    out = jnp.einsum("tec,ecd->td", route.combine, expert_out)
    out = constrain(out, ("tokens", None))
    return out.reshape(b, l, d).astype(x.dtype), route.aux_loss


def route_top_k(x: jax.Array, router_w: jax.Array, *, k: int,
                norm_topk: bool, scoring: str = "softmax",
                bias: jax.Array | None = None,
                scale: float = 1.0) -> Tuple[jax.Array, jax.Array]:
    """The experts each token goes to and their weights: router logits and
    scores in float32 (at full matmul precision: a flipped expert is a
    large change for a small product), top-``k`` of the scores,
    renormalised to sum to one with ``norm_topk``. ``scoring``:
    ``"softmax"`` over the experts, or ``"sigmoid"`` of each logit
    (DeepSeek-V3's ``noaux_tc``), where the top-``k`` is taken of ``score +
    bias`` (``bias`` [E], the load balancer's: it SELECTS and does not
    weigh) and the chosen scores are the weights; ``scale`` multiplies them
    (``routed_scaling_factor``). x: [T, D]. Returns (weights [T, k] float32,
    experts [T, k] int32)."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        chosen = scores if bias is None else scores + bias.astype(jnp.float32)
        _, top_e = jax.lax.top_k(chosen, k)
        top_p = jnp.take_along_axis(scores, top_e, axis=-1)
        if norm_topk:
            top_p = top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + 1e-20)
        return top_p * scale, top_e
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)
    if norm_topk:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_p, top_e


class Route(NamedTuple):
    """Where a step's tokens go, as :func:`moe_experts` takes it. Made from
    the tensor the ROUTER reads, which need not be the tensor the experts
    multiply (:func:`moe_route`)."""
    weights: jax.Array      # [T, k] float32, in the router's order
    experts: jax.Array      # [T, k] int32 in the HELD experts' numbering; E
                            # (past the last): not held here, or padding
    order: jax.Array        # [T * k] pairs (token * k + choice) by expert
    counts: jax.Array       # [E] int32 tokens per held expert


def moe_route(
    x: jax.Array,
    router_w: jax.Array,
    *,
    k: int,
    norm_topk: bool = False,
    valid: jax.Array | None = None,
    scoring: str = "softmax",
    bias: jax.Array | None = None,
    scale: float = 1.0,
    first: int | None = None,
    held: int | None = None,
) -> Route:
    """The route of ``x [T, D]`` through ``router_w [D, E_all]``:
    :func:`route_top_k`, the experts renumbered to the ``held`` ones from
    ``first`` (THE SHARE of :func:`moe_layer_dropless`; ``first`` ``None``:
    all of them), the tokens ``valid`` marks as padding routed nowhere, the
    T * k pairs sorted by expert and counted. ``x`` is whatever the model's
    router reads: the experts' own input, or another tensor at another place
    in the layer (a router ahead of the attention reads the layer's normed
    input)."""
    t = x.shape[0]
    e = router_w.shape[-1]
    with jax.named_scope("moe_router"):
        top_p, top_e = route_top_k(x, router_w, k=k, norm_topk=norm_topk,
                                   scoring=scoring, bias=bias, scale=scale)
        if first is not None:
            # experts in the held ones' own numbering; the rest sort last
            e = held
            top_e = top_e - first
            top_e = jnp.where((top_e >= 0) & (top_e < e), top_e, e)
        if valid is not None:
            top_e = jnp.where(valid[:, None], top_e, e)    # sorts last
        flat_e = top_e.reshape(t * k)
        order = jnp.argsort(flat_e, stable=True)            # pair -> sorted row
        counts = jnp.zeros((e + 1,), jnp.int32).at[flat_e].add(1)[:e]
    return Route(top_p, top_e, order, counts)


def moe_experts(
    x: jax.Array,
    route: Route,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    layer: jax.Array | None = None,
    act: str = "silu",
) -> jax.Array:
    """The experts' weighted sum over ``x [T, D]`` along ``route``: each
    routed pair's ``(act(x W_gate) * (x W_up)) W_down`` in one of the two
    forms :func:`moe_layer_dropless` describes, then a token's ``k`` pairs
    summed in the router's order, weighted in float32. ``act``:
    :data:`ray_tpu.ops.expert_mlp.ACTIVATIONS`. Returns ``[T, D]`` in x's
    dtype."""
    t, d = x.shape
    top_p, top_e, order, counts = route
    k = top_e.shape[1]
    e = counts.shape[0]
    kernel = expert_mlp_impl_for(w_gate) == "pallas"
    with jax.named_scope("moe_experts"):
        if layer is not None:
            w_gate, w_up, w_down = (w.reshape(-1, *w.shape[2:])
                                    for w in (w_gate, w_up, w_down))
        if kernel:
            # rows in and out by index: only a routed pair's row is written
            pairs = expert_mlp_pairs(
                x, order, counts, 0 if layer is None else layer * e,
                w_gate, w_up, w_down, k=k,
                interpret=_pallas_interpret(), act=act).reshape(t, k, d)
            pairs = jnp.where((top_e < e)[:, :, None], pairs, 0.0)
        else:
            groups = counts
            if layer is not None:
                groups = jax.lax.dynamic_update_slice(
                    jnp.zeros((w_gate.shape[0],), jnp.int32), counts,
                    (layer * e,))
            xs = x[order // k]                               # [T*k, D]
            gate = jax.lax.ragged_dot(xs, w_gate, groups,
                                      preferred_element_type=jnp.float32)
            up = jax.lax.ragged_dot(xs, w_up, groups,
                                    preferred_element_type=jnp.float32)
            mid = (ACTIVATIONS[act](gate) * up).astype(x.dtype)
            down = jax.lax.ragged_dot(mid, w_down, groups,
                                      preferred_element_type=jnp.float32)
            # rows past the last group belong to no expert: whatever the
            # grouped matmul left there is not read
            routed = jnp.arange(t * k) < jnp.sum(counts)
            down = jnp.where(routed[:, None], down, 0.0)
            # back to pair order (a gather, not a scatter-add)
            pairs = down[jnp.argsort(order)].reshape(t, k, d)
        # the sum over a token's k experts in one fixed order
        out = jnp.sum(pairs * top_p[:, :, None], axis=1)
    return out.astype(x.dtype)


def moe_layer_dropless(
    x: jax.Array,
    router_w: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    k: int,
    norm_topk: bool = False,
    valid: jax.Array | None = None,
    layer: jax.Array | None = None,
    scoring: str = "softmax",
    bias: jax.Array | None = None,
    scale: float = 1.0,
    first: int | None = None,
    act: str = "silu",
) -> Tuple[jax.Array, jax.Array]:
    """Gated-MLP expert block (SwiGLU, or ReGLU with ``act="relu"``) with NO
    capacity: every token gets all ``k`` of its
    experts, so what a token gets back does not depend on which other rows
    share its step (the serve path's layer; training keeps
    :func:`moe_layer_dense`, whose one-hot dispatch partitions over ``ep``
    and carries the auxiliary loss). The composition of :func:`moe_route`
    over ``x`` and :func:`moe_experts`; a caller whose router reads another
    tensor than the experts multiply calls the two itself.

    x: [T, D]; expert weights [E, D, F] / [E, F, D], multiplied in their
    own type with float32 accumulation. The T*k (token, expert) pairs are
    sorted by expert; ``valid`` [T] marks real tokens: the others (a step's
    padding) are routed nowhere and get zeros. Only the rows of experts
    that were hit are computed and only their weights are read, in one of
    two forms (:func:`ray_tpu.ops.expert_mlp.expert_mlp_impl`: backend, the
    weights' dtype, whether ``D`` and ``F`` are whole lanes):

    - the Pallas kernel of :mod:`ray_tpu.ops.expert_mlp` takes the sorted
      order and the counts, copies each hit expert's OWN rows in by token
      index, keeps ``gate`` and ``up`` in VMEM and writes each routed
      pair's float32 ``down`` row at the pair's index: nothing of width
      ``T * k`` is gathered, and its cost follows the experts hit;
    - elsewhere (every CPU run) one grouped matmul each for gate, up and
      down (``lax.ragged_dot``) over the sorted rows ``[T * k, D]``,
      gathered there and back.

    Either way a token's ``k`` pairs are summed in the order the router
    gave them, weighted in float32.

    With ``layer`` (a traced index) the expert weights are whole STACKS
    ``[L, E, ...]`` and the layer's experts are groups ``layer * E ..`` of
    ``L * E``, every other group empty: a layer scan that sliced its experts
    out of the stack would copy them (1.2 GB a layer at 128 experts of
    2048 x 768, a millisecond a matrix on a v5e), and an empty group costs
    nothing.

    THE SHARE (``first``): the router is as wide as the published model
    (``router_w [D, E_all]``) and picks ``k`` of all its experts, while the
    weights hold the ``E`` experts ``first .. first + E`` only, one chip's
    of a layer divided over several. The pairs whose expert is held are
    computed, the others left out (their part of the sum is another chip's;
    nothing here stands in for it), and the weights are what the whole
    router gave. ``scoring``, ``bias``, ``scale``: :func:`route_top_k`.
    Returns (output [T, D] in x's dtype, tokens per HELD expert [E] int32)."""
    route = moe_route(
        x, router_w, k=k, norm_topk=norm_topk, valid=valid, scoring=scoring,
        bias=bias, scale=scale, first=first, held=w_gate.shape[-3])
    out = moe_experts(x, route, w_gate, w_up, w_down, layer=layer, act=act)
    return out, route.counts
