"""The serve layouts: ONE table of what a configuration keeps on the device
for a request, who may share or ship it, which kernels its step takes and
what a step of it counts.

Six layouts run on the paged serve step (``transformer._step_paged_impl``):

=================  =======================================  ==================
layout             configuration                            module
=================  =======================================  ==================
``uniform``        none of the keys below                   :mod:`.uniform`
``hybrid``         ``layer_kinds`` (SambaY)                 :mod:`.hybrid`
``parallel``       ``layer_kinds`` all ``"parallel"``       :mod:`.parallel_hybrid`
``linear_hybrid``  ``layer_kinds`` of ``"delta"``, ``"full"``  :mod:`.linear_hybrid`
``latent``         ``kv_lora_rank`` (MLA + held experts)    :mod:`.latent`
``windowed_moe``   GQA over dense and expert layers         :mod:`.windowed_moe`
=================  =======================================  ==================

:func:`layout_of` is the one place that turns a configuration into a layout
(``TransformerConfig``'s own properties stay for its validation and its
parameter counts). The model's doors (``models.init_params``,
``param_axes``, ``init_cache_paged``, the step) and the serve engine
(``serve/llm.py``: its constructor and the host's counters of a step) read
the record and name no layout. A seventh layout is a module and a row here.
Nothing registers a layout at run time, and no option picks one.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import (hybrid, latent, linear_hybrid, parallel_hybrid,
                            uniform, windowed_moe)
from ray_tpu.models.config import TransformerConfig
from ray_tpu.ops import (diff_attention, expert_mlp, latent_attention,
                         paged_attention, sparse_attention, ssd_step)

Params = Dict[str, Any]
F32 = jnp.float32


# -- the one parameter scaffold -------------------------------------------------

def draw(key, shape, how, c: TransformerConfig, dtype, draws=None):
    """One leaf, float32 draw cast to ``dtype`` (traceable): ``"gain"``
    (about 1), ``"bias"`` (about 0), ``("proj" | "out", fan_in)`` (normal at
    ``fan_in^-0.5``, output projections over ``sqrt(2 L)``), or a name of
    the layout's own ``draws`` (``name -> (key, shape, config) -> float32``)."""
    normal = lambda std, mean=0.0: \
        jax.random.normal(key, shape, F32) * std + mean
    if draws and how in draws:
        x = draws[how](key, shape, c)
    elif how == "gain":
        x = normal(0.1, 1.0)
    elif how == "bias":
        x = normal(0.1)
    else:
        kind, fan_in = how
        x = normal(fan_in ** -0.5
                   / ((2 * c.n_layers) ** 0.5 if kind == "out" else 1.0))
    return x.astype(dtype)


def _stacks(c: TransformerConfig, layout: "Layout"):
    """``[(path under params["layers"], what is folded into the layers' key
    or None for the key itself, depth, {leaf: (shape, axes, how)})]``: the
    stacks of a layout's tree from its ``block_shapes`` and ``segments``, in
    the three forms the layouts hold them: no segments (one stack, the
    layers' key itself), ``(segment, layers)``, and ``(segment, periods,
    {block name: block kind})`` with several blocks a period. The key of each
    is the layout's own, kept to the bit."""
    shapes = layout.block_shapes(c)
    if layout.segments is None:
        return [((), None, c.n_layers, shapes)]
    out = []
    for s, (seg, n, *blocks) in enumerate(layout.segments(c)):
        if blocks:
            out += [((seg, name), 8 * s + bi, n, shapes[kind])
                    for bi, (name, kind) in enumerate(blocks[0].items())]
        else:
            out.append(((seg,), s, n, shapes[seg]))
    return out


def _put(tree: Params, path: tuple, leaves: Params) -> None:
    for name in path:
        tree = tree.setdefault(name, {})
    tree.update(leaves)


def init_tree(rng: jax.Array, c: TransformerConfig, layout: "Layout"
              ) -> Params:
    """A layout's parameter tree from its ``block_shapes`` and ``segments``:
    every leaf stacked over its segment's layers, an embedding, the final
    norm (``final_norm_b`` where the layout's norms have a bias) and an
    untied head."""
    pdt = jnp.dtype(c.param_dtype)
    one = lambda key, shape, how: draw(key, shape, how, c, pdt, layout.draws)
    k_embed, k_norm, k_layers = jax.random.split(rng, 3)
    layers: Params = {}
    for path, fold, depth, leaves in _stacks(c, layout):
        keys = jax.random.split(
            k_layers if fold is None else jax.random.fold_in(k_layers, fold),
            len(leaves))
        _put(layers, path, {
            leaf: jax.vmap(lambda k: one(k, shape, how))(
                jax.random.split(key, depth))
            for key, (leaf, (shape, _, how)) in zip(keys, leaves.items())})
    params = {"embed": one(k_embed, (c.vocab_size, c.d_model), "bias") * 0.2,
              "layers": layers,
              "final_norm": one(k_norm, (c.d_model,), "gain")}
    if layout.norm_bias:
        params["final_norm_b"] = one(jax.random.fold_in(k_norm, 1),
                                     (c.d_model,), "bias")
    if not c.tie_embeddings:
        params["lm_head"] = draw(jax.random.fold_in(k_embed, 1),
                                 (c.d_model, c.vocab_size),
                                 ("proj", c.d_model), c, pdt)
    return params


def tree_axes(c: TransformerConfig, layout: "Layout") -> Params:
    """Logical axes matching :func:`init_tree` leaf for leaf."""
    layers: Params = {}
    for path, _, _, leaves in _stacks(c, layout):
        _put(layers, path, {leaf: ("layers",) + ax
                            for leaf, (_, ax, _) in leaves.items()})
    axes: Params = {"embed": ("vocab", "embed"), "layers": layers,
                    "final_norm": ("norm",)}
    if layout.norm_bias:
        axes["final_norm_b"] = ("norm",)
    if not c.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


# -- what a step counts ---------------------------------------------------------

class StepRows(NamedTuple):
    """What the host knows of a step before it runs: a value a ROW (a slot
    that holds a request), and the engine's geometry."""
    pos: np.ndarray            # tokens the row has cached
    nvalid: np.ndarray         # tokens the step feeds it
    blocks: np.ndarray         # blocks of its table in the full pool
    window_blocks: np.ndarray  # ... and of the window pool (0 without one)
    chunk: int                 # the grid's positions a row
    block_size: int
    table_width: int           # columns of the step's ``tables``
    kernels: Dict[str, str]    # ``Layout.kernels(config, cache)``


#: blocks of the table the step's attention has to read (each row's live
#: context; a window every layer shares moves the first block a row reads,
#: with mixed or global layers some layer reads from block 0) against the
#: blocks the table is wide; keys single-token rows read against their live
#: keys (the indexer's top-k in a sparse-attention model); the rows that fed
#: anything, and those of them that fed ONE token to an attention kernel,
#: which gives such a row a tile of its own (``kernels["attn_impl"]``)
_ATTENTION_COUNTERS = ("attn_blocks_live", "attn_blocks_table",
                       "attn_keys_live", "attn_keys_selected",
                       "attn_rows_attended", "attn_token_tile_rows")


def _count_attention(c: TransformerConfig, s: StepRows) -> Dict[str, int]:
    bs, window = s.block_size, c.uniform_window
    topk = c.index_topk if c.index_heads else 0
    first = np.maximum(s.pos - window + 1, 0) // bs if window else 0
    single = s.nvalid == 1
    seen = (np.minimum(s.pos + 1, window) if window else s.pos + 1)[single]
    return {
        "attn_blocks_live": int((-(-(s.pos + s.nvalid) // bs) - first).sum()),
        "attn_blocks_table": len(s.pos) * s.table_width,
        "attn_keys_live": int(seen.sum()),
        "attn_keys_selected": int(
            (np.minimum(seen, topk) if topk else seen).sum()),
        "attn_rows_attended": int((s.nvalid > 0).sum()),
        "attn_token_tile_rows":
            int(single.sum()) * (s.kernels["attn_impl"] == "pallas")}


#: with a sparse-attention indexer: the rows whose last query the step's
#: indexer scored (rows that fed a query past ``index_topk`` keys) and those
#: of them the kernel that reads the key pool through the table scored
#: (``kernels["indexer_impl"]``: all or none)
_INDEXER_COUNTERS = ("indexer_rows_scored", "indexer_kernel_rows")


def _count_indexer(c: TransformerConfig, s: StepRows) -> Dict[str, int]:
    if not c.index_heads:
        return {}
    rows = int(((s.nvalid > 0) & (s.pos + s.nvalid > c.index_topk)).sum())
    return {"indexer_rows_scored": rows,
            "indexer_kernel_rows":
                rows * (s.kernels["indexer_impl"] == "pallas")}


#: the (token, expert) pairs the router chose, over the expert layers
_EXPERT_COUNTERS = ("moe_pairs_routed",)


def _count_experts(c: TransformerConfig, s: StepRows) -> Dict[str, int]:
    if not c.num_experts:
        return {}
    return {"moe_pairs_routed": int(s.nvalid.sum()) * c.expert_top_k * (
        c.n_layers - c.dense_layers)}


#: state slots live: a layout with recurrent state keeps a request's by slot
_STATE_COUNTERS = ("state_slots_live",)


def _count_state(c: TransformerConfig, s: StepRows) -> Dict[str, int]:
    return {"state_slots_live": len(s.pos)}


#: with window layers and a pool several layers share: blocks the window
#: layers hold for the step's rows against what a table as wide as each
#: request's context holds, keys the shared-pool layers and the window layers
#: read by the program's rule (the shared pool's layers the whole context, a
#: window layer from the first query's window start), the rows whose
#: attention read the shared pool and those of them the kernel that reads it
#: through the table attended (``kernels["attn_impl"]``: all or none)
_WINDOW_COUNTERS = ("window_blocks_held", "window_blocks_full_table",
                    "shared_kv_keys_read", "window_keys_read",
                    "shared_kv_rows_attended", "shared_kv_kernel_rows")


def _count_windows(pool_layers):
    """``pool_layers(config) -> (window layers, shared-pool layers)``."""
    def count(c: TransformerConfig, s: StepRows) -> Dict[str, int]:
        n_window, n_shared = pool_layers(c)
        end = s.pos + s.nvalid
        rows = len(s.pos)
        return {
            "window_blocks_held": int(s.window_blocks.sum()),
            "window_blocks_full_table": int(s.blocks.sum()),
            "shared_kv_keys_read": n_shared * int(end.sum()),
            "window_keys_read": n_window * int((end - np.maximum(
                s.pos - c.sliding_window + 1, 0)).sum()),
            "shared_kv_rows_attended": rows,
            "shared_kv_kernel_rows":
                rows * (s.kernels["attn_impl"] == "pallas")}
    return count


# -- the record ------------------------------------------------------------------

_STATE_NO_SHIP = (
    "a layout with recurrent state (TransformerConfig.layer_kinds: its "
    "layers hold a state slot, and window layers a pool of their own, "
    "beside the KV blocks); {what} ships KV blocks only and would carry a "
    "partial copy of the request, so it is refused")
_WINDOW_NO_SHIP = (
    "a layout whose window layers release their blocks "
    "(TransformerConfig.window_pool: two pools with ids of their own, "
    "the window pool holding a request's live window only); {what} ships "
    "ONE pool's blocks under one table and would carry the full layers' "
    "keys without the window layers', so it is refused (missing: a payload "
    "with both pools and the window table's first block, "
    "serve/kv_transfer.py)")


def _experts_impl(c: TransformerConfig) -> Dict[str, str]:
    """The form of the routed experts' gated MLP, where the model has experts
    (``ops.moe.moe_layer_dropless`` asks the same of the weights it gets:
    ``[.., d_model, ff_expert]`` in the step's type)."""
    if not c.num_experts:
        return {}
    return {"expert_impl": expert_mlp.impl_for(jax.ShapeDtypeStruct(
        (c.d_model, c.ff_expert), jnp.dtype(c.dtype)))}


@dataclass(frozen=True)
class Layout:
    """One serve layout. Every field is a plain value or a function of the
    configuration; nothing here is state."""
    name: str
    # -- the tree: ``{..: {leaf: (shape, axes, how)}}`` of one layer, the
    # segments it is stacked in (``None``: one stack) and the draws it names
    # of its own; ``None`` where the layout draws its own tree
    block_shapes: Optional[Callable] = None
    segments: Optional[Callable] = None
    draws: Optional[Dict[str, Callable]] = None
    norm_bias: bool = False
    # -- the cache: ``pools(c, num_blocks, block_size, *, dtype, ..)`` with
    # ``window_blocks`` and ``state_slots`` where the layout has such a pool,
    # reached through :meth:`init_cache`; the leaf that gives ``n_layers,
    # n_blocks, block_size``; the leaves that are recurrent state by SLOT,
    # ``[layers, slots, ..]`` each (none: a block is all a token leaves
    # behind)
    pools: Callable = uniform.init_cache
    pool_leaf: str = "k"
    state_leaves: Tuple[str, ...] = ()
    # -- what a scheduler needs. ``table_width(window, chunk, block_size)``:
    # columns of a row's window table (``None``: no window pool). ``no_ship``:
    # why no block of it enters the prefix trie, is copied or is shipped
    # (``None``: a block is a prefix's whole state). ``max_chunk(c) ->
    # (positions, what they are)``: the largest ``prefill_chunk`` its step
    # allows
    table_width: Optional[Callable[[int, int, int], int]] = None
    no_ship: Optional[str] = None
    max_chunk: Optional[Callable] = None
    # ``snapshots`` > 0: a prefix of such a layout IS its blocks and a copy of
    # its ``state_leaves`` at the prefix's end, so the engine keeps such
    # copies at block boundaries (a pool of their own, owned by trie nodes,
    # this many entries a slot) and a prefix hit lands where one is kept (it
    # still ships nothing: ``no_ship``)
    snapshots: float = 0
    # -- the step: the layout's own layer loop ``run_layers(layers, cache, x,
    # c, ctx) -> (x, new cache, expert tokens or None)`` (``None``: the
    # shared loop of ``_step_paged_impl``) and the ``ctx`` fields it takes,
    # in the order the step makes them; cos / sin tables of its own; the
    # subject of the sentence that refuses it off the paged serve step
    run_layers: Optional[Callable] = None
    ctx: Tuple[str, ...] = ()
    rope_tables: Optional[Callable] = None
    serve_only: Optional[str] = None
    # -- ``forms(c, cache) -> {"attn_impl": .., ..}``: the form of each
    # kernel of its own loop, from the call the op makes of the same pool
    forms: Callable = lambda c, cache: {
        "attn_impl": paged_attention.impl_for(cache["k"]),
        **({"indexer_impl": sparse_attention.impl_for(
            cache["ki"], c.index_heads, c.index_head_dim)}
           if c.index_heads else {})}
    # -- the host's counters of one step: ``(c, StepRows) -> {name: n}`` each,
    # and every name they can return
    counts: Tuple[Callable, ...] = (_count_attention, _count_indexer,
                                    _count_experts)
    counters: Tuple[str, ...] = (_ATTENTION_COUNTERS + _INDEXER_COUNTERS
                                 + _EXPERT_COUNTERS)

    @property
    def stateful(self) -> bool:
        return bool(self.state_leaves)

    @property
    def window_pool(self) -> bool:
        return self.table_width is not None

    @property
    def shareable(self) -> bool:
        return self.no_ship is None

    def init_params(self, rng: jax.Array, c: TransformerConfig) -> Params:
        if self.block_shapes is None:
            return uniform.init_params(rng, c)
        return init_tree(rng, c, self)

    def param_axes(self, c: TransformerConfig) -> Params:
        if self.block_shapes is None:
            return uniform.param_axes(c)
        return tree_axes(c, self)

    def init_cache(self, c: TransformerConfig, num_blocks: int,
                   block_size: int, *, window_blocks: Optional[int] = None,
                   state_slots: Optional[int] = None, dtype=None) -> Params:
        """The layout's pools. It refuses an argument it has no pool for,
        and the lack of one it needs."""
        if window_blocks is not None and not self.window_pool:
            raise ValueError(
                f"window_blocks {window_blocks!r}: the layout has no window "
                "pool (TransformerConfig.window_pool)")
        if state_slots is not None and not self.stateful:
            raise ValueError(
                f"state_slots {state_slots!r}: the layout has no recurrent "
                "state (TransformerConfig.layer_kinds)")
        sizes = {}
        if self.window_pool:
            if window_blocks is None:
                raise ValueError("a layout with a window pool needs "
                                 "window_blocks")
            sizes["window_blocks"] = window_blocks
        if self.stateful:
            if state_slots is None:
                raise ValueError("a layout with recurrent state needs "
                                 "state_slots")
            sizes["state_slots"] = state_slots
        return self.pools(c, num_blocks, block_size, dtype=dtype, **sizes)

    def kernels(self, c: TransformerConfig, cache: Params) -> Dict[str, str]:
        """The forms the step program is traced with over ``cache``, each
        from the call its op makes: ``{"attn_impl": ..}`` and, where the
        layout has them, ``"ssd_impl"``, ``"indexer_impl"`` and
        ``"expert_impl"``."""
        return {**self.forms(c, cache), **_experts_impl(c)}

    def count(self, c: TransformerConfig, step: StepRows) -> Dict[str, int]:
        """The host's counters of one step, by the layout's rules."""
        out: Dict[str, int] = {}
        for part in self.counts:
            out.update(part(c, step))
        return out


# -- the table ---------------------------------------------------------------------

UNIFORM = Layout("uniform")

HYBRID = Layout(
    "hybrid",
    block_shapes=hybrid.block_shapes, segments=hybrid.segments,
    draws=hybrid.DRAWS, norm_bias=True,
    pools=hybrid.init_cache, state_leaves=("conv", "ssm"),
    table_width=hybrid.window_table_width, no_ship=_STATE_NO_SHIP,
    run_layers=hybrid.run_layers,
    ctx=("full_tables", "win_tables", "win_pos", "full_rows", "win_rows"),
    serve_only=hybrid.SERVE_ONLY,
    forms=lambda c, cache: {
        "attn_impl": diff_attention.impl_for(cache["k"], c.hdim)},
    counts=(_count_attention, _count_state,
            _count_windows(hybrid.pool_layers)),
    counters=_ATTENTION_COUNTERS + _STATE_COUNTERS + _WINDOW_COUNTERS)

PARALLEL = Layout(
    "parallel",
    block_shapes=parallel_hybrid.block_shapes, draws=parallel_hybrid.DRAWS,
    pools=parallel_hybrid.init_cache, state_leaves=("conv", "ssm"),
    no_ship=_STATE_NO_SHIP,
    max_chunk=parallel_hybrid.max_chunk,
    run_layers=parallel_hybrid.run_layers, ctx=("full_tables", "full_rows"),
    serve_only=parallel_hybrid.SERVE_ONLY,
    forms=lambda c, cache: {
        "attn_impl": paged_attention.impl_for(cache["k"]),
        "ssd_impl": ssd_step.impl_for(cache["ssm"])},
    counts=(_count_attention, _count_state, parallel_hybrid.count),
    counters=_ATTENTION_COUNTERS + _STATE_COUNTERS
    + parallel_hybrid.COUNTERS)

#: (its delta layers hold state by slot and no keys, its full layers the
#: uniform pools; the trie keeps a snapshot of the state beside a prefix's
#: blocks)
LINEAR_HYBRID = Layout(
    "linear_hybrid",
    block_shapes=linear_hybrid.block_shapes, segments=linear_hybrid.segments,
    draws=linear_hybrid.DRAWS,
    pools=linear_hybrid.init_cache, state_leaves=("conv", "delta"),
    # (one a conversation held here beside those of the requests in flight)
    no_ship=_STATE_NO_SHIP, snapshots=2.5,
    max_chunk=linear_hybrid.max_chunk,
    run_layers=linear_hybrid.run_layers, ctx=("full_tables", "full_rows"),
    serve_only=linear_hybrid.SERVE_ONLY,
    counts=(_count_attention, _count_state, linear_hybrid.count),
    counters=_ATTENTION_COUNTERS + _STATE_COUNTERS + linear_hybrid.COUNTERS)

LATENT = Layout(
    "latent",
    block_shapes=latent.block_shapes, segments=latent.segments,
    pools=latent.init_cache, pool_leaf="kv",
    run_layers=latent.run_layers, ctx=("full_tables", "full_rows"),
    rope_tables=latent.rope_tables, serve_only=latent.SERVE_ONLY,
    forms=lambda c, cache: {"attn_impl": latent_attention.impl_for(
        cache["kv"], c.kv_lora_rank)},
    counts=(_count_attention, _count_experts, latent.count),
    counters=_ATTENTION_COUNTERS + _EXPERT_COUNTERS + latent.COUNTERS)

#: (its windows masks over one pair of pools: every layer in ``"k"``, ``"v"``)
WINDOWED_MOE = Layout(
    "windowed_moe",
    block_shapes=windowed_moe.block_shapes, segments=windowed_moe.segments,
    pools=windowed_moe.init_cache, serve_only=windowed_moe.SERVE_ONLY)

#: ... and where ``attn_windows`` mix one window size with full layers
#: (``TransformerConfig.window_pool``): the window layers' pool beside it
WINDOWED_MOE_POOLS = dataclasses.replace(
    WINDOWED_MOE, table_width=hybrid.window_table_width,
    no_ship=_WINDOW_NO_SHIP,
    counts=WINDOWED_MOE.counts + (_count_windows(windowed_moe.pool_layers),),
    counters=WINDOWED_MOE.counters + _WINDOW_COUNTERS)

LAYOUTS = (UNIFORM, HYBRID, PARALLEL, LINEAR_HYBRID, LATENT, WINDOWED_MOE,
           WINDOWED_MOE_POOLS)

#: every name some layout's :meth:`Layout.count` can return (an engine keeps
#: all of them, zero where its layout has no such thing)
COUNTERS = tuple(dict.fromkeys(
    name for layout in LAYOUTS for name in layout.counters))


def layout_of(c: TransformerConfig) -> Layout:
    """The configuration's layout: the ONE place outside
    ``TransformerConfig``'s own validation that asks which it is."""
    if c.parallel_hybrid:
        return PARALLEL
    if c.linear_hybrid:
        return LINEAR_HYBRID
    if c.layer_kinds is not None:
        return HYBRID
    if c.latent:
        return LATENT
    if c.windowed_moe:
        return WINDOWED_MOE_POOLS if c.window_pool else WINDOWED_MOE
    return UNIFORM


def serve_only(c: TransformerConfig, where: str) -> None:
    """A layout that runs on the paged serve step only refuses ``where`` by
    its own name."""
    subject = layout_of(c).serve_only
    if subject is not None:
        raise NotImplementedError(
            f"{subject} runs on the paged serve step only, not in {where}")
