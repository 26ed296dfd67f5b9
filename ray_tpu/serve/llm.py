"""Continuous-batching LLM serving: paged slot engine + serve deployment.

Reference role: ``python/ray/serve/batching.py`` (request batching) +
streaming responses, joined into an LLM decode loop — the reference has
no LLM engine; this is the TPU-first differentiator (CLAUDE.md round-5
note). Design follows Orca-style token-level continuous batching over a
vLLM-style paged KV cache (PAPERS.md: the Gemma-on-TPU serving
comparison shows paged KV + batching policy, not raw FLOPs, decide TPU
serving throughput):

- The engine owns ONE jitted step (:func:`decode_step_paged`) over a
  fixed slot grid [max_slots, prefill_chunk]: static shapes, compiled
  once. Each iteration a decoding slot advances one token while a
  prefilling slot consumes up to ``prefill_chunk`` prompt tokens — so a
  long prompt drains in L/chunk steps WITHOUT stalling the decodes
  sharing its batch. The grid is what a row is FED, not what the step
  multiplies: inside the program the real positions of the step (a
  decoding row has one, not ``prefill_chunk``) are gathered to the front
  and the weights are multiplied by the first ``STEP_BUDGET`` positions
  when those hold every real one, by twice as many when those do (a width
  only a grid wider still has), by the whole grid when not, chosen on
  the device (``stats["step_positions_real"]``,
  ``["step_positions_run"]``, ``["steps_second_width"]``,
  ``["steps_full_width"]``).
- ONE step is in flight: a step's tokens are sampled on the device
  (``serve::sample``) and fed into the next step there
  (``serve::feed_tokens``), so ``step()`` dispatches step n+1 before it
  reads step n, and the host's work of a step (reading ids, emitting,
  admitting, building tables) runs under the device time of the next
  (``stats["steps_dispatched_ahead"]`` over ``["steps"]``).
- KV lives in a block-paged pool (``serve/kv_cache.py`` +
  ``models.init_cache_paged``): admission claims BLOCKS, not slots, and
  a hash-trie prefix cache maps shared system prompts to shared
  immutable blocks — a prefix hit skips that prefill compute entirely
  (``pos`` starts past the reused tokens). Copy-on-write covers the one
  mutable case (a capped match reusing a partial tail block).
- An :class:`~ray_tpu.serve.admission.AdmissionController` sheds
  requests whose projected TTFT/decode rate would breach the declared
  :class:`~ray_tpu.serve.admission.SLOConfig`; per-request
  ``deadline_s`` is enforced across admission queueing AND streaming.
- The engine stamps its own step and its own requests
  (``util.tracing.stamp``): ``step()`` is ``serve::step`` with the phases
  ``serve.step::admit`` / ``build_inputs`` / ``dispatch`` / ``read`` /
  ``route`` (``settle`` when idle) inside it, on the profiler's host plane
  in any traced run, ONE ring record a call when ``RTPU_TRACING`` is on,
  and always-on counters in ``stats``: a request's wait for slot and
  blocks and its prefill (``_TIME_COUNTERS``), a step's time by the kind
  of THAT step's rows, the host's time a call.
- The engine is serve-independent (testable standalone); the
  :class:`LLMDeployment` wrapper runs it on a background thread inside a
  ``max_concurrency`` replica and streams tokens to each caller through
  the ordinary streaming-generator path.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ray_tpu.serve.admission import (AdmissionController,
                                     DeadlineExceededError, RequestShedError,
                                     SLOConfig)
from ray_tpu.serve.kv_cache import BlockPool, PrefixCache
from ray_tpu.util import tracing


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


#: sentinel distinct from None (None IS a stream terminal)
_NO_ITEM = object()

#: the benchmark's files still pass ``paged=True`` (ROADMAP.md D12), so the
#: constructors take the keyword as a value that must be true
_DENSE_REMOVED = ("paged=False: the dense engine was removed in PR 32; "
                  "LLMEngine is the paged engine")

#: Positions the step program multiplies its weights by when they hold every
#: real position of the step (``decode_step_paged``'s ``budget``); a step
#: with up to twice as many takes twice the budget, where the grid is wider
#: than that, and a step with more the whole ``max_slots * prefill_chunk``
#: grid (``models.transformer.step_widths``). A v5e makes
#: 197e12 FLOP/s and reads 819e9 B/s, 240 FLOP a byte, which for a bf16
#: weight matrix is 240 positions: under about 256 a step's matmuls cost the
#: reading of their weights and nothing more, above it they cost their
#: positions, so a smaller budget would save nothing and a larger one
#: computes padding. The second width is for the steps just over it, a
#: chunk row or a few beside the decoding rows (a quarter of a 2048-position
#: grid, not all of it); it is derived, not chosen. The grid itself must be
#: left to a few per cent of the steps (``steps_full_width`` over
#: ``steps``): the gap between tokens is judged by its tail, and the tail
#: sits on the steps that take the full width. A constant of the program,
#: not a knob: an engine whose grid is no wider runs the step as it was.
STEP_BUDGET = 256

#: the paged step's counters the ENGINE keeps (``engine.stats[name]`` and
#: ``rtpu_serve_<name>_total``), beside those its layout counts of a step by
#: its own rules (``models.layouts.COUNTERS``: what attention read, pairs
#: routed, state slots, window and shared-pool reads, the mixers' positions;
#: zero where a layout has no such thing). Read off the device: (token, expert) pairs, each layer's busiest expert, experts
#: hit, over layers and steps
_STEP_COUNTERS = ("moe_expert_tokens_sum", "moe_expert_tokens_max",
                  "moe_experts_hit",
                  # and of the positions it multiplied its weights by: the
                  # real ones (``nvalid`` over active rows), ``STEP_BUDGET``,
                  # twice that or the whole grid, steps that took the whole
                  # grid because their real positions passed the widths
                  # under it
                  "step_positions_real", "step_positions_run",
                  "steps_full_width",
                  # and of the lookahead: steps dispatched while the step
                  # before was still unread; row-steps computed for a
                  # request that had already sampled its ``eos`` (found one
                  # step late, the token dropped)
                  "steps_dispatched_ahead", "rows_run_past_end",
                  # and of an expert layer that holds a SHARE of its
                  # experts (``TransformerConfig.experts_held``): of the
                  # pairs the router chose, those whose expert is held here
                  # (all of them where every expert is), and of the held
                  # pairs those the kernel that walks the experts hit
                  # multiplied (``stats["expert_impl"]``: all or none, the
                  # form the program was traced with)
                  "moe_pairs_held", "moe_kernel_pairs",
                  # and of the window pool's scheduling: blocks the window
                  # layers gave back as rows' windows moved and requests left
                  "window_blocks_released",
                  # and of a layout that keeps snapshots of its recurrent
                  # state (``Layout.snapshots``): copies taken at a prompt's
                  # last block boundary, prefix hits that restored one,
                  # snapshots the trie gave up, bytes copied either way
                  "state_snapshots_taken", "state_snapshots_restored",
                  "state_snapshots_evicted", "state_snapshot_bytes")

#: the engine's own stamps as counters, each sum beside its count (bumped in
#: ONE update of ``stats``: a snapshot from another thread sees both or
#: neither). At admission: requests that claimed slot and blocks, seconds
#: they lay in ``_pending``; of them, the requests that stood at the queue's
#: head short of WINDOW blocks (``waited_for`` ``"window_blocks"``: the window
#: pool's reservation was full) and their seconds. At a request's first token's read: first tokens,
#: seconds from admission to that read, steps that fed the prompt. At a
#: step's read, from THAT step's rows and its own time (one read to the
#: next): steps and seconds by kind: no row fed prompt tokens; a row did and
#: the step took less than the grid; the real positions passed every width
#: under the grid (``steps_full_width``). Beside its kind, the steps and
#: seconds that took the program's SECOND width (over ``STEP_BUDGET``, under
#: the grid). In ``step()``: the call's wall time less its wait for the
#: device, beside ``stats["steps"]``
_TIME_COUNTERS = ("requests_admitted", "pending_wait_s",
                  "requests_waited_window_blocks", "window_blocks_wait_s",
                  "first_tokens", "prefill_s", "prefill_steps",
                  "steps_decode_only", "step_s_decode_only",
                  "steps_chunk", "step_s_chunk", "step_s_full_width",
                  "steps_second_width", "step_s_second_width",
                  "step_host_s",
                  # host seconds of the calls that copy a snapshot into a
                  # slot (their count: ``state_snapshots_restored``)
                  "state_restore_s")

@dataclass(eq=False)   # identity semantics: generated __eq__ would
class _Request:        # elementwise-compare the prompt arrays and raise
    prompt: np.ndarray                 # [P] int32
    max_new_tokens: int
    # token sink: int token, None = done, Exception = engine failure
    emit: Callable[[Any], None]
    consumed: int = 0                  # prompt tokens fed so far
    generated: int = 0
    last_token: int = 0
    eos: Optional[int] = None
    cancelled: bool = False
    # the stream has had its terminal item (finished, expired, swept after
    # a cancel, aborted, migrated): whatever of it is still in flight on
    # the device is dropped when it is read
    ended: bool = False
    # paged-cache state (engine-owned). One step is in flight, so ``pos``
    # and ``consumed`` are the DISPATCHED state (they count the step in
    # flight); ``generated``, ``last_token`` and ``gen_tokens`` are the READ
    # state (one token behind for a row that samples in that step)
    table: List[int] = field(default_factory=list)   # physical block ids
    # a model with window layers: the window pool's blocks that hold
    # logical blocks win_first, win_first + 1, ...; and the most the
    # request can ever hold there, reserved at admission
    win_table: List[int] = field(default_factory=list)
    win_first: int = 0
    win_reserved: int = 0
    pos: int = 0                       # KV tokens cached (incl. shared)
    # latency bookkeeping (TTFT/TPOT + deadline enforcement)
    submit_ts: float = 0.0             # monotonic
    deadline: Optional[float] = None   # monotonic absolute
    last_emit_ts: Optional[float] = None
    # the request's own trace (a traceparent: ``serve.llm::pending`` and
    # ``::prefill`` are recorded under it at the first token's read), when
    # slot and blocks were claimed, what it waited for at the head of the
    # queue ("slot" | "blocks"), requests ahead of it at submit, prompt
    # tokens a prefix hit spared it, steps that fed its prompt and those of
    # them that took the full width
    trace: Optional[str] = None
    admitted_ts: float = 0.0           # monotonic
    waited_for: str = ""
    ahead: int = 0
    prefix_hit: int = 0
    # a layout that keeps snapshots of its state: the position (a whole
    # number of blocks, the prompt's last boundary) at which this request's
    # state is copied; ``None`` once taken, or where a node holds it already
    snapshot_at: Optional[int] = None
    # the slot it was admitted to: where a layout with recurrent state keeps
    # the request's state
    slot: int = -1
    prefill_steps: int = 0
    prefill_full_width: int = 0
    # disaggregated prefill/decode (ISSUE 13)
    prefill_only: bool = False         # stop after the first token and
    #                                    emit a KVExport instead of it
    adopt_kv: Optional[Dict[str, np.ndarray]] = None  # shipped prompt KV
    #                                    to scatter into claimed blocks
    # every sampled token, in order (elastic migration, r20): a live
    # session's continuation prompt on another replica is
    # prompt + gen_tokens[:-1] — the fed-token transcript the cached KV
    # positions actually correspond to. The trie insert on release keys
    # only the true prompt prefix, so this list is what keeps a migrated
    # session's adoption honest about token VALUES, not just counts.
    gen_tokens: List[int] = field(default_factory=list)


@dataclass(eq=False)
class _StepInFlight:
    """The one step the device runs (or has queued) whose tokens the host
    has not read yet."""

    #: (slot, request, samples, last): the step's rows; ``samples``: the
    #: row's logits are sampled (a decoding row, a prompt's last chunk);
    #: ``last``: that token ends the request, which left its slot at
    #: dispatch and keeps its blocks until the token is read
    rows: List[tuple]
    ids: Any            # device [max_slots] int32, the step's samples
    logits: Any         # device [max_slots, V]; read only under ``capture``
    counts: Any         # what only the device counts (device arrays)
    index: int          # ``stats["steps"]`` when it was dispatched
    dispatched: float   # monotonic
    real: int           # positions the rows were fed
    run: int            # positions the program multiplied its weights by
    second_width: bool  # over the budget, and they are the program's
    #                     second width, not the whole grid
    chunk_rows: int     # rows that were fed prompt tokens
    seconds: float = 0.0  # its time once read: one read to the next

    @property
    def kind(self) -> str:
        """By what its rows were fed: ``full_width`` (over the budget, and
        it took THE WHOLE GRID) | ``chunk`` | ``decode_only`` (the suffix
        of its two ``_TIME_COUNTERS``)."""
        if self.real > STEP_BUDGET and not self.second_width:
            return "full_width"
        return "chunk" if self.chunk_rows else "decode_only"

    def facts(self, prefix: str) -> Dict[str, Any]:
        """What the ``serve::step`` record says of it."""
        out = {prefix + "index": self.index, prefix + "rows": len(self.rows),
               prefix + "real_positions": self.real,
               prefix + "positions_run": self.run,
               prefix + "chunk_rows": self.chunk_rows,
               prefix + "kind": self.kind}
        if self.seconds:
            out[prefix + "step_ms"] = self.seconds * 1e3
        return out


@dataclass(eq=False)
class KVExport:
    """What a prefill-only request emits instead of its first token: the
    sampled token plus the prompt's KV blocks gathered off the paged
    pool ([L, n_blocks, bs, kvh, hd] per tensor, host-side) — exactly
    the payload a decode engine's :meth:`LLMEngine.adopt` consumes."""

    token: int
    prompt_len: int
    block_size: int
    kv: Dict[str, np.ndarray]

    @property
    def nbytes(self) -> int:
        return sum(int(a.nbytes) for a in self.kv.values())


class LLMEngine:
    """Slot-based continuous-batching decode engine over one model.

    ``submit`` is thread-safe; ``step`` must be called from ONE driver
    thread (the deployment's loop thread) and returns whether any work
    remains. Greedy sampling by default; ``temperature`` > 0 samples (on
    the device; the program is traced with the value the engine was built
    with).
    """

    #: the logits tap: while true, the read of a step also fetches its
    #: logits and hands each sampling row's to :meth:`_sample` just before
    #: that row's ``emit``; while false nothing of ``[max_slots, V]``
    #: crosses to the host
    capture = False

    def __init__(self, config, params=None, *, max_slots: int = 8,
                 max_len: int = 256, temperature: float = 0.0,
                 seed: int = 0, paged: bool = True,
                 block_size: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 window_blocks: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 prefix_cache: bool = True,
                 slo: Optional[SLOConfig] = None,
                 role: str = "colocated"):
        """``num_blocks``: the pool's blocks (default: ``max_slots`` full
        tables). ``window_blocks``: for a layout with a window pool, that
        pool's blocks (default: ``max_slots`` window tables, which no
        traffic can exhaust; a smaller one is admitted by its
        reservation)."""
        if not paged:
            raise ValueError(_DENSE_REMOVED)
        import jax
        import jax.numpy as jnp

        from ray_tpu import config as _knobs
        from ray_tpu import models
        from ray_tpu.models import layouts
        from ray_tpu.util.tpu_info import ensure_compile_cache

        ensure_compile_cache()  # before this engine's first compile
        if isinstance(config, str):
            config = models.get_config(config)
        self.config = config
        self.max_slots = max_slots
        self.max_len = max_len
        self.temperature = temperature
        if role not in ("colocated", "prefill", "decode"):
            raise ValueError(f"unknown engine role {role!r}")
        self.role = role
        if params is None:
            params = models.init_params(jax.random.PRNGKey(seed), config)
        self.params = params
        # model multiplexing (serve/multiplex.py): a registry-managed
        # engine's params can be PAGED OUT between steps (dropped to the
        # arena store under budget pressure) and re-acquired lazily —
        # the provider is called at the top of step() when params are
        # absent. jit-safe: the step donates only the cache, so swapping
        # the params pytree never invalidates the compiled program.
        self.params_provider: Optional[Callable[[], Any]] = None
        # what the configuration keeps on the device for a request, who may
        # share or ship it, which kernels its step takes and what a step of
        # it counts: the layout's to say (models/layouts.py)
        layout = self._layout = models.layout_of(config)
        bs = int(block_size or _knobs.get("llm_block_size"))
        self._full_width = self._tbl_width = -(-max_len // bs)
        nb = int(num_blocks or max_slots * self._tbl_width)
        self.pool = BlockPool(nb, bs)
        # a prefix of a layout with ``snapshots`` is its blocks and the
        # recurrent state at its end: the trie owns the ids of a pool of
        # such copies (``Layout.snapshots`` a slot), and without a trie
        # there are none
        n_snap = int(layout.snapshots * max_slots) if prefix_cache else 0
        self.prefix = PrefixCache(self.pool, n_snap) if prefix_cache \
            else None
        self.prefill_chunk = max(
            1, int(prefill_chunk or _knobs.get("llm_prefill_chunk")))
        # pools by KIND of layer. A layout with recurrent state
        # (``_stateful``) keeps it indexed by slot. Where it has window
        # layers ``self.pool`` is the full-attention layers' and the window
        # layers share ``self.win_pool``, whose table holds a row's live
        # window only and rides in the last ``_win_width`` columns of the
        # step's one ``tables`` array; without them ``_win_width`` is 0 and
        # there is no ``win_pool``
        self._stateful = layout.stateful
        # no one block is a prefix's whole state (recurrent state by slot, a
        # window pool whose blocks are released): nothing of such a layout
        # enters the trie, is copied or is shipped, and ``_no_ship`` says why
        self._by_kind = not layout.shareable
        self._no_ship = layout.no_ship
        # ... but where the layout keeps snapshots, a prefix's blocks WITH
        # the state at its end are a prefix's whole state: looked up at
        # admission, inserted when the copy is taken
        self._snapshots = n_snap > 0
        self._snapshots_evicted = 0
        limit = layout.max_chunk(config) if layout.max_chunk else None
        if limit is not None and self.prefill_chunk > limit[0]:
            raise ValueError(
                f"prefill_chunk {self.prefill_chunk} passes {limit[1]}")
        self._win_width = 0
        self.win_pool = None
        self._win_reserved = 0
        # (``window_blocks`` for a layout without a window pool: its refusal)
        pools = {"window_blocks": window_blocks}
        if layout.window_pool:
            self._win_width = layout.table_width(
                config.sliding_window, self.prefill_chunk, bs)
            self._tbl_width += self._win_width
            self.win_pool = BlockPool(int(
                window_blocks or max_slots * self._win_width), bs)
            pools["window_blocks"] = self.win_pool.num_blocks
        if layout.stateful:
            pools["state_slots"] = max_slots
        self._cache = layout.init_cache(config, nb, bs, **pools)
        # bytes of ONE request's recurrent state over all layers
        self._state_bytes = sum(
            self._cache[name].nbytes for name in layout.state_leaves
        ) // max_slots
        # the snapshot pool: every state leaf with ``n_snap`` entries where
        # the cache has its slots
        self._snaps = {
            name: jnp.zeros(self._cache[name].shape[:1] + (n_snap,)
                            + self._cache[name].shape[2:],
                            self._cache[name].dtype)
            for name in layout.state_leaves} if self._snapshots else {}
        # donate the cache: without donation every step/copy keeps
        # BOTH pool-sized buffers live (the old one is overwritten
        # immediately), doubling transient HBM for the KV pool —
        # fatal at real pool sizes on a 16 GB v5e. CPU ignores
        # donation (a one-time warning), so tests are unaffected.
        from ray_tpu.util.device_plane import registered_jit

        self._step_fn = registered_jit(self._raw_step_paged,
                                       name="serve::decode_step_paged",
                                       component="serve",
                                       donate_argnums=(1,))
        # sampling and the feeding of a sample into the next step stay on
        # the device: two small programs around the step, whose text and
        # signature the benchmark holds (ROADMAP.md D6)
        self._sample_fn = registered_jit(self._raw_sample,
                                         name="serve::sample",
                                         component="serve")
        self._feed_fn = registered_jit(self._raw_feed,
                                       name="serve::feed_tokens",
                                       component="serve")
        self._sample_key = (jax.random.PRNGKey(seed) if temperature > 0.0
                            else None)
        # the last step's samples (nothing to feed forward yet) and the
        # step in flight; the loop thread's alone
        self._ids = jnp.zeros((max_slots,), jnp.int32)
        self._inflight: Optional[_StepInFlight] = None
        self._read_at = 0.0
        # seconds by phase of the ``step()`` call under way (tracing.stamp)
        self._stamps: Dict[str, float] = {}
        # requests whose last token is dispatched and not yet read: they
        # left their slot at dispatch and hold their blocks until the read
        self._leaving: List[_Request] = []
        self._copy_fn = registered_jit(self._raw_copy,
                                       name="serve::copy_kv_block",
                                       component="serve",
                                       donate_argnums=(0,))
        # disaggregation (ISSUE 13): gather exports a request's
        # blocks (no donation — the pool stays live), scatter adopts
        # a shipped batch (donated — the old pool is dead on write).
        # Distinct block counts retrace; table widths bound the set.
        self._gather_fn = registered_jit(self._raw_gather,
                                         name="serve::gather_kv_blocks",
                                         component="serve")
        self._scatter_fn = registered_jit(self._raw_scatter,
                                          name="serve::scatter_kv_blocks",
                                          component="serve",
                                          donate_argnums=(0,))
        # warm the COW copy's compile NOW, not in the middle of the
        # first prefix-sharing request's admission (block 0 onto
        # itself over an all-zero cache is a no-op; src/dst trace as
        # scalars so one compile serves all)
        if not self._by_kind:   # (no block of such a model is ever copied)
            self._cache = self._copy_fn(self._cache, 0, 0)
        # slot <-> snapshot pool, one small program each way, beside
        # ``serve::copy_kv_block`` and warmed like it (entry 0 and slot 0
        # are zeros both)
        self._snapshot_fn = registered_jit(self._raw_snapshot,
                                           name="serve::snapshot_state",
                                           component="serve",
                                           donate_argnums=(0,))
        self._restore_fn = registered_jit(self._raw_restore,
                                          name="serve::restore_state",
                                          component="serve",
                                          donate_argnums=(0,))
        if self._snapshots:
            self._snaps = self._snapshot_fn(self._snaps, self._cache, 0, 0)
            self._cache = self._restore_fn(self._cache, self._snaps, 0, 0)
        self.admission = AdmissionController(slo)
        self._rng = np.random.default_rng(seed)
        self._lock = threading.Lock()
        self._pending: List[_Request] = []
        self._slots: List[Optional[_Request]] = [None] * max_slots
        # live-session migration intake (elastic serving, r20): the
        # drain thread marks sessions here; the loop thread exports them
        # at the top of the next step (the cache is donation-aliased, so
        # only the step thread may gather from it)
        self._migrations: List[tuple] = []
        self.stats = {"steps": 0, "tokens_generated": 0,
                      "max_concurrent": 0, "requests": 0,
                      "prefix_hit_tokens": 0, "deadline_drops": 0,
                      "exported": 0, "adopted": 0, "migrated_out": 0}
        # blocks of the table the step's attention has to read (each
        # row's live context) against the blocks the table is wide,
        # summed over rows and steps, and the layout's other counters; and
        # the forms of its kernels the step program is traced with
        # (``attn_impl`` and, where the layout has them, ``ssd_impl``,
        # ``indexer_impl`` and ``expert_impl``: each the question its op
        # asks of the same pool)
        self._kernels = layout.kernels(config, self._cache)
        self.stats.update(**self._kernels, **dict.fromkeys(
            layouts.COUNTERS + _STEP_COUNTERS + _TIME_COUNTERS, 0))
        self._metrics = self._init_metrics()

    @staticmethod
    def _init_metrics():
        """Serving-tier built-ins (metric_defs-only creation). Instances
        are cached here so the hot loop never re-resolves the registry."""
        try:
            from ray_tpu.models import layouts
            from ray_tpu.util import metric_defs as md

            return {
                "kv_free": md.get("rtpu_serve_kv_blocks_free"),
                "kv_used": md.get("rtpu_serve_kv_blocks_used"),
                "hits": md.get("rtpu_serve_prefix_cache_hits_total"),
                "misses": md.get("rtpu_serve_prefix_cache_misses_total"),
                "hit_tokens": md.get("rtpu_serve_prefix_hit_tokens_total"),
                "sheds": md.get("rtpu_serve_admission_sheds_total"),
                "ttft": md.get("rtpu_serve_ttft_seconds"),
                "tpot": md.get("rtpu_serve_tpot_seconds"),
                "pool_inflight": md.get("rtpu_serve_pool_inflight"),
                "pool_queued": md.get("rtpu_serve_pool_queued"),
                "pool_kv_used_frac":
                    md.get("rtpu_serve_pool_kv_used_fraction"),
                **{name: md.get(f"rtpu_serve_{name}_total")
                   for name in (layouts.COUNTERS + _STEP_COUNTERS
                                + _TIME_COUNTERS)},
            }
        except Exception:  # metrics plane unavailable (bare unit tests)
            return None

    def _raw_step_paged(self, params, cache, tokens, tables, pos, nvalid,
                        active):
        from ray_tpu.models import decode_step_paged

        return decode_step_paged(params, cache, tokens, tables, pos,
                                 nvalid, self.config, active=active,
                                 step_stats=True, budget=STEP_BUDGET)

    def _raw_sample(self, logits, key=None, step=None):
        """``[max_slots, V]`` logits -> ``[max_slots]`` int32: the first
        arg-max (as ``np.argmax``), or a categorical draw from the engine's
        key folded with the step's number."""
        import jax
        import jax.numpy as jnp

        if self.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            jax.random.fold_in(key, step),
            logits.astype(jnp.float32) / self.temperature,
            axis=-1).astype(jnp.int32)

    @staticmethod
    def _raw_feed(tokens, ids, feed):
        """The host's ``[max_slots, C]`` tokens with column 0 of the rows
        in ``feed`` taken from the last step's samples."""
        import jax.numpy as jnp

        return tokens.at[:, 0].set(jnp.where(feed, ids, tokens[:, 0]))

    @staticmethod
    def _raw_copy(cache, src, dst):
        from ray_tpu.models import copy_kv_block

        return copy_kv_block(cache, src, dst)

    @staticmethod
    def _raw_snapshot(snaps, cache, slot, entry):
        """Slot ``slot``'s recurrent state of every layer into entry
        ``entry`` of the snapshot pool."""
        return {name: pool.at[:, entry].set(cache[name][:, slot])
                for name, pool in snaps.items()}

    @staticmethod
    def _raw_restore(cache, snaps, entry, slot):
        """Entry ``entry`` of the snapshot pool into slot ``slot``."""
        return {**cache, **{name: cache[name].at[:, slot].set(pool[:, entry])
                            for name, pool in snaps.items()}}

    @staticmethod
    def _raw_gather(cache, ids):
        from ray_tpu.models import gather_kv_blocks

        return gather_kv_blocks(cache, ids)

    @staticmethod
    def _raw_scatter(cache, ids, kv):
        from ray_tpu.models import scatter_kv_blocks

        return scatter_kv_blocks(cache, ids, kv)

    # -- thread-safe intake ------------------------------------------------

    def submit(self, prompt, max_new_tokens: int,
               emit: Callable[[Any], None],
               eos: Optional[int] = None,
               deadline_s: Optional[float] = None,
               prefill_only: bool = False,
               trace: Optional[str] = None) -> "_Request":
        """``trace``: the caller's traceparent; the request's wait and
        prefill are recorded as its children."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prefill_only and self._by_kind:
            raise NotImplementedError(
                self._no_ship.format(what="a prefill-only export"))
        if prefill_only:
            # the export happens at the FIRST sample: exactly one token
            # is produced here; the decode pool owns the rest
            max_new_tokens = 1
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        if len(prompt) + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) exceeds the engine's max_len "
                f"({self.max_len})")
        # a prefill-only request claims PROMPT blocks only: its one
        # sampled token's KV is never written (KV lands when a token
        # is FED, and feeding moves to the decode pool)
        width = self.pool.blocks_for_tokens(
            len(prompt) + (0 if prefill_only else max_new_tokens))
        if width > self.pool.num_blocks:
            # bigger than the WHOLE pool: it could never be admitted
            # — queueing it would pin the strict-FIFO head forever
            # and busy-spin the decode loop with zero active slots
            raise ValueError(
                f"request needs {width} KV blocks but the pool has "
                f"only {self.pool.num_blocks} total; raise "
                f"num_blocks or lower max_new_tokens")
        if self.win_pool is not None and \
                min(self._win_width, width) > self.win_pool.num_blocks:
            raise ValueError(
                f"request needs {min(self._win_width, width)} window blocks "
                f"at once but the window pool has only "
                f"{self.win_pool.num_blocks} total")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        # SLO gate BEFORE the request joins the queue: a doomed request
        # gets a fast RequestShedError, not a slow timeout
        with self._lock:
            queued = len(self._pending)
            queued_tokens = sum(len(r.prompt) for r in self._pending)
            free_slots = sum(r is None for r in self._slots)
        try:
            self.admission.check_admit(
                len(prompt), queued, queued_tokens, self.prefill_chunk,
                free_slots, self.max_slots - free_slots,
                deadline_s=deadline_s)
        except RequestShedError as e:
            if self._metrics:
                self._metrics["sheds"].inc(tags={"reason": e.reason})
            raise
        now = time.monotonic()
        req = _Request(prompt, max_new_tokens, emit, eos=eos,
                       submit_ts=now,
                       deadline=(now + deadline_s
                                 if deadline_s is not None else None),
                       prefill_only=prefill_only, trace=trace, ahead=queued)
        with self._lock:
            self._pending.append(req)
            self.stats["requests"] += 1
        return req

    def adopt(self, prompt, kv: Dict[str, np.ndarray], first_token: int,
              max_new_tokens: int, emit: Callable[[Any], None],
              eos: Optional[int] = None,
              deadline_s: Optional[float] = None,
              trace: Optional[str] = None) -> "_Request":
        """Admit a request whose prompt KV was prefilled on ANOTHER
        engine (the decode half of disaggregated serving): claim a full
        table, scatter the shipped block batch into it, and start
        decoding from ``first_token`` — no prompt tokens ever run
        through this engine's model. ``kv`` is the
        :class:`KVExport` payload ([L, n_blocks, bs, kvh, hd] per
        tensor); the first token is re-emitted here so the caller sees
        one uninterrupted stream."""
        if self._by_kind:
            raise NotImplementedError(self._no_ship.format(what="adoption"))
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if len(prompt) + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) exceeds the engine's max_len "
                f"({self.max_len})")
        need = self.pool.blocks_for_tokens(len(prompt))
        # every pool of a payload is [L, n, ...]; the layout's own leaf is
        # [L, n, bs, ...] (the indexer's keys lie several a stored row)
        leaf = kv.get(self._layout.pool_leaf, next(iter(kv.values())))
        got, bs_got = (int(n) for n in leaf.shape[1:3])
        if got != need:
            raise ValueError(
                f"KV payload carries {got} blocks but the prompt needs "
                f"{need} (block_size {self.pool.block_size})")
        if bs_got != self.pool.block_size:
            raise ValueError(
                f"KV payload block_size {bs_got} != this "
                f"engine's {self.pool.block_size}")
        # FULL geometry check, every pool of this engine's cache
        # ([L, n, ...]: K, V, and the indexer's keys where the model
        # has them): per-role engine kwargs make mismatched pool configs
        # constructible, and a bad payload must fail THIS request at
        # adopt — not blow up the jitted scatter later on the engine
        # loop, where abort_all would kill every in-flight stream
        for name, pool in self._cache.items():
            if name not in kv:
                raise ValueError(
                    f"KV payload lacks the {name!r} pool this engine's "
                    "model caches (mismatched pool model configs?)")
            want = (int(pool.shape[0]), got) + tuple(
                int(d) for d in pool.shape[2:])
            if tuple(int(d) for d in kv[name].shape) != want:
                raise ValueError(
                    f"KV payload {name} shape "
                    f"{tuple(kv[name].shape)} does not match this "
                    f"engine's cache geometry {want} "
                    "(mismatched pool model configs?)")
        width = self.pool.blocks_for_tokens(len(prompt) + max_new_tokens)
        if width > self.pool.num_blocks:
            raise ValueError(
                f"request needs {width} KV blocks but the pool has "
                f"only {self.pool.num_blocks} total")
        # decode-side admission: no prefill cost (the blocks arrive
        # precomputed), so only the queue/TPOT gates carry signal
        with self._lock:
            queued = len(self._pending)
            queued_tokens = sum(len(r.prompt) for r in self._pending)
            free_slots = sum(r is None for r in self._slots)
        try:
            self.admission.check_admit(
                1, queued, queued_tokens, self.prefill_chunk, free_slots,
                self.max_slots - free_slots, deadline_s=deadline_s)
        except RequestShedError as e:
            if self._metrics:
                self._metrics["sheds"].inc(tags={"reason": e.reason})
            raise
        now = time.monotonic()
        req = _Request(prompt, max_new_tokens, emit, eos=eos,
                       submit_ts=now,
                       deadline=(now + deadline_s
                                 if deadline_s is not None else None),
                       trace=trace, ahead=queued)
        # the copy is load-bearing, not defensive: store-path payloads
        # arrive as zero-copy views into the object store, and the
        # scatter runs later on the engine loop — by then the caller's
        # descriptor (and its ref pin) may be gone
        req.adopt_kv = {name: np.ascontiguousarray(kv[name])
                        for name in self._cache}
        req.last_token = int(first_token)
        req.gen_tokens.append(int(first_token))
        with self._lock:
            self._pending.append(req)
            self.stats["requests"] += 1
            self.stats["adopted"] += 1
        return req

    # -- weight paging (model multiplexing) --------------------------------

    def set_params(self, params) -> None:
        """Install (swap in) a params pytree. Called from the step/loop
        thread between steps; safe because the jitted step donates the
        cache, never the params."""
        self.params = params

    def drop_params(self) -> None:
        """Page this engine's weights out. Only legal while the engine
        has no in-flight work (the registry's pin accounting guarantees
        it); the next step with work re-acquires via
        ``params_provider``."""
        self.params = None

    def _ensure_params(self) -> None:
        if self.params is None:
            if self.params_provider is None:
                raise RuntimeError(
                    "engine params paged out and no params_provider set")
            self.params = self.params_provider()

    def cancel(self, req: "_Request") -> None:
        """Abandon a request: pending entries are dropped immediately; an
        in-slot request frees its slot (and KV blocks) at the next step
        without emitting further tokens (client disconnect must not leave
        zombie slots). A row of it in the step in flight is dropped when
        that step is read."""
        with self._lock:
            req.cancelled = True
            if req in self._pending:
                self._pending.remove(req)

    def abort_all(self, error: BaseException) -> None:
        """Fail every outstanding request (decode loop died). The step in
        flight is dropped unread: the device may be what died."""
        self._inflight = None
        with self._lock:
            victims = [r for r in self._slots if r is not None]
            victims += self._leaving    # (blocks held, no slot)
            victims += self._pending
            self._pending.clear()
            self._slots = [None] * self.max_slots
        for r in victims:
            # under the lock: block/trie mutation must be invisible to a
            # concurrent kv_state()/load_state() walking the trie
            with self._lock:
                self._retire(r, insert=False)
            try:
                r.emit(error)
            except Exception:
                pass

    # -- paged block accounting -------------------------------------------

    def _claim_blocks(self, req: _Request, pending_copies: list) -> bool:
        """Admission = claiming KV blocks. Prefix-match the prompt, then
        allocate the remainder of the request's table (prompt + budgeted
        new tokens, all up front — a request admitted here can never OOM
        the pool mid-decode). Falls back to trie eviction; False = not
        enough blocks, the request stays queued (``self._short_of`` then
        says ``"window_blocks"`` where it was the window pool's reservation
        that was full).

        Pure host-side bookkeeping (runs under the engine lock): a
        needed copy-on-write DEVICE copy is queued onto
        ``pending_copies`` for :meth:`_sweep_and_admit` to run after the
        lock drops — a slow device op must not freeze
        ``submit()``/``kv_state()`` behind the lock."""
        pool, trie = self.pool, self.prefix
        total = len(req.prompt) + (0 if req.prefill_only
                                   else req.max_new_tokens)
        width = pool.blocks_for_tokens(total)
        if self._by_kind and not self._snapshots:
            # a block of keys is not a prefix's whole state (the state-space
            # layers' state at the prefix's end is not kept; window layers
            # have released the prefix's blocks): no lookup, no hit, never
            # a resume from a zero state. The request claims
            # what it needs of EACH pool, all or nothing: its whole table
            # of the full layer's pool, the most it can ever hold of the
            # window pool (reserved; taken and returned block by block as
            # its window moves), and its slot's state (zeroed by the step
            # at position 0)
            reserve = min(self._win_width, width)
            if self.win_pool is not None and \
                    self._win_reserved + reserve > self.win_pool.num_blocks:
                self._short_of = "window_blocks"
                return False
            fresh = pool.alloc(width)
            if fresh is None:
                return False
            self._win_reserved += reserve
            req.win_reserved = reserve
            req.table = fresh
            req.pos = req.consumed = 0
            return True
        if req.adopt_kv is not None:
            # adoption: the payload IS the prompt KV — a trie match would
            # alias blocks the scatter must not overwrite, so claim all
            # fresh (the finished request still seeds the trie on release)
            fresh = pool.alloc(width)
            if fresh is None and trie is not None:
                trie.evict(width - pool.free_count)
                fresh = pool.alloc(width)
            if fresh is None:
                return False
            req.table = fresh
            req.pos = req.consumed = len(req.prompt)
            n_kv = int(next(iter(req.adopt_kv.values())).shape[1])
            pending_copies.append(("adopt", req, fresh[:n_kv],
                                   req.adopt_kv))
            req.adopt_kv = None
            return True
        lookup_stats = trie.stats() if trie is not None else None
        snapshot = None
        if self._snapshots:
            # a hit lands where the trie keeps the state at the prefix's
            # end, on a block boundary: nothing to copy on write, and the
            # request's own copy is due at its prompt's last boundary
            blocks, matched, snapshot = trie.match_snapshot(
                req.prompt.tolist())
            cow = None
            at = len(req.prompt) // pool.block_size * pool.block_size
            req.snapshot_at = at if at > matched else None
        else:
            blocks, matched, cow = (trie.match(req.prompt.tolist())
                                    if trie is not None else ([], 0, None))
        fresh_needed = width - len(blocks)
        fresh = pool.alloc(fresh_needed)
        if fresh is None and trie is not None:
            trie.evict(fresh_needed - pool.free_count)
            fresh = pool.alloc(fresh_needed)
        def roll_back():
            pool.release_all(blocks)
            if cow is not None:
                pool.release(cow)
            # roll back the lookup accounting: this SAME request re-runs
            # the match on every step while it waits at the queue head —
            # counting each retry would overstate hit rate exactly in
            # the pool-pressure regime the paged A/B measures
            if lookup_stats is not None:
                trie.hits = lookup_stats["hits"]
                trie.misses = lookup_stats["misses"]
                trie.hit_tokens = lookup_stats["hit_tokens"]

        if fresh is None:
            roll_back()
            return False
        if cow is not None:
            # capped match reused part of a shared block: queue the
            # device copy into the request's first fresh block (the cow
            # ref stays held until the copy lands)
            pending_copies.append(("cow", req, cow, fresh[0]))
        if snapshot is not None:
            pending_copies.append(("restore", req, snapshot))
        req.table = blocks + fresh
        req.pos = req.consumed = req.prefix_hit = matched
        self.stats["prefix_hit_tokens"] += matched
        return True

    def _count(self, name: str, n: int) -> None:
        self.stats[name] += n
        if self._metrics:
            self._metrics[name].inc(n)

    def _count_together(self, **grown) -> None:
        """Counters that are read as quotients of one another (a sum of
        seconds and its count), grown in ONE update of ``stats``: a
        ``dict(engine.stats)`` taken from another thread holds all of them
        or none (only the loop thread writes)."""
        stats = self.stats
        stats.update({name: stats[name] + n for name, n in grown.items()})
        if self._metrics:
            for name, n in grown.items():
                if name in self._metrics:      # (``steps`` has no metric)
                    self._metrics[name].inc(n)

    def _move_window(self, req: _Request, n: int) -> None:
        """Before a step that feeds ``n`` tokens to ``req``: return the
        window pool's blocks that lie wholly before the first key the
        row's first query may see, and take those the step's tokens need.
        The step program finds the first block by the same rule
        (``pos - window + 1``), so the table's entry 0 is that block. The
        blocks taken never pass what admission reserved."""
        bs, window = self.pool.block_size, self.config.sliding_window
        first = max(req.pos - window + 1, 0) // bs
        gone = min(max(first - req.win_first, 0), len(req.win_table))
        if gone:
            self.win_pool.release_all(req.win_table[:gone])
            del req.win_table[:gone]
            self._count("window_blocks_released", gone)
        req.win_first = first
        more = (req.pos + n - 1) // bs - first + 1 - len(req.win_table)
        if more > 0:
            fresh = None
            if len(req.win_table) + more <= req.win_reserved:
                fresh = self.win_pool.alloc(more)
            if fresh is None:
                raise RuntimeError(
                    f"window pool: a row needs {more} more blocks beside "
                    f"its {len(req.win_table)} (reserved "
                    f"{req.win_reserved}, free {self.win_pool.free_count})")
            req.win_table += fresh

    def _release_blocks(self, req: _Request, *, insert: bool) -> None:
        """Return a request's KV blocks. ``insert``: first offer the
        fully-written full prompt blocks to the prefix trie (the trie
        retains what it adopts), so the NEXT request with this system
        prompt hits."""
        if not req.table:
            return
        if self.win_pool is not None:
            self._count("window_blocks_released", len(req.win_table))
            self.win_pool.release_all(req.win_table)
            self._win_reserved -= req.win_reserved
            req.win_table, req.win_reserved = [], 0
        if self._by_kind:
            # nothing of it seeds the trie here (with snapshots: its prompt
            # went in with the copy of its state, ``_take_snapshots``)
            insert = False
        if insert and self.prefix is not None:
            n_full = min(len(req.prompt), req.pos) // self.pool.block_size
            if n_full:
                self.prefix.insert(
                    req.prompt[:n_full * self.pool.block_size].tolist(),
                    req.table[:n_full])
        self.pool.release_all(req.table)
        req.table = []

    def _retire(self, req: _Request, *, insert: bool) -> None:
        """End a request's stay (caller holds the lock): its blocks go
        back, its slot (if it still has one) is free, and whatever of it is
        in flight will be dropped."""
        self._release_blocks(req, insert=insert)
        for i, r in enumerate(self._slots):
            if r is req:
                self._slots[i] = None
        if req in self._leaving:
            self._leaving.remove(req)
        req.ended = True

    # -- driver-thread loop body ------------------------------------------

    def _sweep_and_admit(self) -> tuple:
        """Free finished/cancelled/expired slots, then admit pending
        requests while a slot AND their KV blocks are available (strict
        FIFO — no head-of-line bypass, so admission order is fair)."""
        now = time.monotonic()
        expired: List[_Request] = []
        pending_copies: List[tuple] = []
        with self._lock:
            for i in range(self.max_slots):
                r = self._slots[i]
                # (a row of such a request may be in the step in flight:
                # the device runs in order, so whoever is given the blocks
                # writes after it, nothing of them reaches the trie, and
                # the row's token is dropped at the read)
                if r is not None and r.cancelled:
                    self._retire(r, insert=False)
                elif (r is not None and r.deadline is not None
                        and now > r.deadline):
                    self._retire(r, insert=False)
                    expired.append(r)
            # deadline enforcement ACROSS admission queueing: a request
            # that expired while waiting never occupies a slot
            still = []
            for r in self._pending:
                if r.deadline is not None and now > r.deadline:
                    expired.append(r)
                else:
                    still.append(r)
            self._pending[:] = still
            # (read under the lock: no request here was submitted after it)
            admitted = time.monotonic()
            waits = "slot"
            for i in range(self.max_slots):
                if self._slots[i] is None and self._pending:
                    cand = self._pending[0]
                    short_of_window = cand.waited_for == "window_blocks"
                    self._short_of = "blocks"
                    if not self._claim_blocks(cand, pending_copies):
                        waits = self._short_of
                        break  # pool exhausted: stay queued
                    self._pending.pop(0)
                    self._slots[i] = cand
                    cand.slot = i
                    cand.admitted_ts = admitted
                    waited = admitted - cand.submit_ts
                    self._count_together(
                        requests_admitted=1, pending_wait_s=waited,
                        **({"requests_waited_window_blocks": 1,
                            "window_blocks_wait_s": waited}
                           if short_of_window else {}))
            if self._pending:
                self._pending[0].waited_for = waits
            active_now = sum(r is not None for r in self._slots)
            self.stats["max_concurrent"] = max(
                self.stats["max_concurrent"], active_now)
            have_pending = bool(self._pending)
        for r in expired:
            self.stats["deadline_drops"] += 1
            try:
                r.emit(DeadlineExceededError(
                    f"request deadline elapsed after "
                    f"{now - r.submit_ts:.3f}s (generated "
                    f"{r.generated}/{r.max_new_tokens})"))
            except Exception:
                pass
        # COW copies and adoption scatters run AFTER the lock drops
        # (submit()/kv_state() must stay responsive while a device op
        # runs) but BEFORE the step consumes the tables
        adopts = []
        for kind, req, *rest in pending_copies:
            if kind == "adopt":
                adopts.append((req, rest[0], rest[1]))
                continue
            if kind == "restore":
                # the prefix's state into the request's slot: its position
                # starts at the snapshot's depth, so the step zeroes nothing
                took: Dict[str, float] = {}
                with tracing.stamp("serve::restore_state", took):
                    self._cache = self._restore_fn(
                        self._cache, self._snaps, rest[0], req.slot)
                self._count_together(
                    state_snapshots_restored=1,
                    state_snapshot_bytes=self._state_bytes,
                    state_restore_s=took["serve::restore_state"])
                continue
            (src, dst) = rest
            try:
                self._cache = self._copy_fn(self._cache, src, dst)
                with self._lock:
                    self.pool.release(src)
            except BaseException as e:
                # device error: un-claim THIS request and fail it
                # (its table is already published, so abort_all
                # would miss the cow ref); then let the loop's abort
                # path handle the rest of the engine state
                with self._lock:
                    self.pool.release(src)
                    self._retire(req, insert=False)
                try:
                    req.emit(e)
                except Exception:
                    pass
                raise
        if adopts:
            self._apply_adoptions(adopts)
        if self._snapshots:
            self._count_snapshot_evictions()    # (a claim may evict nodes)
        return active_now, have_pending

    def _apply_adoptions(self, adopts: List[tuple]) -> None:
        """Scatter every pending adoption's shipped blocks in ONE device
        op (a burst of arrivals must cost the in-flight decodes one
        kernel, not K), then emit each request's prefill-side first
        token. Ids/payload pad to a power-of-two bucket (pad ids are
        out-of-range -> dropped by the scatter) so the jit retraces per
        bucket, not per batch geometry."""
        import jax.numpy as jnp

        ids: List[int] = []
        for _req, table_prefix, _kv in adopts:
            ids.extend(table_prefix)
        pad = _next_pow2(len(ids)) - len(ids)
        ids = ids + [self.pool.num_blocks] * pad
        batch = {}
        for name in self._cache:       # K, V and every other pool
            parts = [kv[name] for _req, _tp, kv in adopts]
            if pad:
                parts.append(np.zeros(
                    parts[0].shape[:1] + (pad,) + parts[0].shape[2:],
                    parts[0].dtype))
            batch[name] = jnp.asarray(
                parts[0] if len(parts) == 1
                else np.concatenate(parts, axis=1))
        try:
            self._cache = self._scatter_fn(
                self._cache, jnp.asarray(np.asarray(ids, np.int32)), batch)
        except BaseException as e:
            with self._lock:
                for req, _tp, _kv in adopts:
                    self._retire(req, insert=False)
            for req, _tp, _kv in adopts:
                try:
                    req.emit(e)
                except Exception:
                    pass
            raise
        now = time.monotonic()
        for req, _tp, _kv in adopts:
            req.generated = 1
            self._observe_emit(req, now)
            req.emit(req.last_token)
            self.stats["tokens_generated"] += 1
            if req.generated >= req.max_new_tokens or (
                    req.eos is not None and req.last_token == req.eos):
                # degenerate single-token request: done at adoption
                with self._lock:
                    self._retire(req, insert=True)
                req.emit(None)

    def step(self) -> bool:
        """Admit pending requests, dispatch the next step for every active
        slot (one decode token, or up to ``prefill_chunk`` prompt tokens),
        THEN read the step before it and route its tokens to their
        requests: one step of lookahead, so the host's work runs under the
        device's. Returns True if any slot is active or requests are
        waiting."""
        import jax
        import jax.numpy as jnp

        stamps = self._stamps = {}
        dispatched = read = None
        with tracing.stamp("serve::step") as call:
            self._process_migrations(jax, jnp)
            with tracing.stamp("serve.step::admit", stamps):
                active_now, have_pending = self._sweep_and_admit()
            if active_now == 0:
                # nothing to plan: the step in flight (the last tokens of
                # requests that already left their slots) is still read
                # before the engine reports idle
                with tracing.stamp("serve.step::settle", stamps):
                    read = self._settle()
                self._sample_gauges()
                busy = have_pending
            else:
                self._ensure_params()
                read = self._advance_paged(jax, jnp)
                dispatched = self._inflight
                if read is not None:
                    with tracing.stamp("serve.step::route", stamps):
                        self._route(*read)
                self._sample_gauges()
                # the call's wall time less its wait for the device, beside
                # its count (and the lookahead's, as before)
                self._count_together(
                    steps=1, steps_dispatched_ahead=int(read is not None),
                    step_host_s=time.monotonic() - call.t0
                    - stamps.get("serve.step::read", 0.0))
                busy = True
        if (dispatched or read) and tracing.tracing_enabled():
            # ONE record a call: six a step would turn the ring over in
            # seconds and push the requests' spans out
            attrs = {name.rpartition("::")[2] + "_ms": round(sec * 1e3, 4)
                     for name, sec in stamps.items()}
            if dispatched:
                attrs.update(dispatched.facts("dispatched_"))
            if read:
                attrs.update(read[0].facts("read_"))
            call.record(attrs)
        return busy

    def _settle(self) -> Optional[tuple]:
        """Read the step in flight, if any, and route its tokens: after
        this the host knows every token the device has sampled. Whoever
        needs a request's newest token or the cache as the host's books
        describe it (migration) settles first. Returns what was read."""
        step, self._inflight = self._inflight, None
        if step is None:
            return None
        read = self._read(step)
        self._route(*read)
        return read

    def _read(self, step: _StepInFlight) -> tuple:
        """Wait for a dispatched step's samples and its few device
        counters: ONE host transfer of a few hundred bytes (and, under
        ``capture``, of the logits). It returns when THAT step ends, not
        the one dispatched after it: the copies were started before the
        next program was queued (``copy_to_host_async`` in
        ``_advance_paged``)."""
        import jax

        with tracing.stamp("serve.step::read", self._stamps):
            ids, device_counts, logits = jax.device_get(
                (step.ids, step.counts,
                 step.logits if self.capture else None))
        # its device arrays (the ``[max_slots, V]`` logits among them) are
        # freed HERE, inside the call's own accounting: left to the
        # caller's last reference they were freed as ``step()`` returned,
        # 0.3-0.9 ms of host time a call that no stamp covered
        step.ids = step.counts = step.logits = None
        if "expert_tokens" in device_counts:
            per_layer = np.asarray(device_counts["expert_tokens"])
            self._count("moe_expert_tokens_sum", int(per_layer.sum()))
            self._count("moe_expert_tokens_max",
                        int(per_layer.max(axis=1).sum()))
            self._count("moe_experts_hit", int((per_layer > 0).sum()))
            self._count("moe_pairs_held", int(per_layer.sum()))
            if self._kernels["expert_impl"] == "pallas":
                self._count("moe_kernel_pairs", int(per_layer.sum()))
        # the cadence, one read to the next (with a step in flight the wait
        # itself is short, and says nothing); from its own dispatch for a
        # step that found the device idle
        t = time.monotonic()
        step_dt = step.seconds = t - max(step.dispatched, self._read_at)
        self._read_at = t
        # the time of THIS step to the kind of ITS rows (a stamp taken
        # around ``step()`` from outside pairs the rows being dispatched
        # with the wait for the step before them)
        self._count_together(**{"steps_" + step.kind: 1,
                                "step_s_" + step.kind: step_dt})
        if step.second_width:
            self._count_together(steps_second_width=1,
                                 step_s_second_width=step_dt)
        if step.index > 0:
            # skip the FIRST step: it includes the jit trace+compile
            # (seconds), and seeding the EWMA with it would make a
            # freshly booted SLO-armed replica shed the very burst that
            # scaled it up
            self.admission.observe_step(step_dt)
        return step, ids, logits

    def _route(self, step: _StepInFlight, ids: np.ndarray,
               logits) -> None:
        """Hand a read step's tokens to their requests, and end those it
        ends."""
        now = time.monotonic()
        for i, req, samples, last in step.rows:
            if not samples:
                continue   # still prefilling; nothing was sampled
            if req.ended or req.cancelled:
                # the token is dropped; a request that left its slot at
                # dispatch is in no sweep's reach, so its blocks go here
                if last:
                    with self._lock:
                        self._retire(req, insert=False)
                continue
            tok = int(ids[i])
            if logits is not None:
                # the tap: the row's logits pass through the host-side
                # reference sampler IMMEDIATELY before the row's emit
                ref = self._sample(logits[i])
                if self.temperature <= 0.0:
                    assert ref == tok, (
                        f"device sample {tok} != host arg-max {ref}")
            req.last_token = tok
            req.generated += 1
            req.gen_tokens.append(tok)
            self._observe_emit(req, now)
            if req.prefill_only:
                self._emit_prefill_export(req, tok)
                continue
            req.emit(tok)
            self.stats["tokens_generated"] += 1
            if last or (req.eos is not None and tok == req.eos):
                # an ``eos`` is found one step late: the request is a row
                # of the step in flight too, whose token will be dropped.
                # Its blocks go back NOW all the same: that row's write
                # lies at a generated position, beyond every block the
                # trie is offered (full PROMPT blocks only), and the device
                # runs in order, so a later owner of the block writes
                # after it and reads only what it wrote itself.
                # lock: the trie insert mutates children dicts that a
                # concurrent kv_state()/load_state() may be iterating
                with self._lock:
                    self._retire(req, insert=True)
                req.emit(None)
                ahead = self._inflight
                if not last and ahead is not None and any(
                        r is req for _i, r, _s, _l in ahead.rows):
                    self._count("rows_run_past_end", 1)

    def _emit_prefill_export(self, req: _Request, tok: int) -> None:
        """Export INSTEAD of streaming: gather the prompt's blocks off
        the pool (one device op, one host transfer) and hand them to the
        sink with the sampled token; the blocks then release normally —
        full prompt blocks into the trie, so repeated system prompts
        prefill once even on a dedicated prefill pool. The id list is
        padded to a power-of-two bucket (repeating the last id — reads
        are harmless) so the gather retraces per BUCKET, not per block
        count: a mid-stream jit compile would stall every in-flight
        decode for hundreds of ms. With a step in flight the gather queues
        behind it (the device runs in order; that step touches none of
        these blocks)."""
        import jax
        import jax.numpy as jnp

        nb = self.pool.blocks_for_tokens(len(req.prompt))
        bucket = min(_next_pow2(nb), self._tbl_width)
        ids = req.table[:nb] + [req.table[nb - 1]] * (bucket - nb)
        kv_dev = self._gather_fn(
            self._cache, jnp.asarray(np.asarray(ids, np.int32)))
        kv_host = jax.device_get(kv_dev)
        self.stats["exported"] += 1
        req.emit(KVExport(
            token=tok, prompt_len=len(req.prompt),
            block_size=self.pool.block_size,
            kv={name: np.asarray(x)[:, :nb]
                for name, x in kv_host.items()}))
        with self._lock:
            self._retire(req, insert=True)
        req.emit(None)

    # -- live-session migration (elastic serving, r20) ---------------------

    def begin_migration(self) -> List[tuple]:
        """Mark every live DECODING session for export off this engine.
        Returns ``[(request, reply_queue)]``; the loop thread services
        each entry at the top of its next step, putting either the
        export payload dict, ``None`` (the session finished on its own
        before the export ran — nothing left to migrate), or the
        exception that killed the export. Thread-safe; called by the
        deployment's drain path, NOT the loop thread.

        Only sessions past prefill with at least one sampled token
        qualify: a still-prefilling request has no consumer-visible
        progress worth shipping — re-prefilling it on another replica
        via the ordinary retry path costs the same compute as resuming
        a partial prefill would."""
        if self._by_kind:
            raise NotImplementedError(
                self._no_ship.format(what="live-session migration"))
        out: List[tuple] = []
        with self._lock:
            for r in self._slots:
                if (r is None or r.cancelled or r.prefill_only
                        or r.consumed < len(r.prompt)
                        or not r.gen_tokens):
                    continue
                reply: "queue.Queue[Any]" = queue.Queue()
                self._migrations.append((r, reply))
                out.append((r, reply))
        return out

    def _process_migrations(self, jax, jnp) -> None:
        """Service pending session exports on the loop thread (top of
        step, BEFORE the advance — the migrating slot must not decode a
        token its export would then miss). The step in flight is settled
        first: an export ships the session's newest token and the KV of
        every token fed."""
        with self._lock:
            if not self._migrations:
                return
            batch, self._migrations = self._migrations, []
        self._settle()
        for req, reply in batch:
            # the session may have finished/cancelled between the drain
            # thread's mark and this step (its blocks are already
            # released): nothing to migrate, consumer already got the
            # full stream
            with self._lock:
                gone = req.cancelled or req not in self._slots
            if gone:
                reply.put(None)
                continue
            try:
                reply.put(self._export_session(req, jax, jnp))
            except BaseException as e:  # noqa: BLE001 - ships to drain
                reply.put(e)

    def _export_session(self, req: _Request, jax, jnp) -> Dict[str, Any]:
        """Gather a live decoding session's cached KV ([L, nb, bs, kvh,
        hd] per tensor, positions 0..pos-1) and retire the slot. The
        cache covers exactly the FED tokens — prompt plus every sampled
        token except the newest (``last_token`` is sampled but not yet
        fed) — so the destination adopts with prompt=fed transcript,
        first_token=last_token, and decoding continues token-exact.
        Same power-of-two id bucketing as :meth:`_emit_prefill_export`
        (a mid-stream retrace would stall surviving decodes)."""
        nb = self.pool.blocks_for_tokens(req.pos)
        bucket = min(_next_pow2(nb), self._tbl_width)
        ids = req.table[:nb] + [req.table[nb - 1]] * (bucket - nb)
        kv_dev = self._gather_fn(
            self._cache, jnp.asarray(np.asarray(ids, np.int32)))
        kv_host = jax.device_get(kv_dev)
        fed = list(map(int, req.prompt)) + req.gen_tokens[:-1]
        with self._lock:
            self._retire(req, insert=True)
        self.stats["migrated_out"] += 1
        return {
            "kv": {name: np.asarray(x)[:, :nb]
                   for name, x in kv_host.items()},
            "fed_tokens": fed,
            "last_token": int(req.last_token),
            "pos": int(req.pos),
            "generated": int(req.generated),
            "max_new_tokens": int(req.max_new_tokens),
            "eos": req.eos,
            "block_size": self.pool.block_size,
        }

    def _advance_paged(self, jax, jnp):
        """Paged cache: decoding slots feed 1 token, prefilling slots
        feed up to ``prefill_chunk`` prompt tokens — one compiled
        program, no decode stall behind long prompts.

        Plans and dispatches the NEXT step from what the host knows
        without the tokens of the step in flight, then reads that one:
        returns ``(step, ids, logits)`` for :meth:`_route`, or None when
        nothing was in flight. A decoding row's table, position and count
        are known before its token is (blocks are claimed whole at
        admission), and the token itself is fed forward on the device."""
        prev = self._inflight
        with tracing.stamp("serve.step::build_inputs", self._stamps):
            rows, nvalid, real, run, chunk_rows, inputs = self._plan(
                prev, jnp)
        with tracing.stamp("serve.step::dispatch", self._stamps):
            out = self._step_fn(self.params, self._cache, *inputs)
            # (logits, cache, what only the device counts; a wrapper may
            # hand back the first two alone)
            self._cache = out[1]
            index = self.stats["steps"]
            self._ids = (self._sample_fn(out[0]) if self._sample_key is None
                         else self._sample_fn(out[0], self._sample_key,
                                              np.int32(index)))
            step = self._inflight = _StepInFlight(
                rows, self._ids, out[0], out[2] if len(out) > 2 else {},
                index, time.monotonic(), real, run,
                STEP_BUDGET < run < self.max_slots * self.prefill_chunk,
                chunk_rows)
            # the copies to the host start NOW, ahead of whatever is queued
            # after this step, so the read returns when this step ends
            for x in jax.tree.leaves((step.ids, step.counts)):
                x.copy_to_host_async()
            # the requests' books advance at DISPATCH
            full_width = step.kind == "full_width"
            for i, req, _samples, last in rows:
                n = int(nvalid[i])
                req.pos += n
                if req.consumed < len(req.prompt):
                    req.consumed += n
                    req.prefill_steps += 1
                    req.prefill_full_width += full_width
                if last:
                    self._slots[i] = None
                    self._leaving.append(req)
            if self._snapshots:
                self._take_snapshots(rows)
        return self._read(prev) if prev is not None else None

    def _count_snapshot_evictions(self) -> None:
        """The trie's own count of the snapshots it gave up with nothing to
        stand in for them (``PrefixCache.snapshot_evictions``: the least
        recently used taken back for a new one, or gone with an evicted
        node's block), mirrored into the engine's counters where it may have
        grown."""
        grown = self.prefix.snapshot_evictions - self._snapshots_evicted
        if grown:
            self._snapshots_evicted += grown
            self._count("state_snapshots_evicted", grown)

    def _take_snapshots(self, rows) -> None:
        """After the dispatch of a step that brought a row to its prompt's
        last block boundary: copy the slot's state to the snapshot pool (the
        device runs in order: after that step, before the row's next) and
        offer the prompt's blocks WITH it to the trie, which owns both from
        here on. The blocks are full and behind the row, so nobody writes
        them again. No id to be had: no copy, and nothing is inserted (a
        chain of blocks that ends on no snapshot serves no hit)."""
        bs = self.pool.block_size
        for i, req, _samples, _last in rows:
            at = req.snapshot_at
            if at is None or req.pos != at:
                continue
            req.snapshot_at = None
            with self._lock:
                entry = self.prefix.alloc_snapshot()
            self._count_snapshot_evictions()
            if entry is None:
                continue
            self._snaps = self._snapshot_fn(self._snaps, self._cache, i,
                                            entry)
            with self._lock:
                self.prefix.insert(req.prompt[:at].tolist(),
                                   req.table[:at // bs], snapshot=entry)
            self._count_together(state_snapshots_taken=1,
                                 state_snapshot_bytes=self._state_bytes)

    def _plan(self, prev: Optional[_StepInFlight], jnp) -> tuple:
        """The next step's rows and inputs from the slots as they stand
        and the host's counters of what the step will do: ``(rows,
        nvalid, real positions, positions the program will run, rows fed
        prompt tokens, the step program's five device inputs)``."""
        from ray_tpu.models.layouts import StepRows
        from ray_tpu.models.transformer import step_widths

        C = self.prefill_chunk
        # slots whose newest token is still on the device: the request
        # sampled in the step in flight (and is one token further along
        # than its ``generated`` says)
        on_device = {i for i, r, samples, _last in prev.rows
                     if samples and self._slots[i] is r} if prev else ()
        feed = np.zeros(self.max_slots, bool)
        rows = []
        tokens = np.zeros((self.max_slots, C), np.int32)
        nvalid = np.zeros(self.max_slots, np.int32)
        active = np.zeros(self.max_slots, bool)
        pos = np.zeros(self.max_slots, np.int32)
        tables = np.zeros((self.max_slots, self._tbl_width), np.int32)
        chunk_rows = 0
        for i, req in enumerate(self._slots):
            if req is None:
                continue
            active[i] = True
            pos[i] = req.pos
            tables[i, :len(req.table)] = req.table
            if req.consumed < len(req.prompt):
                n = min(C, len(req.prompt) - req.consumed)
                if req.snapshot_at is not None:
                    # a chunk ENDS on the boundary the state is copied at
                    n = min(n, req.snapshot_at - req.consumed)
                tokens[i, :n] = req.prompt[req.consumed:req.consumed + n]
                nvalid[i] = n
                samples = req.consumed + n >= len(req.prompt)
                chunk_rows += 1
            else:
                if i in on_device:
                    feed[i] = True
                else:
                    tokens[i, 0] = req.last_token
                nvalid[i] = 1
                samples = True
            # the row's token, once sampled, is the request's last: it is
            # not planned again, and its slot may be given away at once
            last = samples and (
                req.prefill_only or
                req.generated + (i in on_device) + 1 >= req.max_new_tokens)
            rows.append((i, req, samples, last))
            if self.win_pool is not None:
                with self._lock:
                    self._move_window(req, int(nvalid[i]))
                at = self._full_width
                tables[i, at:at + len(req.win_table)] = req.win_table
        # the positions the program multiplies its weights by, by the rule
        # it applies on the device: the narrowest of its widths that holds
        # the real ones
        real = int(nvalid.sum())
        run = next(w for w in step_widths(STEP_BUDGET, self.max_slots * C)
                   if real <= w)
        # and what the step's rows will read, by the layout's own rules
        reqs = [req for _, req, _samples, _last in rows]
        counted = self._layout.count(self.config, StepRows(
            pos[active], nvalid[active],
            np.array([len(r.table) for r in reqs], np.int64),
            np.array([len(r.win_table) for r in reqs], np.int64), chunk=C,
            block_size=self.pool.block_size, table_width=self._tbl_width,
            kernels=self._kernels))
        counted.update(step_positions_real=real, step_positions_run=run)
        for name, n in counted.items():
            self._count(name, n)
        inputs = (self._feed_fn(tokens, self._ids, feed),
                  jnp.asarray(tables), jnp.asarray(pos), jnp.asarray(nvalid),
                  jnp.asarray(active))
        return rows, nvalid, real, run, chunk_rows, inputs

    def _observe_emit(self, req: _Request, now: float) -> None:
        m = self._metrics
        if req.last_emit_ts is None:
            ttft = now - req.submit_ts
            self.admission.observe_ttft(ttft)
            if m:
                m["ttft"].observe(ttft)
            # the first token's time in the engine's two parts, which add
            # up to ``ttft`` exactly: in ``_pending``, then admitted
            self._count_together(first_tokens=1,
                                 prefill_s=now - req.admitted_ts,
                                 prefill_steps=req.prefill_steps)
            if req.trace is not None and tracing.tracing_enabled():
                at = [tracing.epoch_ns(t) for t in
                      (req.submit_ts, req.admitted_ts, now)]
                tracing.record_span(
                    "serve.llm::pending", at[0], at[1],
                    {"ahead_at_submit": req.ahead,
                     "waited_for": req.waited_for}, parent=req.trace)
                tracing.record_span(
                    "serve.llm::prefill", at[1], at[2],
                    {"prompt_tokens": len(req.prompt),
                     "prefix_hit_tokens": req.prefix_hit,
                     "steps": req.prefill_steps,
                     "full_width_steps": req.prefill_full_width,
                     **({"state_slot": req.slot} if self._stateful
                        else {})},
                    parent=req.trace)
        else:
            tpot = now - req.last_emit_ts
            self.admission.observe_tpot(tpot)
            if m:
                m["tpot"].observe(tpot)
        req.last_emit_ts = now

    _mirrored = ("hits", "misses", "hit_tokens")

    def _sample_gauges(self) -> None:
        m = self._metrics
        if not m:
            return
        role = {"role": self.role}
        with self._lock:
            m["pool_inflight"].set(
                sum(r is not None for r in self._slots), tags=role)
            m["pool_queued"].set(len(self._pending), tags=role)
        m["kv_free"].set(self.pool.free_count)
        m["kv_used"].set(self.pool.used_count)
        m["pool_kv_used_frac"].set(
            self.pool.used_count / max(self.pool.num_blocks, 1),
            tags=role)
        if self.prefix is not None:
            # counters mirror the trie's totals via deltas
            cur = self.prefix.stats()
            prev = getattr(self, "_mirror_prev", None) or {}
            for k in self._mirrored:
                d = cur[k] - prev.get(k, 0)
                if d > 0:
                    m[k].inc(d)
            self._mirror_prev = {k: cur[k] for k in self._mirrored}

    def _sample(self, logits: np.ndarray) -> int:
        if self.temperature <= 0.0:
            return int(np.argmax(logits))
        z = logits / self.temperature
        z = z - z.max()
        p = np.exp(z)
        p /= p.sum()
        return int(self._rng.choice(len(p), p=p))

    # -- introspection (routing + tests) ----------------------------------

    def kv_state(self) -> Dict[str, Any]:
        """Routing/leak-audit snapshot: block accounting + prefix-cache
        + admission state, all host-side (no device sync)."""
        # ONE lock covers slots AND the pool/trie walk: every trie
        # mutation site (claim in _sweep_and_admit, the finish/abort
        # releases) holds the same lock, so the iteration below can
        # never see a children dict resize mid-walk
        with self._lock:
            out: Dict[str, Any] = {
                "role": self.role,
                "inflight": sum(r is not None for r in self._slots),
                "queued": len(self._pending),
                "max_slots": self.max_slots,
                "kv_total": self.pool.num_blocks,
                "kv_free": self.pool.free_count,
                "kv_used": self.pool.used_count,
                "block_size": self.pool.block_size,
            }
            if self._by_kind:
                # the blocks of EVERY pool kind, and the kinds apart; the
                # state pool in slots and in bytes (a slot holds a
                # request's state of every layer)
                out["kv_pools"] = {
                    "full": {"total": self.pool.num_blocks,
                             "free": self.pool.free_count}}
            if self._stateful:
                out["kv_pools"]["state"] = {
                    "total": self.max_slots, "live": out["inflight"],
                    "slot_bytes": self._state_bytes,
                    "bytes": self.max_slots * self._state_bytes}
                if self._snapshots:
                    # (the trie's ``stats`` say how many it holds and has
                    # free: the two sum to this where none has leaked)
                    out["kv_pools"]["state"]["snapshots"] = \
                        self.prefix.snapshots
            if self.win_pool is not None:
                win = self.win_pool
                out["kv_pools"]["window"] = {
                    "total": win.num_blocks, "free": win.free_count,
                    "reserved": self._win_reserved}
                out["kv_total"] += win.num_blocks
                out["kv_free"] += win.free_count
                out["kv_used"] += win.used_count
            if self.prefix is not None:
                out["prefix"] = self.prefix.stats()
                # cluster-wide prefix affinity (serve/multiplex.py): the
                # top trie roots by hit-weight, published through load
                # reports so handles can route sessions sharing a system
                # prompt to the replica that already holds it
                try:
                    from ray_tpu import config as _knobs

                    top = int(_knobs.get("serve_prefix_digest_top"))
                except Exception:
                    top = 8
                out["prefix_digest"] = self.prefix.digest(top)
                # claimable = free + evictable-from-trie: the CAPACITY
                # signal (a warm replica's raw free count trends to ~0
                # because the trie retains every finished prompt — that
                # is cache value, not pressure)
                out["kv_claimable"] = (self.pool.free_count
                                       + self.prefix.evictable_count())
            else:
                out["kv_claimable"] = self.pool.free_count
        out["admission"] = self.admission.snapshot()
        return out


class LLMDeployment:
    """Serve deployment: continuous-batching token streaming.

    Deploy with a concurrent replica so requests interleave::

        app = serve.deployment(
            LLMDeployment,
            ray_actor_options={"max_concurrency": 16},
        ).bind("llama-debug", max_slots=8, max_len=256)
        handle = serve.run(app, name="llm")
        for tok in handle.options(stream=True).remote([1, 2, 3], 16):
            ...

    Each ``__call__`` is a SYNC generator (the proven streaming-replica
    path); the engine advances on a dedicated background thread, so all
    concurrent callers share one jitted decode program and one paged KV
    pool. ``slo`` (dict or :class:`SLOConfig`) arms admission shedding;
    per-request ``deadline_s`` bounds queueing AND streaming.
    """

    def __init__(self, model="llama-debug", *, max_slots: int = 8,
                 max_len: int = 256, temperature: float = 0.0,
                 params=None, seed: int = 0, paged: bool = True,
                 block_size: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 window_blocks: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 prefix_cache: bool = True,
                 slo: Optional[Any] = None,
                 role: str = "colocated",
                 stream_batch: int = 1):
        if not paged:
            raise ValueError(_DENSE_REMOVED)
        if isinstance(slo, dict):
            slo = SLOConfig(**slo)
        # stream_batch > 1 turns on micro-batched token delivery: each
        # streamed message carries a LIST of up to stream_batch tokens —
        # whatever the engine produced since the consumer last kept up.
        # The first token still ships the moment it exists (TTFT is
        # untouched); only messages the consumer was already lagging
        # behind coalesce. This is the 1M-request envelope knob: at high
        # request rates the per-token object/message cost dominates the
        # serving stack, and a lagging consumer turns N messages into 1.
        self._stream_batch = max(1, int(stream_batch))
        # advertised in load reports so handles can route by model
        # residency (serve/multiplex.py multiplexes several of these)
        self._model_id = model if isinstance(model, str) else "custom"
        self.engine = self._engine_factory(
            model, params, max_slots=max_slots, max_len=max_len,
            temperature=temperature, seed=seed,
            block_size=block_size, num_blocks=num_blocks,
            window_blocks=window_blocks,
            prefill_chunk=prefill_chunk, prefix_cache=prefix_cache,
            slo=slo, role=role)
        self._error: Optional[BaseException] = None
        self._wake = threading.Event()
        self._stop = False
        # disaggregation plumbing (ISSUE 13), all lazy: the transfer
        # plane only exists on replicas that actually ship/adopt blocks
        self._kv_sender = None
        self._kv_receiver = None
        self._xfer_lock = threading.Lock()
        self._ident: Optional[Dict[str, str]] = None
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="llm-decode-loop")
        self._thread.start()

    def _engine_factory(self, *args, **kw) -> LLMEngine:
        """Engine construction seam: subclasses swap the engine class
        (``serve/multiplex.py``'s speculative deployment) without
        re-plumbing the loop-thread/streaming machinery."""
        return LLMEngine(*args, **kw)

    def _loop(self) -> None:
        if self.engine.role == "prefill":
            # dedicated-decode-capacity analog for shared-core hosts:
            # the prefill pool's step loop yields the core to decode
            # cadence (see the serve_prefill_nice knob); on a real
            # accelerator the step blocks on the device, so this is free
            try:
                from ray_tpu import config as _knobs

                nice = int(_knobs.get("serve_prefill_nice"))
                if nice > 0:
                    os.setpriority(os.PRIO_PROCESS,
                                   threading.get_native_id(), nice)
            except Exception:
                pass
        while not self._stop:
            try:
                busy = self.engine.step()
            except BaseException as e:  # noqa: BLE001 - must not die silent
                # fail every outstanding request and surface via
                # check_health; the thread keeps running so a transient
                # backend error doesn't permanently kill the replica
                self._error = e
                self.engine.abort_all(e)
                self._wake.wait(timeout=1.0)
                self._wake.clear()
                continue
            if not busy:
                # idle: park until the next submit
                self._wake.wait(timeout=0.2)
                self._wake.clear()

    def __call__(self, prompt_tokens, max_new_tokens: int = 16,
                 eos: Optional[int] = None,
                 deadline_s: Optional[float] = None):
        q: "queue.Queue[Any]" = queue.Queue()

        def submit(trace):
            return self.engine.submit(prompt_tokens, max_new_tokens,
                                      q.put_nowait, eos=eos,
                                      deadline_s=deadline_s, trace=trace)

        return self._token_stream(q, submit, len(prompt_tokens),
                                  max_new_tokens, deadline_s)

    def _token_stream(self, q: "queue.Queue[Any]", submit,
                      n_prompt: int, max_new_tokens: int,
                      deadline_s: Optional[float]):
        """The streaming body shared by the colocated request path and
        the decode pool's adopt path: run ``submit`` (engine intake, given
        the queue span's traceparent), then drain the request's token queue
        to the caller."""
        from ray_tpu import config as _knobs
        stall_timeout = float(_knobs.get("llm_stall_timeout_s"))
        deadline = (time.monotonic() + deadline_s
                    if deadline_s is not None else None)
        # manual spans (not span()): this is a generator — a thread-local
        # span context held across a yield would leak onto whatever the
        # worker thread runs next (graftlint tracing-context-capture).
        # queue = admission wait to the FIRST token (slot contention +
        # prefill); stream = the whole token stream — the per-request
        # latency decomposition SLO admission control needs (ISSUE 7).
        # The engine records the queue span's two parts under it
        # (``serve.llm::pending``, ``::prefill``: ``_observe_emit``); what
        # is left as its self time is the hop to this consumer.
        stream_span = tracing.manual_span(
            "serve.llm::stream", {"prompt_tokens": n_prompt,
                                  "max_new_tokens": max_new_tokens,
                                  "role": self.engine.role})
        queue_span = tracing.manual_span(
            "serve.llm::queue", {},
            parent=stream_span.traceparent if stream_span else None)
        req = None
        produced = 0
        try:
            # submit INSIDE the try: a dead engine must still finish the
            # admission span (it is the SLO signal for failed admission)
            req = submit(queue_span.traceparent if queue_span else None)
            self._wake.set()
            while True:
                wait = stall_timeout
                if deadline is not None:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        raise DeadlineExceededError(
                            f"request deadline ({deadline_s}s) elapsed "
                            f"after {produced} tokens")
                    wait = min(wait, remaining)
                try:
                    tok = q.get(timeout=wait)
                except queue.Empty:
                    if (deadline is not None
                            and time.monotonic() >= deadline):
                        raise DeadlineExceededError(
                            f"request deadline ({deadline_s}s) elapsed "
                            f"after {produced} tokens")
                    raise TimeoutError(
                        f"llm decode loop produced no token for "
                        f"{stall_timeout:.0f}s"
                        + (f" (loop error: {self._error!r})"
                           if self._error else ""))
                if queue_span is not None:
                    queue_span.finish()
                    queue_span = None
                if tok is None:
                    return
                if isinstance(tok, (DeadlineExceededError,
                                    RequestShedError)):
                    raise tok  # admission/deadline verdicts pass through
                if isinstance(tok, BaseException):
                    raise RuntimeError(f"llm decode loop failed: {tok!r}")
                if self._stream_batch == 1:
                    produced += 1
                    yield tok
                    continue
                # micro-batched delivery: sweep whatever else the engine
                # already produced (bounded by stream_batch) into this
                # message; a terminal item found mid-sweep is handled
                # AFTER the tokens before it reach the consumer
                chunk = [tok]
                terminal = _NO_ITEM
                while len(chunk) < self._stream_batch:
                    try:
                        nxt = q.get_nowait()
                    except queue.Empty:
                        break
                    if nxt is None or isinstance(nxt, BaseException):
                        terminal = nxt
                        break
                    chunk.append(nxt)
                produced += len(chunk)
                yield chunk
                if terminal is _NO_ITEM:
                    continue
                if terminal is None:
                    return
                if isinstance(terminal, (DeadlineExceededError,
                                         RequestShedError)):
                    raise terminal
                raise RuntimeError(
                    f"llm decode loop failed: {terminal!r}")
        finally:
            # client stopped consuming (disconnect / GC'd generator):
            # free the slot instead of generating into an orphan queue
            if req is not None:
                self.engine.cancel(req)
            if queue_span is not None:
                # failed/abandoned BEFORE the first token: the admission
                # wait still gets recorded (it is the SLO signal), marked
                # as never having produced
                queue_span.finish(error="no token produced")
            if stream_span is not None:
                stream_span.finish({"tokens": produced})

    # -- disaggregated prefill/decode (ISSUE 13) ---------------------------

    def identity(self) -> Dict[str, str]:
        """This replica's transfer identity: actor id (channel naming)
        + node id (channel-vs-store path choice). Cached — the runtime
        context is task-local, so capture happens on first request."""
        if self._ident is None or self._ident["actor"] is None:
            # actor id is TASK-context-local: calls arriving outside a
            # task (the load-report push thread) see None — keep retrying
            # until a real request captures it. Channel names derive from
            # it, so it must be the unique actor id, never a placeholder.
            try:
                import ray_tpu

                ctx = ray_tpu.get_runtime_context()
                self._ident = {"actor": ctx.get_actor_id(),
                               "node": ctx.get_node_id(),
                               "role": self.engine.role}
            except Exception:
                # no runtime at all (in-process engine A/B harness):
                # a stable per-process host identity still lets the
                # same-host channel path work
                import os

                self._ident = {"actor": None,
                               "node": os.environ.get("RTPU_NODE_ID",
                                                      "local"),
                               "role": self.engine.role}
        return self._ident

    def _max_payload_bytes(self) -> int:
        eng = self.engine
        per_block = sum(
            int(c.dtype.itemsize) * int(np.prod(c.shape[2:]))
            * int(c.shape[0]) for c in eng._cache.values())
        return per_block * eng._tbl_width

    def prefill_export(self, prompt_tokens, transfer: Dict[str, Any],
                       deadline_s: Optional[float] = None
                       ) -> Dict[str, Any]:
        """Prefill-pool entry point: run chunked prefill, then ship the
        prompt's KV blocks toward the decode replica named by
        ``transfer`` ({req, dst, dst_node}) and return the transfer
        descriptor (+ first token in its meta). The payload moves over a
        DeviceChannel ring when both replicas share ``dst_node``'s host,
        else through the object store's chunk-parallel pull path."""
        from ray_tpu import config as _knobs
        from ray_tpu.serve.kv_transfer import KVSender

        stall_timeout = float(_knobs.get("llm_stall_timeout_s"))
        q: "queue.Queue[Any]" = queue.Queue()
        req = self.engine.submit(prompt_tokens, 1, q.put_nowait,
                                 deadline_s=deadline_s, prefill_only=True)
        self._wake.set()
        export = None
        try:
            wait = stall_timeout if deadline_s is None \
                else min(stall_timeout, deadline_s)
            while True:
                tok = q.get(timeout=wait)
                if isinstance(tok, KVExport):
                    export = tok
                    continue
                if tok is None:
                    break
                if isinstance(tok, BaseException):
                    raise tok
        except queue.Empty:
            raise TimeoutError(
                f"prefill produced no export for {wait:.0f}s"
                + (f" (loop error: {self._error!r})"
                   if self._error else ""))
        finally:
            self.engine.cancel(req)
        if export is None:
            raise RuntimeError("prefill finished without a KV export")
        with self._xfer_lock:
            if self._kv_sender is None:
                import uuid

                # actor id when deployed; a process-unique fallback for
                # the in-process harness (bench/replay A/B) — channel
                # names must never collide across senders on one host
                src = self.identity()["actor"] or uuid.uuid4().hex[:12]
                self._kv_sender = KVSender(
                    src, max_payload_bytes=self._max_payload_bytes())
        same_host = bool(transfer.get("dst_node")) and \
            transfer["dst_node"] == self.identity()["node"]
        return self._kv_sender.ship(
            export, req_id=transfer["req"], dst_id=transfer["dst"],
            same_host=same_host)

    def adopt_stream(self, prompt_tokens, desc: Dict[str, Any],
                     max_new_tokens: int = 16, eos: Optional[int] = None,
                     deadline_s: Optional[float] = None):
        """Decode-pool entry point: fetch the shipped KV-block batch
        named by ``desc``, adopt it into this engine's pool, and stream
        the tokens (the first one — sampled by prefill — included)."""
        from ray_tpu.serve.kv_transfer import KVReceiver

        with self._xfer_lock:
            if self._kv_receiver is None:
                self._kv_receiver = KVReceiver()
        q: "queue.Queue[Any]" = queue.Queue()

        def submit(trace):
            timeout = 30.0 if deadline_s is None else min(30.0, deadline_s)
            meta, kv = self._kv_receiver.fetch(desc, timeout=timeout)
            return self.engine.adopt(prompt_tokens, kv, meta["token"],
                                     max_new_tokens, q.put_nowait,
                                     eos=eos, deadline_s=deadline_s,
                                     trace=trace)

        return self._token_stream(q, submit, len(prompt_tokens),
                                  max_new_tokens, deadline_s)

    # -- elastic drain: migrate live sessions instead of re-prefilling -----

    def drain_sessions(self, destinations: List[Dict[str, Any]],
                       timeout_s: float = 30.0) -> Dict[str, Any]:
        """Preemption drain (r20): ship every live decode session's KV
        blocks to a surviving replica over the ISSUE-13 transfer plane,
        then hand each session's stream a migration marker so the caller
        splices the continuation — no re-prefill, token-exact under
        greedy sampling. ``destinations`` is a round-robin candidate
        list of ``{"dst": actor_id_hex, "dst_node": node_id|None}``.

        The marker rides the ordinary token stream (a dict is not a
        token): :class:`~ray_tpu.serve.disagg.DisaggHandle` intercepts
        it, reconstructs the fed-token prompt from what it already
        yielded, and calls ``adopt_stream`` on the destination. The
        re-emitted handoff token (adoption re-emits ``first_token``) is
        deduped handle-side."""
        from ray_tpu.serve.kv_transfer import KVSender
        from ray_tpu.util import events

        if not destinations:
            raise ValueError("drain needs at least one destination "
                             "replica")
        pending = self.engine.begin_migration()
        self._wake.set()
        me = self.identity()["actor"] or ""
        try:
            events.emit("serve_drain", replica=me,
                        role=self.engine.role, sessions=len(pending),
                        destinations=len(destinations))
        except Exception:
            pass
        migrated, failed, finished = 0, 0, 0
        if pending:
            with self._xfer_lock:
                if self._kv_sender is None:
                    import uuid

                    src = me or uuid.uuid4().hex[:12]
                    self._kv_sender = KVSender(
                        src, max_payload_bytes=self._max_payload_bytes())
        for n, (req, reply) in enumerate(pending):
            dst = destinations[n % len(destinations)]
            try:
                payload = reply.get(timeout=timeout_s)
                if payload is None:
                    finished += 1   # completed on its own pre-export
                    continue
                if isinstance(payload, BaseException):
                    raise payload
                import uuid

                req_id = uuid.uuid4().hex
                same_host = bool(dst.get("dst_node")) and \
                    dst["dst_node"] == self.identity()["node"]
                desc = self._kv_sender.ship(
                    KVExport(token=payload["last_token"],
                             prompt_len=payload["pos"],
                             block_size=payload["block_size"],
                             kv=payload["kv"]),
                    req_id=req_id, dst_id=dst["dst"],
                    same_host=same_host)
                # budget: adoption re-emits the handoff token (deduped
                # by the handle), so the destination owes remaining+1
                req.emit({"__migrate__": {
                    "desc": desc, "dst": dst["dst"],
                    "prompt_tokens": payload["fed_tokens"],
                    "first_token": payload["last_token"],
                    "max_new_tokens": (payload["max_new_tokens"]
                                       - payload["generated"] + 1),
                    "eos": payload["eos"],
                }})
                req.emit(None)
                migrated += 1
                try:
                    events.emit("serve_session_migrated", replica=me,
                                dst=dst["dst"], req=req_id,
                                kv_tokens=payload["pos"],
                                generated=payload["generated"])
                except Exception:
                    pass
            except BaseException as e:  # noqa: BLE001 - per-session
                failed += 1
                try:
                    req.emit(e)
                except Exception:
                    pass
        return {"sessions": len(pending), "migrated": migrated,
                "failed": failed, "finished": finished}

    def stats(self) -> Dict[str, Any]:
        out = dict(self.engine.stats)
        out.update(self.engine.kv_state())
        return out

    def kv_state(self) -> Dict[str, Any]:
        return self.engine.kv_state()

    def load_state(self) -> Dict[str, Any]:
        """Load report the replica pushes to the controller (the routing
        + autoscaling signal). ``kv_free`` here is the CLAIMABLE count
        (free list + trie-evictable): prefix-cache retention is cache
        value, not pressure — reporting the raw free count would make a
        warm idle replica read ~100% utilized, steering traffic to cold
        replicas and driving autoscale runaway."""
        s = self.engine.kv_state()
        return {"inflight": s["inflight"] + s["queued"],
                # model-residency + prefix-affinity routing signals
                # (ISSUE 16): which models this replica can serve without
                # a swap-in, and the hottest cached system prompts
                "models": {self._model_id: {
                    "state": "hbm",
                    "inflight": s["inflight"] + s["queued"]}},
                "prefix_digest": s.get("prefix_digest", []),
                "kv_free": s.get("kv_claimable", s.get("kv_free", 0)),
                "kv_total": s.get("kv_total", 0),
                # disaggregation routing signals (ISSUE 13): pool role,
                # host identity for channel-vs-store transfer choice,
                # and queue depth for prefill-capacity picking
                "role": s.get("role", "colocated"),
                "node": self.identity()["node"],
                "actor": self.identity()["actor"],
                "queued": s["queued"],
                "max_slots": s["max_slots"],
                "block_size": s.get("block_size", 0)}

    def check_health(self) -> None:
        if not self._thread.is_alive():
            raise RuntimeError("llm decode loop thread died")
        if self._error is not None:
            raise RuntimeError(f"llm decode loop error: {self._error!r}")

    def close(self) -> None:
        """Stop the step loop and unlink/close the KV-transfer planes.
        In-process harnesses MUST call this: outside a
        runtime the rings carry the unswept ``nosess`` session prefix,
        so GC-time ``__del__`` is the only other thing standing between
        a ring and a leaked /dev/shm segment."""
        self._stop = True
        with self._xfer_lock:
            planes, self._kv_sender, self._kv_receiver = (
                (self._kv_sender, self._kv_receiver), None, None)
        for plane in planes:
            if plane is not None:
                try:
                    plane.close()
                except Exception:
                    pass

    def __del__(self):  # pragma: no cover - GC-time best effort
        if hasattr(self, "_xfer_lock"):  # else the constructor raised
            self.close()
