"""Multi-model serving plane: multiplexing, speculation, affinity.

Reference role: ``python/ray/serve/multiplex.py`` (``_ModelMultiplexWrapper``
— per-replica LRU of resident models behind ``serve.multiplexed``) grown
into a first-class subsystem over this repo's paged LLM engine:

- :class:`ModelRegistry` — per-replica catalog of many models (full
  weight sets and LoRA-style deltas over a shared base,
  ``models/delta.py``). Cold weights live in the ARENA OBJECT STORE via
  the public ``ray_tpu.put`` (spill-compressed tiers come free); a model
  materializes on first use and is LRU-evicted under a byte budget —
  never while an in-flight request pins it. All-pinned + over budget
  sheds with ``RequestShedError(reason="model_budget")``.
- :class:`MultiplexedLLMDeployment` — one replica serving N models:
  lazy per-model :class:`~ray_tpu.serve.llm.LLMDeployment` engines whose
  params page in/out through the registry (``params_provider`` /
  ``drop_params`` seam in ``serve/llm.py``). Load reports grow a
  resident-model digest + merged prefix digest, which
  ``serve/handle.py`` folds into routing (model affinity beats a
  swap-in; prefix affinity beats a prefill).
- :class:`SpeculativeLLMEngine` — greedy speculative decoding: a
  drafter proposes up to ``spec_k`` tokens per round and the target
  verifies them in ONE batched :func:`~ray_tpu.models.verify_step_paged`
  call (all-position logits). Emitted tokens are ALWAYS the target's
  exact greedy sequence: position ``j``'s draft is accepted iff it
  EQUALS the target argmax at ``j-1``'s continuation, and the first
  mismatch is replaced by that argmax (the "free correction"), so a
  round advances ``accepted+1`` tokens for one target step. Drafters:
  ``"ngram"`` (prompt-lookup — zero model cost) and ``"model"`` (a
  small draft model riding its OWN paged cache). A per-request
  acceptance EWMA falls the request back to plain decode when drafts
  stop landing (speculation must never lose more than the draft cost).

Everything here stays on the PUBLIC task/actor/object API (architecture
seam, CLAUDE.md): weights travel as ordinary objects, residency is read
via ``ray_tpu.util.state.object_store_tier``, and no experimental
transport is touched.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

import numpy as np

from ray_tpu.serve.admission import RequestShedError
from ray_tpu.serve.llm import LLMDeployment, LLMEngine, _Request


def _registry_metrics():
    try:
        from ray_tpu.util import metric_defs as md

        return {
            "swaps": md.get("rtpu_serve_model_swaps_total"),
            "resident": md.get("rtpu_serve_model_resident"),
            "bytes": md.get("rtpu_serve_model_resident_bytes"),
            "sheds": md.get("rtpu_serve_admission_sheds_total"),
        }
    except Exception:  # metrics plane unavailable (bare unit tests)
        return None


class ModelRegistry:
    """Per-replica model catalog with arena-paged weights.

    ``register`` parks a model's HOST weights in the object store (one
    ``ray_tpu.put`` — the store's spill tiers age cold models to disk
    for free; outside a runtime an in-process host copy stands in).
    ``ensure_resident`` materializes device params on demand, LRU-
    evicting unpinned models past ``budget_bytes``; ``pin``/``unpin``
    bracket every in-flight request so its model can NEVER be paged out
    mid-decode. A delta variant (``base=..., delta=...``) materializes
    via :func:`~ray_tpu.models.apply_delta` — untouched leaves are
    SHARED with the base, and the variant is charged only its unique
    bytes.

    Thread-safe; materialization runs under the lock (swap-in must be
    atomic against the evictor — the chaos test kills a replica exactly
    here and asserts no stranded store refs).
    """

    def __init__(self, *, budget_bytes: Optional[int] = None):
        from ray_tpu import config as _knobs

        self.budget_bytes = int(
            budget_bytes if budget_bytes is not None
            else _knobs.get("serve_model_budget_bytes"))
        self._lock = threading.RLock()
        self._entries: Dict[str, Dict[str, Any]] = {}
        self._metrics = _registry_metrics()

    # -- catalog -----------------------------------------------------------

    def register(self, model_id: str, config: Any = None, *,
                 params: Any = None, base: Optional[str] = None,
                 delta: Any = None, seed: int = 0) -> None:
        """Add a model. ``config`` is a preset name or
        ``TransformerConfig`` (inherited from ``base`` when omitted);
        ``params`` is an optional host pytree (random-initialized when
        absent and no delta). ``base``+``delta`` registers a LoRA-style
        variant over an already-registered base."""
        import jax

        from ray_tpu import models

        with self._lock:
            if model_id in self._entries:
                raise ValueError(f"model {model_id!r} already registered")
            if base is not None:
                be = self._entries.get(base)
                if be is None:
                    raise ValueError(
                        f"base {base!r} of {model_id!r} is not registered")
                if delta is None:
                    raise ValueError(
                        f"variant {model_id!r} names base={base!r} but "
                        "carries no delta")
                cfg = be["config"] if config is None else config
            else:
                if config is None:
                    raise ValueError(
                        f"model {model_id!r} needs a config (or a base)")
                cfg = config
            if isinstance(cfg, str):
                cfg = models.get_config(cfg)

            host = None
            nbytes = 0
            if base is None:
                if params is None:
                    params = models.init_params(
                        jax.random.PRNGKey(seed), cfg)
                host = jax.tree_util.tree_map(np.asarray, params)
                nbytes = models.params_bytes(host)
            else:
                # the variant's host payload is the (small) delta; its
                # RESIDENT charge is the rebuilt projection leaves plus
                # the factors — every other leaf is shared with the base
                host = jax.tree_util.tree_map(np.asarray, delta)
                L, d = cfg.n_layers, cfg.d_model
                itemsize = np.dtype(cfg.param_dtype).itemsize
                shapes = {"wq": d * cfg.n_heads * cfg.hdim,
                          "wk": d * cfg.kv_heads * cfg.hdim,
                          "wv": d * cfg.kv_heads * cfg.hdim,
                          "wo": cfg.n_heads * cfg.hdim * d}
                nbytes = models.delta_bytes(host) + sum(
                    L * shapes[t] * itemsize for t in host["targets"])

            ref = None
            try:
                import ray_tpu

                if ray_tpu.is_initialized():
                    ref = ray_tpu.put(host)
                    host = None  # the store owns the cold copy
            except Exception:
                ref = None
            self._entries[model_id] = {
                "config": cfg, "ref": ref, "host": host, "bytes": nbytes,
                "params": None, "pins": 0, "last_used": 0.0,
                "swaps_in": 0, "swaps_out": 0, "base": base,
                "evict_cb": None,
            }

    def __contains__(self, model_id: str) -> bool:
        with self._lock:
            return model_id in self._entries

    def models(self) -> List[str]:
        with self._lock:
            return list(self._entries)

    def config_of(self, model_id: str):
        with self._lock:
            return self._entries[model_id]["config"]

    def bind(self, model_id: str, evict_cb: Callable[[], None]) -> None:
        """Attach the engine-side drop hook eviction must fire (the
        engine and the registry reference the SAME params pytree)."""
        with self._lock:
            self._entries[model_id]["evict_cb"] = evict_cb

    # -- pinning (in-flight requests) --------------------------------------

    def pin(self, model_id: str) -> None:
        with self._lock:
            self._entries[model_id]["pins"] += 1

    def unpin(self, model_id: str) -> None:
        with self._lock:
            e = self._entries[model_id]
            if e["pins"] <= 0:
                raise RuntimeError(f"unpin of unpinned model {model_id!r}")
            e["pins"] -= 1

    # -- residency ---------------------------------------------------------

    def _fetch_host(self, e: Dict[str, Any]):
        if e["host"] is not None:
            return e["host"]
        import ray_tpu

        return ray_tpu.get(e["ref"])

    def _materialize(self, e: Dict[str, Any]):
        import jax.numpy as jnp
        from jax import tree_util

        from ray_tpu import models

        host = self._fetch_host(e)
        if e["base"] is None:
            return tree_util.tree_map(jnp.asarray, host)
        base_params = self._ensure_resident_locked(e["base"])
        delta = tree_util.tree_map(jnp.asarray, host)
        return models.apply_delta(base_params, delta)

    def resident_bytes(self) -> int:
        with self._lock:
            return sum(e["bytes"] for e in self._entries.values()
                       if e["params"] is not None)

    def _evict_for(self, need: int, keep: str) -> None:
        """Make room for ``need`` bytes (caller holds the lock)."""
        if self.budget_bytes <= 0:
            return
        while True:
            resident = sum(e["bytes"] for e in self._entries.values()
                           if e["params"] is not None)
            if resident + need <= self.budget_bytes:
                return
            victims = [(mid, e) for mid, e in self._entries.items()
                       if e["params"] is not None and e["pins"] == 0
                       and mid != keep]
            if not victims:
                if self._metrics:
                    self._metrics["sheds"].inc(
                        tags={"reason": "model_budget"})
                raise RequestShedError(
                    f"model {keep!r} needs {need} resident bytes but the "
                    f"budget ({self.budget_bytes}) is held by pinned "
                    "models", reason="model_budget")
            mid, e = min(victims, key=lambda kv: kv[1]["last_used"])
            e["params"] = None
            e["swaps_out"] += 1
            if self._metrics:
                self._metrics["swaps"].inc(tags={"direction": "out"})
            cb = e["evict_cb"]
            if cb is not None:
                try:
                    cb()
                except Exception:
                    pass

    def _ensure_resident_locked(self, model_id: str):
        e = self._entries.get(model_id)
        if e is None:
            raise KeyError(f"unknown model {model_id!r}")
        if e["params"] is None:
            self._evict_for(e["bytes"], keep=model_id)
            e["params"] = self._materialize(e)
            e["swaps_in"] += 1
            if self._metrics:
                self._metrics["swaps"].inc(tags={"direction": "in"})
        e["last_used"] = time.monotonic()
        return e["params"]

    def ensure_resident(self, model_id: str):
        """Materialized device params for ``model_id`` (swap-in on
        miss, LRU eviction for room). Raises ``RequestShedError``
        (reason ``model_budget``) when nothing can be evicted."""
        with self._lock:
            return self._ensure_resident_locked(model_id)

    # -- introspection -----------------------------------------------------

    def _tier(self, e: Dict[str, Any]) -> str:
        if e["params"] is not None:
            return "hbm"
        if e["ref"] is None:
            return "host"
        try:
            from ray_tpu.util.state import object_store_tier

            t = object_store_tier(e["ref"])
            return {"shm": "host", "spilled": "spilled"}.get(t, "host")
        except Exception:
            return "host"

    def snapshot(self) -> Dict[str, Dict[str, Any]]:
        with self._lock:
            out = {
                mid: {"state": self._tier(e), "bytes": e["bytes"],
                      "pins": e["pins"], "swaps_in": e["swaps_in"],
                      "swaps_out": e["swaps_out"], "base": e["base"],
                      "resident": e["params"] is not None}
                for mid, e in self._entries.items()
            }
        if self._metrics:
            by_state: Dict[str, int] = {}
            for rec in out.values():
                by_state[rec["state"]] = by_state.get(rec["state"], 0) + 1
            for state, n in by_state.items():
                self._metrics["resident"].set(n, tags={"state": state})
            self._metrics["bytes"].set(
                sum(r["bytes"] for r in out.values() if r["resident"]))
        return out

    def free(self) -> None:
        """Drop every store ref (replica shutdown — the chaos test
        asserts no stranded arena weight refs survive a close)."""
        with self._lock:
            refs = [e.pop("ref") for e in self._entries.values()
                    if e.get("ref") is not None]
            for e in self._entries.values():
                e["ref"] = None
                e["params"] = None
        if refs:
            try:
                import ray_tpu

                ray_tpu.free(refs)
            except Exception:
                pass


# -- drafters ---------------------------------------------------------------


class _NgramDraft:
    """Prompt-lookup drafting (assisted-generation style): the last
    ``n``-gram of the request's history is searched backwards through
    the history itself and the tokens FOLLOWING the most recent earlier
    occurrence become the draft. Zero model cost — acceptance is pure
    upside — and strong exactly where speculation pays most (templated
    continuations, code, the repetitive tails greedy decoding produces).
    """

    def __init__(self, n: int = 3):
        self.n = max(1, int(n))

    def propose(self, req: _Request, k: int, engine: "SpeculativeLLMEngine",
                slot: int) -> List[int]:
        hist = engine._spec_state(req)["hist"]
        n = min(self.n, len(hist) - 1)
        while n >= 1:
            pat = hist[-n:]
            for s in range(len(hist) - n - 1, -1, -1):
                if hist[s:s + n] == pat:
                    return [int(t) for t in hist[s + n:s + n + k]]
            n -= 1
        return []

    def prune(self, live: Set[_Request]) -> None:  # stateless
        pass


class _ModelDraft:
    """Model drafting: a small draft model (same vocab as the target)
    rides its OWN paged cache with one statically-owned table per
    target slot. Per round it catches up on committed history in
    chunks (re-feeding overwrites any stale rejected-draft KV — the
    same write-before-gather guarantee the verify path relies on), then
    rolls the draft forward token by token. ``fed`` counts COMMITTED
    tokens only, so a rejected draft costs nothing to undo."""

    def __init__(self, config: Any = None, params: Any = None, *,
                 seed: int = 1):
        self._config = config
        self._params_in = params
        self._seed = seed
        self._ready = False

    def _ensure(self, engine: "SpeculativeLLMEngine") -> None:
        if self._ready:
            return
        import jax

        from ray_tpu import models

        cfg = self._config if self._config is not None else engine.config
        if isinstance(cfg, str):
            cfg = models.get_config(cfg)
        if cfg.vocab_size != engine.config.vocab_size:
            raise ValueError(
                f"draft vocab {cfg.vocab_size} != target vocab "
                f"{engine.config.vocab_size} (tokens must be "
                "interchangeable)")
        self.cfg = cfg
        self.params = (self._params_in if self._params_in is not None
                       else models.init_params(
                           jax.random.PRNGKey(self._seed), cfg))
        self.S = engine.max_slots
        self.W = engine._tbl_width
        self.C = engine.prefill_chunk
        nb = self.S * self.W
        self._cache = models.init_cache_paged(cfg, nb,
                                              engine.pool.block_size)
        self._tables = np.arange(nb, dtype=np.int32).reshape(self.S,
                                                             self.W)

        def raw(params, cache, tokens, tables, pos, nvalid, active):
            from ray_tpu.models import decode_step_paged

            return decode_step_paged(params, cache, tokens, tables, pos,
                                     nvalid, cfg, active=active)

        from ray_tpu.util.device_plane import registered_jit

        self._step = registered_jit(raw, name="serve::mux_decode_step",
                                    component="serve",
                                    donate_argnums=(1,))
        self._bound: List[Optional[_Request]] = [None] * self.S
        self._fed = [0] * self.S
        self._ready = True

    def _advance(self, slot: int, toks: List[int], pos0: int) -> np.ndarray:
        import jax
        import jax.numpy as jnp

        tokens = np.zeros((self.S, self.C), np.int32)
        nvalid = np.zeros(self.S, np.int32)
        active = np.zeros(self.S, bool)
        pos = np.zeros(self.S, np.int32)
        tokens[slot, :len(toks)] = toks
        nvalid[slot] = len(toks)
        active[slot] = True
        pos[slot] = pos0
        logits, self._cache = self._step(
            self.params, self._cache, jnp.asarray(tokens),
            jnp.asarray(self._tables), jnp.asarray(pos),
            jnp.asarray(nvalid), jnp.asarray(active))
        return np.asarray(jax.device_get(logits))[slot]

    def propose(self, req: _Request, k: int, engine: "SpeculativeLLMEngine",
                slot: int) -> List[int]:
        self._ensure(engine)
        if self._bound[slot] is not req:
            self._bound[slot] = req
            self._fed[slot] = 0
        hist = engine._spec_state(req)["hist"]
        fed = self._fed[slot]
        logits = None
        while fed < len(hist):
            n = min(self.C, len(hist) - fed)
            logits = self._advance(slot, hist[fed:fed + n], fed)
            fed += n
        self._fed[slot] = fed
        if logits is None:  # pragma: no cover - hist grows every round
            return []
        out = [int(np.argmax(logits))]
        while len(out) < k:
            logits = self._advance(slot, [out[-1]], fed + len(out) - 1)
            out.append(int(np.argmax(logits)))
        return out[:k]

    def prune(self, live: Set[_Request]) -> None:
        if not self._ready:
            return
        for i, r in enumerate(self._bound):
            if r is not None and r not in live:
                self._bound[i] = None
                self._fed[i] = 0


# -- speculative engine ------------------------------------------------------


class SpeculativeLLMEngine(LLMEngine):
    """Greedy speculative decoding over the paged slot engine.

    Every step is ONE batched :func:`~ray_tpu.models.verify_step_paged`
    call (all-position logits): prefilling slots feed prompt chunks
    exactly as the base engine does, while decoding slots feed
    ``[last_token, d_1..d_k']`` and accept the longest draft prefix that
    matches the target's own argmax chain — emitted tokens are exactly
    the plain-greedy sequence by construction (the acceptance check IS
    equality with the target argmax, and the first mismatch emits that
    argmax instead). KV written at rejected positions is never attended
    (the visibility mask stops at the request's committed position) and
    is overwritten by the next round's feed before it could be.

    Requires greedy sampling (``temperature<=0``): lossless speculation
    is only defined against a deterministic target.
    """

    SPEC_WARMUP = 6  # rounds before the acceptance EWMA may trip

    def __init__(self, config, params=None, *, spec_k: Optional[int] = None,
                 drafter: str = "ngram", draft_model: Any = None,
                 draft_params: Any = None, draft_seed: int = 1,
                 spec_accept_floor: Optional[float] = None,
                 ngram: int = 3, **kw):
        from ray_tpu import config as _knobs

        self.spec_k = int(spec_k if spec_k is not None
                          else _knobs.get("spec_k"))
        if self.spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {self.spec_k}")
        self.spec_accept_floor = float(
            spec_accept_floor if spec_accept_floor is not None
            else _knobs.get("spec_accept_floor"))
        if kw.get("temperature", 0.0) > 0.0:
            raise ValueError(
                "speculative decoding requires greedy sampling "
                "(temperature <= 0): lossless acceptance is defined "
                "against the target's deterministic argmax chain")
        # the slot grid's chunk width carries BOTH prefill chunks and
        # the verify window [last, d1..dk]
        pc = int(kw.get("prefill_chunk")
                 or _knobs.get("llm_prefill_chunk"))
        kw["prefill_chunk"] = max(pc, self.spec_k + 1)
        super().__init__(config, params, **kw)

        import jax

        from ray_tpu.util.device_plane import registered_jit

        self._verify_fn = registered_jit(self._raw_verify_paged,
                                         name="serve::verify_step_paged",
                                         component="serve",
                                         donate_argnums=(1,))
        if drafter == "ngram":
            self._draft = _NgramDraft(n=ngram)
        elif drafter == "model":
            self._draft = _ModelDraft(draft_model, draft_params,
                                      seed=draft_seed)
        else:
            raise ValueError(
                f"unknown drafter {drafter!r} (want 'ngram' or 'model')")
        self.drafter = drafter
        # per-request speculation state, identity-keyed (_Request is
        # eq=False); pruned to live slots every step
        self._spec: Dict[_Request, Dict[str, Any]] = {}
        self.stats.update(spec_rounds=0, spec_proposed=0,
                          spec_accepted=0, spec_fallbacks=0)

    @staticmethod
    def _init_metrics():
        m = LLMEngine._init_metrics()
        if m is None:
            return None
        try:
            from ray_tpu.util import metric_defs as md

            m.update(
                spec_rounds=md.get("rtpu_spec_rounds_total"),
                spec_proposed=md.get("rtpu_spec_proposed_tokens_total"),
                spec_accepted=md.get("rtpu_spec_accepted_tokens_total"),
                spec_fallbacks=md.get("rtpu_spec_fallbacks_total"))
        except Exception:
            pass
        return m

    def _raw_verify_paged(self, params, cache, tokens, tables, pos,
                          nvalid, active):
        from ray_tpu.models import verify_step_paged

        return verify_step_paged(params, cache, tokens, tables, pos,
                                 nvalid, self.config, active=active)

    def _spec_state(self, req: _Request) -> Dict[str, Any]:
        st = self._spec.get(req)
        if st is None:
            st = {"ewma": 1.0, "rounds": 0, "off": False, "hist": None}
            self._spec[req] = st
        return st

    def step(self) -> bool:
        """The base loop with multi-token emission: a decoding slot may
        route up to ``accepted+1`` tokens per step."""
        import jax
        import jax.numpy as jnp

        active_now, have_pending = self._sweep_and_admit()
        if active_now == 0:
            if self._spec:
                self._spec.clear()
                self._draft.prune(set())
            self._sample_gauges()
            return have_pending
        self._ensure_params()

        t0 = time.perf_counter()
        emitted, nvalid = self._advance_spec(jax, jnp)
        if self.stats["steps"] > 0:
            self.admission.observe_step(time.perf_counter() - t0)

        now = time.monotonic()
        for i, req in enumerate(self._slots):
            if req is None:
                continue
            if req.consumed < len(req.prompt):
                req.consumed += int(nvalid[i])
                if req.consumed < len(req.prompt):
                    continue  # still prefilling; nothing sampled yet
            for tok in emitted[i]:
                req.last_token = tok
                req.generated += 1
                self._observe_emit(req, now)
                if req.prefill_only:
                    self._emit_prefill_export(req, tok)
                    break  # slot cleared by the export
                req.emit(tok)
                self.stats["tokens_generated"] += 1
                if req.generated >= req.max_new_tokens or (
                        req.eos is not None and tok == req.eos):
                    with self._lock:
                        self._release_blocks(req, insert=True)
                    req.emit(None)
                    self._slots[i] = None
                    break
        live = {r for r in self._slots if r is not None}
        if len(self._spec) > len(live):
            self._spec = {r: st for r, st in self._spec.items()
                          if r in live}
            self._draft.prune(live)
        self.stats["steps"] += 1
        self._sample_gauges()
        return True

    def _advance_spec(self, jax, jnp) -> Tuple[List[List[int]], np.ndarray]:
        """One verify round: build the batch (prefill chunks as usual,
        draft windows for decoders), run the all-logits step, accept.
        Returns per-slot emitted-token lists plus the fed counts (the
        step loop advances ``consumed`` off them for prefill rows)."""
        C = self.prefill_chunk
        tokens = np.zeros((self.max_slots, C), np.int32)
        nvalid = np.zeros(self.max_slots, np.int32)
        active = np.zeros(self.max_slots, bool)
        pos = np.zeros(self.max_slots, np.int32)
        tables = np.zeros((self.max_slots, self._tbl_width), np.int32)
        drafted: List[List[int]] = [[] for _ in range(self.max_slots)]
        for i, req in enumerate(self._slots):
            if req is None:
                continue
            active[i] = True
            pos[i] = req.pos
            tables[i, :len(req.table)] = req.table
            if req.consumed < len(req.prompt):
                n = min(C, len(req.prompt) - req.consumed)
                tokens[i, :n] = req.prompt[req.consumed:req.consumed + n]
                nvalid[i] = n
                continue
            st = self._spec_state(req)
            if st["hist"] is None:
                # first decode round: committed history = prompt + the
                # boundary token sampled when prefill finished
                st["hist"] = req.prompt.tolist() + [req.last_token]
            d: List[int] = []
            if not st["off"] and not req.prefill_only:
                # clamp so the round can never write past the claimed
                # table: accepted+1 <= k'+1 stays within max_new
                k = min(self.spec_k, C - 1,
                        req.max_new_tokens - req.generated - 1)
                if k > 0:
                    d = self._draft.propose(req, k, self, i)[:k]
            drafted[i] = d
            tokens[i, 0] = req.last_token
            for j, t in enumerate(d):
                tokens[i, 1 + j] = t
            nvalid[i] = 1 + len(d)

        logits, self._cache = self._verify_fn(
            self.params, self._cache, jnp.asarray(tokens),
            jnp.asarray(tables), jnp.asarray(pos), jnp.asarray(nvalid),
            jnp.asarray(active))
        logits_h = np.asarray(jax.device_get(logits))  # [B, C, V]

        emitted: List[List[int]] = [[] for _ in range(self.max_slots)]
        for i, req in enumerate(self._slots):
            if req is None:
                continue
            n = int(nvalid[i])
            if req.consumed < len(req.prompt):
                req.pos += n
                if req.consumed + n >= len(req.prompt):
                    # prompt completes this step: the last valid
                    # position's logits seed generation (same token the
                    # base engine samples)
                    emitted[i] = [int(np.argmax(logits_h[i, n - 1]))]
                continue
            d = drafted[i]
            toks = [int(np.argmax(logits_h[i, 0]))]
            accepted = 0
            for j, dt in enumerate(d):
                if dt != toks[-1]:
                    break  # mismatch: toks[-1] IS the correction
                accepted += 1
                toks.append(int(np.argmax(logits_h[i, j + 1])))
            # commit exactly the accepted prefix + the target token:
            # pos advances past what the greedy chain confirmed, never
            # past what was fed
            req.pos += accepted + 1
            st = self._spec_state(req)
            if d:
                m = self._metrics if (self._metrics
                                      and "spec_rounds" in self._metrics
                                      ) else None
                self.stats["spec_rounds"] += 1
                self.stats["spec_proposed"] += len(d)
                self.stats["spec_accepted"] += accepted
                if m:
                    m["spec_rounds"].inc()
                    m["spec_proposed"].inc(len(d))
                    if accepted:
                        m["spec_accepted"].inc(accepted)
                st["rounds"] += 1
                st["ewma"] = 0.5 * st["ewma"] + 0.5 * (accepted / len(d))
                if (st["rounds"] >= self.SPEC_WARMUP
                        and st["ewma"] < self.spec_accept_floor):
                    # acceptance collapsed: this request decodes plain
                    # from here on (k'=0 rides the same verify fn)
                    st["off"] = True
                    self.stats["spec_fallbacks"] += 1
                    if m:
                        m["spec_fallbacks"].inc()
            if st["hist"] is not None:
                st["hist"].extend(toks)
            emitted[i] = toks
        return emitted, nvalid

    def kv_state(self) -> Dict[str, Any]:
        out = super().kv_state()
        out["spec"] = {k: self.stats[k] for k in
                       ("spec_rounds", "spec_proposed", "spec_accepted",
                        "spec_fallbacks")}
        return out


class SpeculativeLLMDeployment(LLMDeployment):
    """:class:`~ray_tpu.serve.llm.LLMDeployment` whose engine decodes
    speculatively. Extra kwargs: ``spec_k``, ``drafter`` ("ngram" |
    "model"), ``draft_model``/``draft_params`` (the "model" drafter's
    config + optional host weights), ``spec_accept_floor``."""

    def __init__(self, model="llama-debug", *, spec_k: Optional[int] = None,
                 drafter: str = "ngram", draft_model: Any = None,
                 draft_params: Any = None, draft_seed: int = 1,
                 spec_accept_floor: Optional[float] = None,
                 ngram: int = 3, **kw):
        self._spec_opts = dict(spec_k=spec_k, drafter=drafter,
                               draft_model=draft_model,
                               draft_params=draft_params,
                               draft_seed=draft_seed,
                               spec_accept_floor=spec_accept_floor,
                               ngram=ngram)
        super().__init__(model, **kw)

    def _engine_factory(self, *args, **kw) -> SpeculativeLLMEngine:
        return SpeculativeLLMEngine(*args, **kw, **self._spec_opts)


# -- the multiplexed deployment ---------------------------------------------


class MultiplexedLLMDeployment:
    """One replica serving MANY models: per-model engines created
    lazily, weights paged through a shared :class:`ModelRegistry`.

    ``models_spec`` maps ``model_id`` to a preset name, a
    ``TransformerConfig``, or a dict ``{"config": ..., "params": ...,
    "base": ..., "delta": ..., "seed": ...}`` (base+delta registers a
    LoRA-style variant). Requests address a model with
    ``model_id=`` (default: the first registered model)::

        dep = MultiplexedLLMDeployment(
            {"m0": "llama-debug", "m1": "gpt2-debug"},
            budget_bytes=1 << 20)
        for tok in dep([1, 2, 3], 16, model_id="m1"):
            ...

    Each model gets its own :class:`~ray_tpu.serve.llm.LLMDeployment`
    (loop thread, admission, streaming, paged KV + prefix trie) the
    first time a request lands on it — the registry's swap counters are
    the lazy-paging proof the multiplexing A/B asserts on. A request
    PINS its model for its stream's lifetime, so eviction (LRU under
    ``budget_bytes``) only ever fires on idle engines; the engine's
    ``params_provider`` re-acquires on the next step after a page-out.
    ``load_state`` aggregates the per-model engines and adds the
    resident-model digest + merged prefix digest that
    ``serve/handle.py`` routes on.
    """

    def __init__(self, models_spec, *, default_model: Optional[str] = None,
                 budget_bytes: Optional[int] = None,
                 speculative: bool = False, spec_k: Optional[int] = None,
                 drafter: str = "ngram", draft_model: Any = None,
                 draft_params: Any = None,
                 spec_accept_floor: Optional[float] = None,
                 max_slots: int = 8, max_len: int = 256,
                 temperature: float = 0.0, seed: int = 0,
                 block_size: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 prefix_cache: bool = True, slo: Optional[Any] = None,
                 stream_batch: int = 1):
        if isinstance(models_spec, (list, tuple)):
            models_spec = {mid: mid for mid in models_spec}
        if not models_spec:
            raise ValueError("models_spec is empty")
        self.registry = ModelRegistry(budget_bytes=budget_bytes)
        for mid, spec in models_spec.items():
            if isinstance(spec, dict):
                self.registry.register(
                    mid, spec.get("config"), params=spec.get("params"),
                    base=spec.get("base"), delta=spec.get("delta"),
                    seed=spec.get("seed", seed))
            else:
                self.registry.register(mid, spec, seed=seed)
        self._default = default_model or next(iter(models_spec))
        if self._default not in self.registry:
            raise ValueError(
                f"default_model {self._default!r} is not registered")
        self._dep_kw = dict(max_slots=max_slots, max_len=max_len,
                            temperature=temperature, seed=seed,
                            block_size=block_size, num_blocks=num_blocks,
                            prefill_chunk=prefill_chunk,
                            prefix_cache=prefix_cache, slo=slo,
                            stream_batch=stream_batch)
        self._spec_kw = (dict(spec_k=spec_k, drafter=drafter,
                              draft_model=draft_model,
                              draft_params=draft_params,
                              spec_accept_floor=spec_accept_floor)
                         if speculative else None)
        self._deps: Dict[str, LLMDeployment] = {}
        self._dep_lock = threading.Lock()
        self._ident: Optional[Dict[str, Any]] = None

    # -- engine lifecycle --------------------------------------------------

    def _get_dep(self, model_id: str) -> LLMDeployment:
        with self._dep_lock:
            dep = self._deps.get(model_id)
            if dep is None:
                cfg = self.registry.config_of(model_id)
                params = self.registry.ensure_resident(model_id)
                if self._spec_kw is not None:
                    dep = SpeculativeLLMDeployment(cfg, params=params,
                                                   **self._spec_kw,
                                                   **self._dep_kw)
                else:
                    dep = LLMDeployment(cfg, params=params,
                                        **self._dep_kw)
                dep._model_id = model_id
                dep.engine.params_provider = (
                    lambda m=model_id: self.registry.ensure_resident(m))
                self.registry.bind(model_id, dep.engine.drop_params)
                self._deps[model_id] = dep
        return dep

    # -- request path ------------------------------------------------------

    def __call__(self, prompt_tokens, max_new_tokens: int = 16,
                 model_id: Optional[str] = None, eos: Optional[int] = None,
                 deadline_s: Optional[float] = None):
        mid = model_id or self._default
        if mid not in self.registry:
            raise ValueError(
                f"unknown model_id {mid!r}; registered: "
                f"{sorted(self.registry.models())}")
        # pin FIRST: between the residency check and the stream's end
        # this model must be un-evictable (the engine only reads params
        # while it has active work, and active work implies this pin)
        self.registry.pin(mid)
        try:
            self.registry.ensure_resident(mid)
            dep = self._get_dep(mid)
            inner = dep(prompt_tokens, max_new_tokens, eos=eos,
                        deadline_s=deadline_s)
        except BaseException:
            self.registry.unpin(mid)
            raise

        def stream():
            try:
                yield from inner
            finally:
                self.registry.unpin(mid)

        return stream()

    # -- replica surface (serve protocol) ----------------------------------

    def identity(self) -> Dict[str, Any]:
        if self._ident is None or self._ident.get("actor") is None:
            try:
                import ray_tpu

                ctx = ray_tpu.get_runtime_context()
                self._ident = {"actor": ctx.get_actor_id(),
                               "node": ctx.get_node_id()}
            except Exception:
                import os

                self._ident = {
                    "actor": None,
                    "node": os.environ.get("RTPU_NODE_ID",
                                           f"proc-{os.getpid()}")}
        return self._ident

    def stats(self) -> Dict[str, Any]:
        with self._dep_lock:
            deps = dict(self._deps)
        out: Dict[str, Any] = {"models": self.registry.snapshot()}
        for mid, dep in deps.items():
            out[mid] = dep.stats()
        return out

    def load_state(self) -> Dict[str, Any]:
        with self._dep_lock:
            deps = dict(self._deps)
        states = {mid: dep.load_state() for mid, dep in deps.items()}
        ident = self.identity()
        out: Dict[str, Any] = {
            "inflight": sum(s["inflight"] for s in states.values()),
            "kv_free": sum(s["kv_free"] for s in states.values()),
            "kv_total": sum(s["kv_total"] for s in states.values()),
            "role": "colocated",
            "node": ident["node"],
            "actor": ident["actor"],
            "queued": sum(s["queued"] for s in states.values()),
            "max_slots": (sum(s["max_slots"] for s in states.values())
                          or self._dep_kw["max_slots"]),
            "block_size": next((s["block_size"] for s in states.values()
                                if s.get("block_size")), 0),
        }
        snap = self.registry.snapshot()
        out["models"] = {
            mid: {"state": rec["state"],
                  "inflight": states.get(mid, {}).get("inflight", 0),
                  "swaps_in": rec["swaps_in"],
                  "swaps_out": rec["swaps_out"]}
            for mid, rec in snap.items()
        }
        agg: Dict[str, int] = {}
        for s in states.values():
            for key, w in s.get("prefix_digest", []):
                agg[key] = agg.get(key, 0) + int(w)
        try:
            from ray_tpu import config as _knobs

            top = int(_knobs.get("serve_prefix_digest_top"))
        except Exception:
            top = 8
        out["prefix_digest"] = sorted(
            agg.items(), key=lambda kv: -kv[1])[:top]
        return out

    def check_health(self) -> None:
        with self._dep_lock:
            deps = list(self._deps.values())
        for dep in deps:
            dep.check_health()

    def close(self) -> None:
        with self._dep_lock:
            deps, self._deps = list(self._deps.values()), {}
        for dep in deps:
            try:
                dep.close()
            except Exception:
                pass
        self.registry.free()
