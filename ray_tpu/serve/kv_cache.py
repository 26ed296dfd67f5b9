"""Paged KV-cache bookkeeping: refcounted block pool + prefix trie.

The serving tier's memory manager (vLLM's PagedAttention block manager
role, arxiv 2309.06180; the Gemma-on-TPU serving comparison in PAPERS.md
shows paged KV + batching policy — not raw FLOPs — decide TPU serving
throughput). Physical KV storage is a device array of fixed-size token
blocks (``models.init_cache_paged``); THIS module is the host-side truth
about who owns which block:

- :class:`BlockPool` — a refcounted free-list over the physical blocks.
  Admission claims blocks, not slots; a request holds one reference per
  table entry, the prefix cache holds one per trie node, and a block
  returns to the free list only when the last reference drops — which is
  exactly the leak-detection surface the chaos tests assert on (free
  count returns to baseline after a replica death).
- :class:`PrefixCache` — a hash trie keyed by FULL blocks of prompt
  tokens. Two requests whose prompts share a system prefix map the
  shared tokens to the SAME immutable physical blocks; only full blocks
  are ever shared, so shared blocks are never written (a capped match
  that reuses a partial tail block goes through copy-on-write instead —
  the pool's :meth:`BlockPool.need_cow` + the engine's device-side
  ``models.copy_kv_block``). Eviction is LRU over leaves whose only
  remaining reference is the trie's own. For a layout whose layers also
  carry recurrent state (``Layout.snapshots``) a prefix is its blocks AND
  the state at its end: a node may own a SNAPSHOT (an id of the engine's
  snapshot pool), and a match lands only where one is kept
  (:meth:`PrefixCache.match_snapshot`).

Pure host-side data structures (no jax, no device state): unit-testable
without a mesh, and the engine stays the single owner of device arrays.
"""

from __future__ import annotations

import hashlib
import time
from typing import Dict, List, Optional, Sequence, Tuple


def prefix_key_digest(tokens: Sequence[int]) -> str:
    """Stable cross-process digest of one block's token tuple — the key
    replicas publish in their prefix digest and handles recompute from a
    request's first prompt block to route for affinity. Content-hashed
    (not id-based) so two replicas that independently cached the same
    system prompt advertise the SAME key."""
    raw = ",".join(str(int(t)) for t in tokens).encode()
    return hashlib.blake2b(raw, digest_size=8).hexdigest()


class KVCacheError(RuntimeError):
    """Invariant violation in block accounting (double free, foreign
    block) — always a bug, never load-dependent."""


class BlockPool:
    """Refcounted pool of physical KV block ids ``0..num_blocks-1``.

    ``alloc`` is all-or-nothing (admission must never half-claim), and
    every block's lifecycle is ref-based: allocation returns blocks at
    refcount 1; ``retain``/``release`` move them; refcount 0 returns the
    block to the free list. LIFO reuse keeps recently-touched HBM warm.
    """

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 1 or block_size < 1:
            raise ValueError(
                f"need >=1 block of >=1 tokens, got {num_blocks}x{block_size}")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self._ref: List[int] = [0] * num_blocks

    # -- views -------------------------------------------------------------

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return self.num_blocks - len(self._free)

    def refcount(self, block_id: int) -> int:
        return self._ref[block_id]

    def blocks_for_tokens(self, n_tokens: int) -> int:
        """Table length covering ``n_tokens`` positions."""
        return -(-max(n_tokens, 0) // self.block_size)

    # -- lifecycle ---------------------------------------------------------

    def alloc(self, n: int) -> Optional[List[int]]:
        """Claim ``n`` blocks at refcount 1, or None (nothing claimed) if
        fewer than ``n`` are free — admission decides queue vs shed."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._ref[b] = 1
        return out

    def retain(self, block_id: int) -> None:
        if self._ref[block_id] <= 0:
            raise KVCacheError(f"retain of free block {block_id}")
        self._ref[block_id] += 1

    def release(self, block_id: int) -> bool:
        """Drop one reference; True when the block returned to the free
        list (the caller held the last reference)."""
        r = self._ref[block_id]
        if r <= 0:
            raise KVCacheError(f"release of free block {block_id}")
        self._ref[block_id] = r - 1
        if r == 1:
            self._free.append(block_id)
            return True
        return False

    def release_all(self, block_ids: Sequence[int]) -> int:
        return sum(1 for b in block_ids if self.release(b))

    def need_cow(self, block_id: int) -> bool:
        """True when writing into ``block_id`` requires copy-on-write:
        someone else (another request or the prefix trie) also holds it."""
        return self._ref[block_id] > 1


class _TrieNode:
    __slots__ = ("key", "block_id", "children", "parent", "last_used",
                 "hit_weight", "snapshot")

    def __init__(self, key: Optional[Tuple[int, ...]],
                 block_id: Optional[int], parent: Optional["_TrieNode"]):
        self.key = key
        self.block_id = block_id
        self.parent = parent
        self.children: Dict[Tuple[int, ...], "_TrieNode"] = {}
        self.last_used = 0.0
        # tokens reused through this ROOT child (only root children
        # accumulate weight — the digest ranks system prompts, and a
        # system prompt is identified by its first block)
        self.hit_weight = 0
        # id of the recurrent state kept at this node's END (the engine's
        # snapshot pool), or None
        self.snapshot: Optional[int] = None


class PrefixCache:
    """Hash trie mapping chains of FULL token blocks to physical blocks.

    ``match`` walks the prompt block-by-block and retains every matched
    block on behalf of the caller (the request's table references); the
    match is capped at ``len(tokens) - 1`` so at least one prompt token
    always runs through the model — its logits seed sampling. When the
    cap lands mid-block the tail block is returned as a copy-on-write
    source, never as a table entry.

    ``insert`` registers a finished request's full prompt blocks;
    existing chains are adopted as-is (no duplicate physical blocks for
    one prefix). ``evict`` reclaims LRU leaves whose only reference is
    the trie's.

    ``snapshots`` > 0 (a layout with recurrent state): the trie also owns
    that many snapshot ids. A node holds one where the engine copied the
    state at its end (``insert(.., snapshot=id)``); ``match_snapshot`` lands
    on the DEEPEST node of the matched path that holds one, never between
    two; ``alloc_snapshot`` hands out a free id or takes one back from a
    node: first from a node with a snapshot both above and below it on its
    path (a prompt that leaves the chain between them falls back to the
    one above), else from the least recently used. A node outlives its
    snapshot and still lends its blocks to a hit that lands above it; a
    node evicted for its block takes its snapshot with it.
    ``snapshot_evictions`` counts the snapshots given up that NOTHING STOOD
    IN FOR (the least recently used, and those gone with their block): a
    prompt on such a path lands shallower or nowhere. One taken from between
    two others is ``snapshots_superseded``.
    """

    def __init__(self, pool: BlockPool, snapshots: int = 0):
        self.pool = pool
        self.block_size = pool.block_size
        self._root = _TrieNode(None, None, None)
        self._nodes = 0
        self.snapshots = snapshots
        self._snap_free: List[int] = list(range(snapshots - 1, -1, -1))
        self._snap_nodes: Dict[int, _TrieNode] = {}
        self.snapshot_evictions = 0
        self.snapshots_superseded = 0
        # lookup-level counters (the engine mirrors them into metrics)
        self.hits = 0
        self.misses = 0
        self.hit_tokens = 0
        self.evictions = 0

    def __len__(self) -> int:
        return self._nodes

    def _chunks(self, tokens: Sequence[int]) -> List[Tuple[int, ...]]:
        bs = self.block_size
        n_full = len(tokens) // bs
        return [tuple(tokens[i * bs:(i + 1) * bs]) for i in range(n_full)]

    # -- lookup ------------------------------------------------------------

    def match(self, tokens: Sequence[int]
              ) -> Tuple[List[int], int, Optional[int]]:
        """Longest shared prefix of ``tokens`` already cached.

        Returns ``(full_blocks, matched_tokens, cow_src)``:
        ``full_blocks`` are retained for the caller and usable as-is;
        ``matched_tokens`` counts reused positions (capped at
        ``len(tokens) - 1``); ``cow_src`` is a block id (also retained)
        whose first ``matched_tokens % block_size`` positions must be
        COPIED into a fresh block when the cap split a block — the caller
        releases it after the device copy.
        """
        node = self._root
        chain: List[_TrieNode] = []
        now = time.monotonic()
        for key in self._chunks(tokens):
            child = node.children.get(key)
            if child is None:
                break
            child.last_used = now
            chain.append(child)
            node = child
        if not chain:
            self.misses += 1
            return [], 0, None
        first_child = chain[0]
        matched = len(chain) * self.block_size
        cow_src: Optional[int] = None
        if matched >= len(tokens):
            # cap below the full prompt: the final matched block is only
            # partially reused -> copy-on-write source, not a table entry
            matched = len(tokens) - 1
            tail = chain.pop()
            if matched % self.block_size:
                cow_src = tail.block_id
                self.pool.retain(cow_src)
        blocks = [n.block_id for n in chain]
        for b in blocks:
            self.pool.retain(b)
        self.hits += 1
        self.hit_tokens += matched
        first_child.hit_weight += matched
        return blocks, matched, cow_src

    def match_snapshot(self, tokens: Sequence[int]
                       ) -> Tuple[List[int], int, Optional[int]]:
        """:meth:`match` for a layout with recurrent state: the longest
        cached prefix of ``tokens`` that ENDS at a node holding a snapshot
        and leaves at least one token to run. Returns ``(blocks, matched
        tokens, snapshot id)``, the blocks retained for the caller; ``([], 0,
        None)`` where no node of the path holds one: the caller then
        prefills from a zero state, never from a state that is not the
        prefix's own. A hit ends on a block boundary, so nothing is ever
        copied on write."""
        node = self._root
        chain: List[_TrieNode] = []
        landed = 0
        now = time.monotonic()
        for key in self._chunks(tokens[:len(tokens) - 1]):
            child = node.children.get(key)
            if child is None:
                break
            child.last_used = now
            chain.append(child)
            if child.snapshot is not None:
                landed = len(chain)
            node = child
        if not landed:
            self.misses += 1
            return [], 0, None
        blocks = [n.block_id for n in chain[:landed]]
        for b in blocks:
            self.pool.retain(b)
        matched = landed * self.block_size
        self.hits += 1
        self.hit_tokens += matched
        chain[0].hit_weight += matched
        return blocks, matched, chain[landed - 1].snapshot

    # -- registration ------------------------------------------------------

    def insert(self, tokens: Sequence[int], block_ids: Sequence[int],
               snapshot: Optional[int] = None) -> int:
        """Register a request's prompt: ``block_ids[i]`` holds tokens
        ``[i*bs, (i+1)*bs)``. Only full blocks are inserted; new nodes
        retain their block for the trie, existing nodes keep theirs (the
        request's duplicate block simply gets released by its owner).
        ``snapshot``: the id of the state at the END of ``tokens`` (whole
        blocks), owned by the last node from here on; a node that already
        holds one keeps it and the id goes back to the free ones.
        Returns how many NEW blocks the trie adopted."""
        node = self._root
        adopted = 0
        now = time.monotonic()
        for i, key in enumerate(self._chunks(tokens)):
            if i >= len(block_ids):
                break
            child = node.children.get(key)
            if child is None:
                child = _TrieNode(key, block_ids[i], node)
                self.pool.retain(block_ids[i])
                node.children[key] = child
                self._nodes += 1
                adopted += 1
            child.last_used = now
            node = child
        if snapshot is not None:
            if node is self._root or node.snapshot is not None:
                self._snap_free.append(snapshot)
            else:
                node.snapshot = snapshot
                self._snap_nodes[snapshot] = node
        return adopted

    # -- snapshots -----------------------------------------------------------

    def _drop_snapshot(self, node: _TrieNode) -> int:
        snap, node.snapshot = node.snapshot, None
        del self._snap_nodes[snap]
        return snap

    def _between(self, node: _TrieNode) -> bool:
        """A snapshot lies both above and below ``node`` on its path."""
        up = node.parent
        while up is not None and up.snapshot is None:
            up = up.parent
        if up is None:
            return False
        stack = list(node.children.values())
        while stack:
            n = stack.pop()
            if n.snapshot is not None:
                return True
            stack.extend(n.children.values())
        return False

    def alloc_snapshot(self) -> Optional[int]:
        """An id for a new snapshot (the caller's until it is inserted or
        freed): a free one, else one taken from a node by the class's rule;
        ``None`` where the trie owns none to give."""
        if self._snap_free:
            return self._snap_free.pop()
        owners = sorted(self._snap_nodes.values(), key=lambda n: n.last_used)
        if not owners:
            return None
        victim = next((n for n in owners if self._between(n)), None)
        if victim is None:
            victim = owners[0]
            self.snapshot_evictions += 1
        else:
            self.snapshots_superseded += 1
        return self._drop_snapshot(victim)

    def free_snapshot(self, snapshot: int) -> None:
        """Give back an id that was never inserted."""
        self._snap_free.append(snapshot)

    def snapshots_free(self) -> int:
        return len(self._snap_free)

    # -- eviction ----------------------------------------------------------

    def _leaves(self) -> List[_TrieNode]:
        out: List[_TrieNode] = []
        stack = [self._root]
        while stack:
            n = stack.pop()
            kids = list(n.children.values())
            if not kids and n is not self._root:
                out.append(n)
            stack.extend(kids)
        return out

    def evict(self, n_blocks: int) -> int:
        """Reclaim up to ``n_blocks`` physical blocks by dropping LRU
        leaves whose ONLY reference is the trie's (a leaf a live request
        still shares is pinned). Dropping a leaf may expose its parent;
        the scan repeats until satisfied or nothing is reclaimable."""
        reclaimed = 0
        while reclaimed < n_blocks:
            victims = [l for l in self._leaves()
                       if self.pool.refcount(l.block_id) == 1]
            if not victims:
                break
            victims.sort(key=lambda l: l.last_used)
            for leaf in victims:
                leaf.parent.children.pop(leaf.key, None)
                self._nodes -= 1
                if leaf.snapshot is not None:
                    self._snap_free.append(self._drop_snapshot(leaf))
                    self.snapshot_evictions += 1
                if self.pool.release(leaf.block_id):
                    reclaimed += 1
                    self.evictions += 1
                if reclaimed >= n_blocks:
                    break
        return reclaimed

    def clear(self) -> int:
        """Drop every node (engine shutdown); returns blocks freed."""
        freed = 0
        stack = list(self._root.children.values())
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            if self.pool.release(n.block_id):
                freed += 1
        self._root.children.clear()
        self._nodes = 0
        self._snap_free += list(self._snap_nodes)
        self._snap_nodes.clear()
        return freed

    def evictable_count(self) -> int:
        """Blocks reclaimable by :meth:`evict` RIGHT NOW: nodes whose
        whole subtree is only trie-referenced (eviction is leaf-first,
        so a node above a request-pinned block is stuck until the sharer
        releases). This is the capacity signal routing/autoscaling must
        add to the free count — a warm idle replica's pool reads ~full
        otherwise, which would steer traffic to cold replicas and drive
        autoscale runaway."""

        # iterative post-order (chains are one node per prompt block —
        # recursion would blow the stack on long-context configs):
        # a node is counted when its whole subtree is trie-only
        count = 0
        stack = [(n, False) for n in self._root.children.values()]
        free: Dict[int, bool] = {}          # id(node) -> subtree free?
        while stack:
            n, visited = stack.pop()
            if not visited:
                stack.append((n, True))
                stack.extend((c, False) for c in n.children.values())
                continue
            ok = (self.pool.refcount(n.block_id) == 1
                  and all(free[id(c)] for c in n.children.values()))
            free[id(n)] = ok
            if ok:
                count += 1
        return count

    def digest(self, top: int = 8) -> List[Tuple[str, int]]:
        """Top trie roots by hit-weight as ``(key_digest, weight)`` pairs
        — the cluster-wide prefix-affinity signal. One entry per resident
        ROOT child (≈ one per distinct system prompt). Roots that never
        produced a hit publish weight 0: a HELD root is routable — the
        tenant's first repeat request would hit it, so omitting cold
        entries scatters every session's opening requests across the
        fleet before affinity can converge. Hot roots sort first so the
        ``top`` cap sheds cold ones under pressure. Small and stable by
        construction: ``top`` entries of ~24 bytes ride every load
        report."""
        roots = sorted(self._root.children.values(),
                       key=lambda n: -n.hit_weight)
        return [(prefix_key_digest(n.key), n.hit_weight)
                for n in roots[:max(top, 0)]]

    def stats(self) -> Dict[str, int]:
        out = {"nodes": self._nodes, "hits": self.hits,
               "misses": self.misses, "hit_tokens": self.hit_tokens,
               "evictions": self.evictions}
        if self.snapshots:
            out.update(snapshots=self.snapshots,
                       snapshots_held=len(self._snap_nodes),
                       snapshots_free=len(self._snap_free),
                       snapshot_evictions=self.snapshot_evictions,
                       snapshots_superseded=self.snapshots_superseded)
        return out
