"""Architecture invariants, enforced by graftlint (ISSUE 6).

This file used to be a pile of regex greps; it is now a thin runner over
``ray_tpu.devtools.graftlint`` — one test per rule family, each failing
with ``path:line RULE message`` findings. The AST rules are alias-aware
and multi-line-safe where the greps were not, and every rule carries
positive/negative fixtures under ``tests/graftlint_fixtures/``
(self-checked in test_graftlint.py).

What the families guard (CLAUDE.md "Architecture invariants"):

- ``locks``       unguarded shared-state writes, lock-order inversions,
                  and blocking calls held under driver/GCS locks — the
                  static twin of util/contention.py's runtime profiler.
- ``jax``         memory-safe attention VJPs, honest TPU timing
                  barriers, JAX_PLATFORMS hygiene, and the 1.9 s/worker
                  module-scope-jax-import tax.
- ``layering``    data/train/tune/serve/rllib build ONLY on the public
                  task/actor/object API (the portability seam).
- ``invariants``  one-receiver-thread pipes, cloudpickle-first
                  serialization, metric_defs-only metrics,
                  deadline-capable cluster waits.
- ``failpoints``  the chaos-plane site catalog stays unique, literal,
                  and documented.
- ``meta``        every inline suppression names a real rule and
                  carries a reason (no silent baselines).
- ``protocol``    the wire vocabularies (worker pipe casts/reqs/frame
                  kinds, GCS + peer RPC methods, pubsub topics) agree
                  three ways: senders, dispatch arms, and the
                  checked-in core/protocol.py catalog (ISSUE 15).
- ``lifecycle``   session-scoped resources are reclaimable: shm rings
                  session-named for the shutdown sweep, BlockPool
                  claims rolled back on failure exits, manual spans
                  finished or handed off.
- ``lockgraph``   the merged whole-program held->acquired lock graph
                  is acyclic (3+-cycles and cross-module cycles the
                  per-class inversion rule cannot see).
"""

from pathlib import Path

import pytest

from ray_tpu.devtools import graftlint

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def tree_findings():
    """One full-tree lint shared by every family test AND by
    test_graftlint.py (the analysis pass dominates the cost; rules are
    cheap) — see tests/_graftlint_tree.py."""
    from _graftlint_tree import tree_findings as shared

    findings = shared()
    by_family = {fam: [] for fam in graftlint.FAMILIES}
    rule_family = {r.name: r.family for r in graftlint.all_rules()}
    for f in findings:
        by_family.setdefault(rule_family.get(f.rule, "meta"), []).append(f)
    return by_family


def _assert_clean(by_family, family, hint):
    findings = by_family[family]
    rendered = "\n  ".join(f.render() for f in findings)
    assert not findings, (
        f"graftlint family '{family}' found violations:\n  {rendered}\n"
        f"{hint}")


def test_lock_discipline(tree_findings):
    """Unguarded writes to lock-managed attributes, inverted lock
    orders, and blocking calls (sleep/recv/rpc-call/wait) under a lock.
    r8 proved the driver control plane is ~1-2 ms of GIL-serialized CPU
    per task under ONE lock — blocking it blocks everyone."""
    _assert_clean(
        tree_findings, "locks",
        "take the lock (or use the _locked-suffix caller-holds-lock "
        "convention); judged-intentional lock-free sites need "
        "'# graftlint: disable=... -- reason'")


def test_jax_tpu_discipline(tree_findings):
    """Raw attention kernels outside ops/ (no memory-efficient VJP —
    ~50 GB of residuals at llama-250M scale), block_until_ready as a
    timing barrier (the tree's one idiom is a dependent device_get),
    JAX_PLATFORMS leaking into worker envs (chip fights), and
    module-scope jax imports in zygote-imported core/cluster modules
    (a jax import per worker boot)."""
    _assert_clean(
        tree_findings, "jax",
        "route attention through ray_tpu.ops.flash_attention; time with "
        "a data-dependent device_get; set explicit per-worker platforms")


def test_layering_seam(tree_findings):
    """ML libraries import only the public task/actor/object API, util/,
    and each other — the seam that keeps them portable (CLAUDE.md)."""
    _assert_clean(
        tree_findings, "layering",
        "use the ray_tpu top-level API or add a public accessor to "
        "ray_tpu.util (e.g. util.state.actor_queue_depths)")


def test_ported_invariants(tree_findings):
    """AST ports of the old regex greps: single pipe receiver thread,
    cloudpickle-first serialize, metric_defs-only metric creation in
    core/cluster, deadline-capable cluster-plane waits."""
    _assert_clean(
        tree_findings, "invariants",
        "see the rule messages — each names the CLAUDE.md invariant and "
        "the compliant pattern")


def test_failpoint_site_catalog(tree_findings):
    """Every failpoints.hit() site: unique literal name, documented in
    util/failpoints.py's Sites list; no stale documented sites."""
    _assert_clean(
        tree_findings, "failpoints",
        "add new sites to the Sites block of util/failpoints.py; "
        "suffix names when instrumenting a second call site")


def test_suppression_hygiene(tree_findings):
    """Inline disables must name real rules and carry reasons — the
    no-silent-baseline rule that keeps the other families honest."""
    _assert_clean(
        tree_findings, "meta",
        "write '# graftlint: disable=<rule> -- <why this is safe>'")


def test_wire_protocol_sync(tree_findings):
    """Whole-program protocol drift (ISSUE 15): every pipe cast/req/
    frame kind, GCS/peer RPC literal, and pubsub topic has a sender, a
    dispatch arm, and a core/protocol.py catalog entry. A send without
    a handler is a silently dropped message; a handler without a sender
    is dead protocol (the r14 native migration left two)."""
    _assert_clean(
        tree_findings, "protocol",
        "update ray_tpu/core/protocol.py in the same change as the "
        "sender/handler — the catalog is the wire-protocol review "
        "surface")


def test_resource_lifecycle(tree_findings):
    """Acquire/release symmetry for session-scoped resources: shm
    rings created with session-derived names (the rtpu-chan-<session>-*
    sweep must be able to reclaim them), pool.alloc claims released on
    every failure exit, manual spans finished or handed off."""
    _assert_clean(
        tree_findings, "lifecycle",
        "pair every acquire with a release on each exit path; see the "
        "rule messages for the compliant in-tree pattern")


def test_global_lock_order(tree_findings):
    """The merged held->acquired lock graph over all modules is
    acyclic — catches 3+-cycles inside one class and cross-module
    cycles through shared module-level locks, which the per-class
    inversion rule structurally cannot see."""
    _assert_clean(
        tree_findings, "lockgraph",
        "pick one global acquisition order (each edge in the reported "
        "cycle carries its witness file:line)")
