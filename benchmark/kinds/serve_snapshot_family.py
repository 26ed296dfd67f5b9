"""A serve cell of a model family whose prefix hits RESTORE A SNAPSHOT of
recurrent state (``ray_tpu.models.layouts.Layout.snapshots``), driver side.

The flow, the clocks and the comparison with the plain reference are
``kinds/serve_state_family.py``'s, imported whole: the family's own
``SCOPES``, ``KERNELS`` and ``STEP_COUNTERS``, the window, the give-up rule,
the logits check, self-agreement and the verdicts. The replica is
``SnapshotFamilyLLM`` (``serve_snapshot_family_replica.py``: its logits
check serves the sample cold and then through its snapshots); the imported
flow names its replica class by a module global, which ``run`` points at
that class for the length of the call.
A request here lives a twentieth of the window, so the cell's ``pre_roll`` is
0 seconds and the window opens on an idle engine that holds what the
traffic's set-up left (``warm_prompts``: every conversation's history served
once, a snapshot at its last block boundary), as ``kinds/serve_family.py``'s
cells do. (``serve_family.py`` itself binds its replica's scopes to one
family's list; ``serve_state_family.py`` leaves two of this cell's limits
out. Hence a file that imports from both.)

What this kind adds to ``correct``, under the cell's ``snapshot_limits``:

- ``self_agreement_missed_prefix``: of the one prompt ``self_agreement``
  serves twice (``kinds/serve.py``), the second serve took its prefix from
  the trie AND copied a snapshot into its slot (the engine's
  ``prefix_hit_tokens`` and ``state_snapshots_restored`` both grew between
  the marks ``agree_1`` and ``agree_2``); that it then gave the cold serve's
  tokens is ``self_disagreement``, which the imported flow holds to 0. So a
  warm request served THROUGH a snapshot hit is compared with the same
  prompt served cold in every run.
- ``state_snapshots_leaked``: with the engine drained, the snapshot pool's
  entries are all either owned by a trie node or free.
- ``snapshot_logit_drift``: the check's own sample served cold and then
  through its snapshots gives the same logits, to the bit on sound code
  (``serve_snapshot_family_replica.py``); ``snapshot_check_missed`` is 1
  where none of the sample restored a snapshot, so that the comparison
  cannot pass for want of a hit.
- ``state_rel_err_first_layer``: the recurrent state the sample's slots hold
  after that warm serve against the plain reference's after the same tokens
  (the family's ``slot_state``, the reference's ``state_at``), in the first
  stateful layer's worst head: what a state pool held below the precision
  the configuration states moves, and the logits do not show
  (``serve_snapshot_family_replica.py::state_summary``).

This process never imports jax.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import check
from benchmark.kinds import serve_state_family
from benchmark.kinds.serve import log
from benchmark.kinds.serve_snapshot_family_replica import SnapshotFamilyLLM


def snapshot_numbers(run: Dict[str, Any]) -> Dict[str, int]:
    marks = run["replica"]["marks"]
    grew = lambda k: (marks["agree_2"]["stats"].get(k, 0)
                      - marks["agree_1"]["stats"].get(k, 0))
    prefix = run["replica"]["kv_state"]["prefix"]
    twice = run["replica"]["snapshot_check"]
    return {
        "snapshot_logit_drift": twice["snapshot_logit_drift"],
        "state_rel_err_first_layer": twice["state_rel_err_first_layer"],
        "snapshot_check_missed": 0 if twice["restored"] > 0 else 1,
        "self_agreement_missed_prefix": 0 if (
            grew("prefix_hit_tokens") > 0
            and grew("state_snapshots_restored") > 0) else 1,
        "state_snapshots_leaked": prefix["snapshots"]
        - prefix["snapshots_held"] - prefix["snapshots_free"],
    }


def run(ctx) -> Dict[str, Any]:
    theirs = serve_state_family.StateFamilyLLM
    serve_state_family.StateFamilyLLM = SnapshotFamilyLLM
    try:
        out = serve_state_family.run(ctx)
    finally:
        serve_state_family.StateFamilyLLM = theirs
    verdicts = check.verdict(snapshot_numbers(out["run"]),
                             out["run"]["cell"]["snapshot_limits"])
    for v in verdicts:
        log(f"check {v['name']}: {v['value']} (limit {v['limit']}) "
            f"{'ok' if v['ok'] else 'NOT OK'}")
    stats = {k: out["run"]["marks"]["end"]["stats"].get(k, 0)
             - out["run"]["marks"]["start"]["stats"].get(k, 0)
             for k in ("requests_admitted", "state_snapshots_taken",
                       "state_snapshots_restored", "state_snapshots_evicted",
                       "prefix_hit_tokens")}
    log(f"the check's sample cold and then through its snapshots: "
        f"{out['run']['replica']['snapshot_check']}")
    log(f"snapshots over the window: {stats}; the trie at the end "
        f"{out['run']['replica']['kv_state']['prefix']}")
    out["correct"] = bool(out["correct"] and all(v["ok"] for v in verdicts))
    return out
