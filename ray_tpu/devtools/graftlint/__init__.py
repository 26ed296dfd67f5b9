"""graftlint — AST-based architecture linter for ray_tpu.

Rule families (see the generated catalog in README "Static analysis"):

- ``locks``      lock discipline / race detection (static twin of the
                 runtime contention profiler)
- ``jax``        JAX/TPU call discipline (VJP-safe attention, timing
                 barriers, JAX_PLATFORMS hygiene, worker-boot cost)
- ``layering``   the ML-libraries-over-public-API portability seam
- ``invariants`` AST ports of the old test_invariants.py regex greps
- ``failpoints`` chaos-plane site catalog consistency
- ``meta``       suppression hygiene
- ``protocol``   whole-program wire-protocol sync: every pipe cast/req,
                 GCS/peer rpc_* method, and pubsub topic matches the
                 checked-in catalog in ``core/protocol.py`` AND has both
                 a live sender and a dispatch arm
- ``lifecycle``  session-scoped resource lifecycles: shm/DeviceChannel
                 names carry the session id (sweep-reachable), BlockPool
                 claims roll back on every error path, manual spans are
                 finished or handed off
- ``lockgraph``  global lock-order graph: held->acquired edges merged
                 across ALL modules, cycles reported with witness paths

Public entry points::

    from ray_tpu.devtools import graftlint
    findings = graftlint.lint([Path("ray_tpu")])          # all rules
    findings = graftlint.lint(paths, families=["locks"])  # one family

CLI: ``python -m ray_tpu.devtools.graftlint`` (see --help / Makefile's
``make lint``). Stdlib-only by design — no jax import.
"""

from __future__ import annotations

from pathlib import Path
from typing import Iterable, List, Optional

from ray_tpu.devtools.graftlint.engine import (  # noqa: F401
    ModuleIndex,
    Project,
    build_project,
    load_module,
    run_rules,
)
from ray_tpu.devtools.graftlint.model import (  # noqa: F401
    FAMILIES,
    Finding,
    Rule,
    all_rules,
    rule_names,
    select_rules,
)


def lint(paths: List[Path], rules: Iterable[str] = (),
         families: Iterable[str] = (),
         root: Optional[Path] = None,
         cache: bool = True) -> List[Finding]:
    """Analyze ``paths`` and return sorted findings (parse errors
    included as findings). The one-call API tests build on.

    ``cache=False`` bypasses the ``.graftlint_cache/`` model cache
    (which is only consulted when ``root`` is given anyway)."""
    project, errors = build_project([Path(p) for p in paths], root=root,
                                    cache=cache)
    findings = run_rules(project, select_rules(rules, families))
    return sorted(errors + findings, key=lambda f: f.sort_key())
