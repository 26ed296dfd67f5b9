"""graftlint CLI.

Usage::

    python -m ray_tpu.devtools.graftlint [paths...]      # default: ray_tpu/
        [--rule RULE]... [--family FAM]... [--list-rules]
        [--markdown | --check README.md | --update README.md]
        [--baseline PATH] [--update-baseline]

Exit status: 0 clean, 1 findings (printed as ``path:line RULE message``),
2 usage/config error.

We never import jax ourselves (the linter is pure ``ast``).
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

from ray_tpu.devtools import graftlint
from ray_tpu.devtools.graftlint import catalog


def _default_root() -> Path:
    """The repo root (parent of the ray_tpu package this module runs
    from) — makes ``make lint`` work from any cwd."""
    return Path(__file__).resolve().parents[3]


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(
        prog="python -m ray_tpu.devtools.graftlint",
        description="AST-based architecture linter "
                    "(lock discipline, JAX/TPU discipline, layering seam)")
    p.add_argument("paths", nargs="*",
                   help="files/dirs to lint (default: the ray_tpu/ package)")
    p.add_argument("--rule", action="append", default=[],
                   help="run only this rule (repeatable)")
    p.add_argument("--family", action="append", default=[],
                   help=f"run only this family (repeatable; "
                        f"one of {', '.join(graftlint.FAMILIES)})")
    p.add_argument("--list-rules", action="store_true",
                   help="print the rule catalog and exit")
    p.add_argument("--markdown", action="store_true",
                   help="print the generated README rule table")
    p.add_argument("--check", metavar="README",
                   help="verify README's rule table matches the registry")
    p.add_argument("--update", metavar="README",
                   help="rewrite README's rule table in place")
    p.add_argument("--no-cache", action="store_true",
                   help="bypass the .graftlint_cache/ per-module model "
                        "cache (escape hatch; results must be identical "
                        "— tested by test_graftlint.py cache parity)")
    p.add_argument("--baseline", metavar="PATH",
                   help="baseline file of known findings to ignore "
                        "(default: <root>/.graftlint-baseline.json if "
                        "present; the tree intentionally ships none — "
                        "prefer inline '# graftlint: disable=... -- reason')")
    p.add_argument("--update-baseline", action="store_true",
                   help="write current findings to the baseline file")
    args = p.parse_args(argv)

    if args.list_rules:
        for r in graftlint.all_rules():
            print(f"{r.name:26s} [{r.family}] {r.summary}")
        return 0
    if args.markdown:
        print(catalog.markdown_table())
        return 0
    if args.check or args.update:
        return catalog.check_or_update(args.check or args.update,
                                       update=bool(args.update))

    root = _default_root()
    paths = [Path(p) for p in args.paths] or [root / "ray_tpu"]
    for path in paths:
        if not path.exists():
            print(f"error: no such path: {path}", file=sys.stderr)
            return 2
    try:
        findings = graftlint.lint(paths, rules=args.rule,
                                  families=args.family, root=root,
                                  cache=not args.no_cache)
    except KeyError as e:
        print(f"error: {e.args[0]}", file=sys.stderr)
        return 2

    baseline_path = Path(args.baseline) if args.baseline else (
        root / ".graftlint-baseline.json")
    if args.update_baseline:
        baseline_path.write_text(json.dumps(
            [f.render() for f in findings], indent=1) + "\n")
        print(f"wrote {len(findings)} finding(s) to {baseline_path}")
        return 0
    if baseline_path.exists():
        known = set(json.loads(baseline_path.read_text()))
        kept = [f for f in findings if f.render() not in known]
        hidden = len(findings) - len(kept)
        if hidden:
            # a baseline must never be SILENT — say what it swallowed
            print(f"note: {hidden} finding(s) hidden by {baseline_path} "
                  f"(prefer inline '# graftlint: disable=... -- reason')",
                  file=sys.stderr)
        findings = kept

    for f in findings:
        print(f.render())
    if findings:
        print(f"\n{len(findings)} finding(s). Fix, or annotate a "
              f"judged-intentional site with "
              f"'# graftlint: disable=<rule> -- <reason>'.",
              file=sys.stderr)
    return 1 if findings else 0


if __name__ == "__main__":
    raise SystemExit(main())
