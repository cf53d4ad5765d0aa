#!/usr/bin/env python
"""graftlint CLI — the documented pre-PR check (ROADMAP.md), run it
beside ``tools/tier1_budget.py``::

    python -m tools.graftlint                 # whole tree, text output
    python -m tools.graftlint --json          # machine-readable
    python -m tools.graftlint --changed-only  # pre-commit: git-diff filter
    python -m tools.graftlint --select lock-discipline,span-leak
    python -m tools.graftlint dlrover_tpu/ckpt   # a subtree

Exit codes: 0 = no unsuppressed findings; 1 = findings; 2 = usage.

``--changed-only`` restricts the per-file checkers (lock-discipline
sites, span-leak, durable-rename) to files changed vs HEAD plus
untracked files; the cross-file checkers (rpc-idempotency,
metric-doc-drift, fault-site) always see the whole tree — a one-file
diff can still break a two-sided invariant, and they are the cheap
ones anyway.
"""

from __future__ import annotations

import argparse
import os
import sys

from tools.graftlint.checkers import ALL_CHECKERS
from tools.graftlint.core import (
    Context,
    changed_files,
    discover_files,
    render_json,
    render_text,
    run_checkers,
    unsuppressed,
)

DEFAULT_TARGETS = ("dlrover_tpu", "tools")


def find_root(start: str) -> str:
    """The repo root: nearest ancestor holding ``dlrover_tpu/``."""
    cur = os.path.abspath(start)
    while True:
        if os.path.isdir(os.path.join(cur, "dlrover_tpu")):
            return cur
        parent = os.path.dirname(cur)
        if parent == cur:
            return os.path.abspath(start)
        cur = parent


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="graftlint", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument(
        "paths", nargs="*",
        help=f"files/dirs to lint (default: {' '.join(DEFAULT_TARGETS)})",
    )
    parser.add_argument("--json", action="store_true", dest="as_json")
    parser.add_argument(
        "--changed-only", action="store_true",
        help="per-file checkers run only over git-changed files",
    )
    parser.add_argument(
        "--select", default="",
        help="comma-separated checker ids to run (default: all)",
    )
    parser.add_argument(
        "--list-checkers", action="store_true",
        help="print checker ids and exit",
    )
    parser.add_argument(
        "-v", "--verbose", action="store_true",
        help="also print suppressed findings (with their reasons)",
    )
    parser.add_argument(
        "--root", default="",
        help="repo root (default: discovered from cwd)",
    )
    args = parser.parse_args(argv)

    if args.list_checkers:
        for c in ALL_CHECKERS:
            print(f"{c.id}  [{c.scope}]")
        return 0

    root = os.path.abspath(args.root) if args.root else find_root(os.getcwd())
    # path operands restrict EMISSION the way --changed-only does: the
    # Context always spans the default targets so the repo-scope
    # checkers (dispatch matrix, metric drift, fault sites) keep their
    # whole-tree view — a subtree lint must not compare docs/comm.py
    # against an almost-empty code set. A path that matches nothing is
    # a usage error, not a vacuous clean pass (the silent-fallback
    # class this tool exists to catch).
    sub_files = None
    if args.paths:
        missing = [
            p for p in args.paths
            if not os.path.exists(p)
            and not os.path.exists(os.path.join(root, p))
        ]
        if missing:
            print(
                f"graftlint: no such path(s): {', '.join(missing)}",
                file=sys.stderr,
            )
            return 2
        sub_files = discover_files(root, args.paths)
        if not sub_files:
            print(
                "graftlint: path(s) matched no lintable .py files",
                file=sys.stderr,
            )
            return 2
    targets = [
        t for t in DEFAULT_TARGETS
        if os.path.exists(os.path.join(root, t))
    ]
    if not targets:
        print("graftlint: nothing to lint", file=sys.stderr)
        return 2
    files = discover_files(root, targets)
    changed = changed_files(root) if args.changed_only else None
    if sub_files is not None:
        sub = set(sub_files)
        changed = (
            sorted(sub.intersection(changed))
            if changed is not None
            else sub_files
        )
        # operands outside the default targets still lint: per-file
        # checkers visit Context files, so fold them in
        files = sorted(set(files) | sub)
    ctx = Context(root, files, changed=changed)

    select = None
    if args.select:
        select = {s.strip() for s in args.select.split(",") if s.strip()}
        known = {c.id for c in ALL_CHECKERS}
        unknown = select - known
        if unknown:
            print(
                f"graftlint: unknown checker(s) {sorted(unknown)} "
                f"(known: {sorted(known)})",
                file=sys.stderr,
            )
            return 2

    findings = run_checkers(ctx, ALL_CHECKERS, select=select)
    if args.as_json:
        print(render_json(findings))
    else:
        print(render_text(findings, verbose=args.verbose))
    return 1 if unsuppressed(findings) else 0


if __name__ == "__main__":
    sys.exit(main())
