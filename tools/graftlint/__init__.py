"""graftlint: AST-based invariant checkers for this repo.

The review findings that recur across PRs — blocking calls under held
locks (the PR-14 ABBA/brownout class), tracer spans leaked on exception
paths (PR 4), non-idempotent RPCs silently retried, the hand-synced
`dlrover_*` metric table in docs/observability.md, fault-point sites
nobody exercises, and rename-without-fsync "durable" commits (PR 11) —
are mechanized here as repo-specific static checks. Pure `ast`, no
third-party deps, sub-second over the whole tree, so the suite runs as
a tier-1 test and a pre-PR CLI (`python -m tools.graftlint`).

Deliberate violations are suppressed in place, and a suppression
REQUIRES a reason::

    os.replace(tmp, path)  # graftlint: disable=durable-rename reason=telemetry file; atomicity not durability

See docs/static-analysis.md for the checker catalog.
"""

from tools.graftlint.core import (  # noqa: F401
    Context,
    Finding,
    run_checkers,
)
from tools.graftlint.checkers import ALL_CHECKERS  # noqa: F401
