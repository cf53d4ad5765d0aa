"""span-leak: manual tracer handles and episode pairs must close on
every exception path.

Mechanizes the PR-4 hardening class: a ``SpanTracer`` handle taken
manually (``sp = span("step")``) that an exception path never
``end()``s/``cancel()``s stays on the thread's open-span stack forever
— hang attribution then blames a phase that finished hours ago, and
the goodput ledger keeps attributing wall time to it. The same failure
shape applies to the ledger's episode channels (the "span()-adjacent
mutations"): ``eviction_begin()`` without a guaranteed
``eviction_end()`` books every subsequent second to ``eviction``.

Rules (per function):

- an assigned handle ``name = <...>span(...)`` must have at least one
  ``name.end()`` / ``name.cancel()`` call, and at least one of those
  calls must sit on an exception-safe path: inside a ``finally`` block
  or inside an ``except``/``except Exception``/``except BaseException``
  handler. Handles that escape the function (returned, stored on an
  attribute, passed to a call, yielded) are skipped — ownership moved.
- an episode ``X_begin()`` whose matching ``X_end()`` appears in the
  SAME function must likewise have the end on an exception-safe path.
  Begin/end in sibling branches of one ``if`` (the dispatch-helper
  shape, e.g. ``goodput.note_degraded``) and cross-function episodes
  are exempt — only a begin that can strand its own function's end is
  a leak.

``with span(...):`` needs no analysis — the context manager closes on
unwind by construction.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Tuple

from tools.graftlint.core import (
    Context,
    Finding,
    call_name,
    own_nodes,
    last_segment,
    walk_functions,
)

EPISODE_PAIRS = {
    "eviction_begin": "eviction_end",
    "replay_begin": "replay_end",
    "degraded_enter": "degraded_exit",
    "serving_begin": "serving_end",
}

_CLOSERS = ("end", "cancel")


class SpanLeakChecker:
    id = "span-leak"
    scope = "file"

    def run(self, ctx: Context) -> List[Finding]:
        findings: List[Finding] = []
        for path in ctx.iter_files():
            try:
                tree = ctx.tree(path)
            except (OSError, SyntaxError):
                continue
            rel = ctx.rel(path)
            for fn in walk_functions(tree):
                findings.extend(self._check_handles(fn, rel))
                findings.extend(self._check_episodes(fn, rel))
        return findings

    # -- manual handles ------------------------------------------------
    def _check_handles(self, fn, rel: str) -> List[Finding]:
        handles: Dict[str, int] = {}  # var name -> assignment line
        for node in own_nodes(fn):
            if isinstance(node, ast.Assign) and isinstance(
                node.value, ast.Call
            ):
                if last_segment(call_name(node.value)) == "span":
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            handles[t.id] = node.lineno
        if not handles:
            return []

        findings: List[Finding] = []
        for name, line in handles.items():
            if _escapes(fn, name, line):
                continue
            closes = _close_sites(fn, name)
            if not closes:
                findings.append(
                    Finding(
                        checker="span-leak",
                        path=rel,
                        line=line,
                        message=(
                            f"manual span handle `{name}` is never "
                            "end()ed or cancel()ed"
                        ),
                        hint=(
                            "use `with span(...)` or close the handle "
                            "in a finally"
                        ),
                    )
                )
                continue
            if not any(_exception_safe(fn, c) for c in closes):
                findings.append(
                    Finding(
                        checker="span-leak",
                        path=rel,
                        line=line,
                        message=(
                            f"manual span handle `{name}` is not closed "
                            "on exception paths (no end()/cancel() in a "
                            "finally or except handler)"
                        ),
                        hint=(
                            "wrap the region in try/except BaseException:"
                            " cancel + raise, or try/finally: end"
                        ),
                    )
                )
        return findings

    # -- episode pairs -------------------------------------------------
    def _check_episodes(self, fn, rel: str) -> List[Finding]:
        begins: List[Tuple[str, ast.Call]] = []
        ends: Dict[str, List[ast.Call]] = {}
        for node in own_nodes(fn):
            if not isinstance(node, ast.Call):
                continue
            seg = last_segment(call_name(node))
            if seg in EPISODE_PAIRS:
                begins.append((seg, node))
            for b, e in EPISODE_PAIRS.items():
                if seg == e:
                    ends.setdefault(e, []).append(node)
        findings: List[Finding] = []
        for bname, bnode in begins:
            ename = EPISODE_PAIRS[bname]
            enodes = ends.get(ename, [])
            if not enodes:
                continue  # cross-function episode: out of scope
            if all(_sibling_branches(fn, bnode, e) for e in enodes):
                continue  # dispatch helper (if entered: begin else end)
            if not any(_exception_safe(fn, e) for e in enodes):
                findings.append(
                    Finding(
                        checker="span-leak",
                        path=rel,
                        line=bnode.lineno,
                        message=(
                            f"episode `{bname}()` is not closed on "
                            f"exception paths (`{ename}()` exists in "
                            "this function but not in a finally or "
                            "except handler)"
                        ),
                        hint=(
                            f"move `{ename}()` into a finally covering "
                            "the episode body"
                        ),
                    )
                )
        return findings



def _close_sites(fn, name: str) -> List[ast.Call]:
    out = []
    for node in own_nodes(fn):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in _CLOSERS
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id == name
        ):
            out.append(node)
    return out


def _escapes(fn, name: str, assign_line: int) -> bool:
    """True when the handle leaves this function's custody: returned,
    yielded, stored on an object, or passed as a call argument."""
    for node in own_nodes(fn):
        if isinstance(node, (ast.Return, ast.Yield, ast.YieldFrom)):
            if node.value is not None and _mentions(node.value, name):
                return True
        if isinstance(node, ast.Assign):
            if _mentions(node.value, name) and any(
                not isinstance(t, ast.Name) for t in node.targets
            ):
                return True
        if isinstance(node, ast.Call):
            for arg in list(node.args) + [k.value for k in node.keywords]:
                if _mentions(arg, name):
                    return True
    return False


def _mentions(node: ast.AST, name: str) -> bool:
    return any(
        isinstance(n, ast.Name) and n.id == name for n in ast.walk(node)
    )


def _exception_safe(fn, target: ast.AST) -> bool:
    """True when ``target`` sits inside a ``finally`` block or a
    broad-enough ``except`` handler (bare, ``Exception`` or
    ``BaseException``) within ``fn``. A close only inside a NARROW
    handler (``except StopIteration``) does not cover other exception
    paths — the PR-4 leak survives those."""
    path = _path_to(fn, target)
    if path is None:
        return False
    for i, node in enumerate(path):
        if isinstance(node, ast.Try):
            nxt = path[i + 1] if i + 1 < len(path) else None
            if nxt is not None and any(
                nxt is n or _contains(n, nxt) for n in node.finalbody
            ):
                return True
        if isinstance(node, ast.ExceptHandler) and _broad_handler(node):
            return True
    return False


def _broad_handler(handler: ast.ExceptHandler) -> bool:
    if handler.type is None:
        return True
    types = (
        handler.type.elts
        if isinstance(handler.type, ast.Tuple)
        else [handler.type]
    )
    for t in types:
        name = t.id if isinstance(t, ast.Name) else getattr(t, "attr", "")
        if name in ("Exception", "BaseException"):
            return True
    return False


def _sibling_branches(fn, a: ast.AST, b: ast.AST) -> bool:
    """True when ``a`` and ``b`` live in opposite branches of the same
    ``if`` — mutually exclusive paths, not a begin-then-end pair."""
    for node in ast.walk(fn):
        if not isinstance(node, ast.If):
            continue
        a_body = any(_contains(n, a) or n is a for n in node.body)
        a_else = any(_contains(n, a) or n is a for n in node.orelse)
        b_body = any(_contains(n, b) or n is b for n in node.body)
        b_else = any(_contains(n, b) or n is b for n in node.orelse)
        if (a_body and b_else) or (a_else and b_body):
            return True
    return False


def _path_to(root: ast.AST, target: ast.AST) -> Optional[list]:
    """Ancestor chain from ``root`` down to ``target`` (inclusive)."""
    path: list = []

    def rec(node) -> bool:
        path.append(node)
        if node is target:
            return True
        for child in ast.iter_child_nodes(node):
            if rec(child):
                return True
        path.pop()
        return False

    return path if rec(root) else None


def _contains(node: ast.AST, target: ast.AST) -> bool:
    return any(n is target for n in ast.walk(node))
