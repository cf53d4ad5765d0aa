#!/usr/bin/env python3
"""What the program itself wrote into a profiler trace (``*.xplane.pb``),
read over the traced stretch of ``xplane.py``: the device's time by the
step program's ``jax.named_scope("scope/<name>")`` scopes, and the
device's idle gaps by the host spans the ``SpanTracer`` mirrors onto the
profiler's clock (``accel/profiler.install_profiler_mirror``).

    python benchmark/scopes.py <trace.xplane.pb> <out.json>

prints the table as one JSON line and writes it to ``<out.json>``; rc 3
and ``{"refused": ...}`` where the trace holds no stretch of whole steps.
It imports neither JAX nor the program, so it runs beside a live job on
any ``.xplane.pb`` (a ``ProfilerCapture`` bundle's, a benchmark's).

**Why the proto.** A scope's path reaches a device operation as the
``tf_op`` stat of its event's *metadata* (xprof's "TF Op" column:
``jit(train_step)/transpose(jvp(scope/lm_head))/dot_general``; a fusion
carries its root's path). ``jax.profiler.ProfileData``, which ``xplane.py``
reads through, hands out an event's own stats and not its metadata's, so
this file parses the ``XSpace`` proto. The generated module
(``tensorflow/tsl/profiler/protobuf/xplane_pb2.py``) needs
``google.protobuf`` alone and is loaded by its path: importing it through
the ``tensorflow`` package takes half a minute, by path 0.1 s.

**One stretch, one arithmetic.** The first device plane's ``XLA Modules``
line gives the traced stretch (``xplane.step_stretch``), its ``XLA Ops``
events are clipped to it (``xplane.clip``) and each counts its own time,
its duration less what its nested children cover (``xplane.self_times``):
imported, not copied, on times truncated to whole nanoseconds as
``ProfileData`` hands them out, so the sum of all own seconds is
``trace_reduced.json``'s ``busy_s`` of that device.

**Parts.** ``PARTS`` is the one table from scope name to part of the
model. An operation belongs to the innermost scope on its path that the
table gives a part (``layer/moe/*`` opens inside ``layer/mlp`` and is
``moe``; ``layer/out_norm`` stays with the layer around it). A scope the
table does not know counts with what encloses it and is listed by name
under ``unknown_scopes``; an operation under no known scope is
``unscoped``. A Pallas or ``ragged-dot`` custom call takes its part from
its path like any operation. **Phases:** ``recompute`` where the path
holds ``rematted_computation`` (what ``jax.checkpoint`` makes again in the
backward pass), else ``bwd`` where it holds ``transpose(``, else ``fwd``.

**The host line** is the line of the ``/host:CPU`` plane that holds the
``train`` events (the ``step`` span is mirrored as
``StepTraceAnnotation("train")`` and is called ``step`` here). Each idle
gap of the stretch goes to the deepest host span that encloses its start
(``(no span)`` where none does). The two clocks are trusted as one only
after ``clock_check``: each step execution begins after the ``dispatch``
span that launched it began and before the next one begins, no
``device_wait`` returns before the execution it waits for has ended, and
one of them returns right where its execution ends. Where they do not,
``host`` holds the check's numbers and ``refused``, and no seconds by
span.
"""

import functools
import importlib.util
import json
import os
import re
import subprocess
import sys
import time
from typing import Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import xplane  # noqa: E402

# scope name -> part, as {part: {scope: sub-scopes opened inside it}}.
# ``None`` is no part: the scope stays with what encloses it. Every name
# the program opens is here (tests/test_scope_coverage.py holds that).
_MIXER = ("in_proj", "conv", "scan", "gate", "out_proj")
PARTS = {
    "attn": {
        "layer/attn": ("window", "gate", "diff", "kv_down", "kv_up"),
    },
    "mlp": {"layer/mlp": ()},
    "moe": {
        "layer/moe": ("route", "route/groups", "dispatch", "experts",
                      "combine", "shared"),
    },
    "mixer": {
        "layer/ssm": _MIXER,
        "layer/gdn": _MIXER,
        "layer/sscan": _MIXER + ("x_proj",),
        "layer/gmu": ("in_proj", "gate", "out_proj"),
    },
    "head": {"embed": (), "final_norm": (), "lm_head": (), "xent": ()},
    "update": {"grad_sync": (), "grad_norm": (), "optimizer": ()},
    None: {"layer/out_norm": ()},
}
KNOWN: Dict[str, Optional[str]] = {
    name: part
    for part, roots in PARTS.items()
    for root, subs in roots.items()
    for name in (root, *(f"{root}/{s}" for s in subs))
}
UNSCOPED = "unscoped"
REMAT_MARK = "rematted_computation"
HOST_PLANE = "/host:CPU"
STEP_EVENT = "train"  # the ``step`` span's name on the host line
NO_SPAN = "(no span)"
# how far the tightest ``device_wait`` may return from the end of the step
# execution it waited for, for the two lines to count as one clock (1.0 to
# 1.9 ms in the nine cells' traces, PERF.md PR 55)
CLOCK_SLACK_NS = 3e6

# What the compiler names itself, so that no scope's path reaches it. XLA
# makes its own custom calls of ``lax.ragged_dot`` and gives them the path
# ``ragged-dot-none:``; a copy it makes of a leaf of the step's state (a
# layout change at the step's top) carries the argument's name for a path,
# ``state.params['layers'][0]['moe'].w_up:``, whose keys are the scopes'
# names (``['moe']`` is ``layer/moe``, ``['embed']`` is ``embed``).
BY_NAME = {"%ragged-dot": "layer/moe/experts"}
_KEY = re.compile(r"\['(\w+)'\]")

_SCOPE = re.compile(r"(?<![A-Za-z0-9_])scope/")
_NAME = re.compile(r"([A-Za-z0-9_/]*)(.?)")
_NUMBER = re.compile(r"[.\d]*\d")


def scopes_on(path: str) -> List[Tuple[str, bool]]:
    """The ``scope/<name>`` scopes on a ``tf_op`` path, outermost first,
    each as (name, whether the table knows it). What follows a scope's name
    on the path (a primitive, an einsum, ``jit(...)``) is cut off by the
    table's longest match; an unknown scope is named by its first component,
    or its first two under ``layer/``."""
    out = []
    for piece in _SCOPE.split(path)[1:]:
        run, then = _NAME.match(piece).groups()
        comps = [c for c in run.split("/") if c]
        # "scope/optimizer/jit(_where)" leaves a last component cut mid-name
        if then not in ("", ")") and not run.endswith("/"):
            comps = comps[:-1]
        for n in range(len(comps), 0, -1):
            if "/".join(comps[:n]) in KNOWN:
                out.append(("/".join(comps[:n]), True))
                break
        else:
            if comps:
                keep = 2 if comps[0] == "layer" else 1
                out.append(("/".join(comps[:keep]), False))
    return out


@functools.lru_cache(maxsize=None)
def classify(path: str, name: str = "") -> Tuple[str, str, str, Tuple, str]:
    """(part, scope, phase, unknown scopes, how) of one operation, by its
    ``tf_op`` path and, where no scope of the table is on it, by what the
    compiler put there in the path's place (``how``: ``path``, ``name``,
    ``leaf`` or, for ``unscoped``, empty)."""
    part, scope, unknown, how = UNSCOPED, "", [], ""
    for found, known in scopes_on(path):
        if not known:
            unknown.append(found)
        elif KNOWN[found] is not None:
            part, scope, how = KNOWN[found], found, "path"
    if not how:
        by_name = next(
            (sc for prefix, sc in BY_NAME.items() if name.startswith(prefix)),
            None,
        )
        leaf = next(
            (sc for key in _KEY.findall(path)
             for sc in (f"layer/{key}", key) if KNOWN.get(sc)), None,
        )
        if by_name:
            part, scope, how = KNOWN[by_name], by_name, "name"
        elif leaf:
            part, scope, how = KNOWN[leaf], leaf, "leaf"
    if REMAT_MARK in path:
        phase = "recompute"
    elif "transpose(" in path:
        phase = "bwd"
    else:
        phase = "fwd"
    return part, scope, phase, tuple(unknown), how


def nesting(events: List[xplane.Event]) -> Tuple[List[int], List[int]]:
    """For each event of a line the index of the event it is nested in
    (-1 at the top), and the indices in an order in which a parent comes
    before its children (``xplane.self_times`` walks the same nesting)."""
    order = sorted(
        range(len(events)), key=lambda i: (events[i][1], -events[i][2])
    )
    above = [-1] * len(events)
    stack: List[int] = []
    for i in order:
        start = events[i][1]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            above[i] = stack[-1]
        stack.append(i)
    return above, order


@functools.lru_cache(maxsize=None)
def load_proto():
    """The generated ``xplane_pb2`` module, by its path where it is found
    on ``sys.path`` (0.1 s), else through its package (half a minute)."""
    rel = os.path.join(
        "tensorflow", "tsl", "profiler", "protobuf", "xplane_pb2.py"
    )
    for base in sys.path:
        path = os.path.join(base, rel)
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                "_scopes_xplane_pb2", path
            )
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    return xplane_pb2


def read_space(path: str):
    space = load_proto().XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def _stat_text(plane, stat) -> str:
    kind = stat.WhichOneof("value")
    if kind == "str_value":
        return stat.str_value
    if kind == "ref_value":
        return plane.stat_metadata[stat.ref_value].name
    if kind == "bytes_value":
        return stat.bytes_value.decode(errors="replace")
    return ""


def tf_ops(plane) -> Dict[int, str]:
    """metadata id -> the ``tf_op`` stat of that event metadata."""
    wanted = {
        i for i, m in plane.stat_metadata.items() if m.name == "tf_op"
    }
    out = {}
    for i, meta in plane.event_metadata.items():
        for stat in meta.stats:
            if stat.metadata_id in wanted:
                out[i] = _stat_text(plane, stat)
                break
    return out


def line_events(plane, line, paths=None) -> List[xplane.Event]:
    """A line's events as ``xplane.py`` holds them: (name, start_ns,
    dur_ns, stats), the name cut before `` = `` (a TPU operation is named
    by its whole HLO text), times in whole nanoseconds as ``ProfileData``
    gives them. The fourth field is the event's ``tf_op`` path where
    ``paths`` (by metadata id) is given, else empty."""
    meta = plane.event_metadata
    paths = paths or {}
    names = {}
    out = []
    for e in line.events:
        name = names.get(e.metadata_id)
        if name is None:
            name = names[e.metadata_id] = (
                meta[e.metadata_id].name.partition(" = ")[0]
            )
        out.append((
            name,
            float(line.timestamp_ns + e.offset_ps // 1000),
            float(e.duration_ps // 1000),
            paths.get(e.metadata_id, ""),
        ))
    return out


def device_events(plane) -> Tuple[List[xplane.Event], List[xplane.Event]]:
    """A device plane's ``XLA Modules`` events and its ``XLA Ops`` events,
    these with their ``tf_op`` paths."""
    lines = {ln.name: ln for ln in plane.lines}
    modules = (
        line_events(plane, lines[xplane.MODULES_LINE])
        if xplane.MODULES_LINE in lines else []
    )
    return modules, line_events(plane, lines[xplane.OPS_LINE], tf_ops(plane))


def device_table(name: str, modules: List[xplane.Event],
                 ops: List[xplane.Event]) -> Dict:
    """One device plane's traced stretch by (part, scope, phase)."""
    try:
        stretch = xplane.step_stretch(modules)
    except xplane.NoStretch as e:
        raise xplane.NoStretch(f"{name}: {e}") from None
    begin, end = stretch.pop("begin_ns"), stretch.pop("end_ns")
    inside = xplane.clip(ops, begin, end)
    own = xplane.self_times(inside)
    _rows, idle_ns = xplane.gaps_table(ops, begin, end, keep=0)

    # An operation the compiler made without a path (a layout change, a
    # copy of a loop's state, the wait of an asynchronous copy) counts with
    # the operation it is nested in; at the top level, where the device
    # runs one operation after the other, with the part of the operations
    # before and after it if they are of one part. A parent comes before
    # its children in ``order``, which is the order of time.
    above, order = nesting(inside)
    found: List[Optional[Tuple]] = [None] * len(inside)
    before, waiting = None, []
    for i in order:
        name, _s, _d, path = inside[i]
        # the name matters only where the path holds no scope
        kind = classify(path, "" if "scope/" in path else name)
        if not kind[4] and above[i] >= 0 and found[above[i]][4]:
            kind = (*found[above[i]][:3], kind[3], "parent")
        found[i] = kind
        if not kind[4]:
            waiting.append(i)
            continue
        if before is not None and before[0] == kind[0]:
            scope = before[1] if before[1] == kind[1] else ""
            for j in waiting:
                found[j] = (kind[0], scope, before[2], found[j][3], "between")
        before, waiting = kind, []

    rows: Dict[Tuple[str, str, str], List[float]] = {}
    unknown: Dict[Tuple[str, str], List[float]] = {}
    outside: Dict[Tuple[str, str], List[float]] = {}
    by_how: Dict[str, float] = {}

    def add(table, key, own_ns):
        row = table.setdefault(key, [0.0, 0])
        row[0] += own_ns
        row[1] += 1

    for (name, _s, _d, path), own_ns, kind in zip(inside, own, found):
        part, scope, phase, unknown_here, how = kind
        add(rows, (part, scope, phase), own_ns)
        by_how[how or UNSCOPED] = by_how.get(how or UNSCOPED, 0.0) + own_ns / 1e9
        for u in unknown_here:
            add(unknown, (u, part), own_ns)
        if part == UNSCOPED:
            add(outside, (_NUMBER.sub("", name), _NUMBER.sub("N", path)),
                own_ns)

    def listed(table, keep=None):
        ordered = sorted(table.items(), key=lambda kv: -kv[1][0])[:keep]
        return [[*key, ns / 1e9, n] for key, (ns, n) in ordered]

    parts: Dict[str, float] = {}
    for (part, _scope, _phase), (ns, _n) in rows.items():
        parts[part] = parts.get(part, 0.0) + ns / 1e9
    return dict(
        stretch,
        plane=name,
        begin_ns=begin,
        end_ns=end,
        window_s=(end - begin) / 1e9,
        # the stretch less its idle gaps, as xplane.reduce_device has it,
        # and the sum of every operation's own time: one number on a line
        # whose events nest
        busy_s=(end - begin - idle_ns) / 1e9,
        own_s=sum(own) / 1e9,
        idle_s=idle_ns / 1e9,
        events=len(inside),
        paths=len({e[3] for e in inside}),
        parts=parts,
        # own seconds by how the part was found: on the path, by the
        # operation's name, by a state leaf's key, from the operation around
        # it, from the operations before and after it
        attributed_by=by_how,
        # [part, scope, phase, own seconds, events]
        rows=listed(rows),
        # [scope, the part around it, own seconds, events]
        unknown_scopes=listed(unknown),
        # [kind of operation, its tf_op path, own seconds, events], numbers
        # taken out of both: the largest
        unscoped_ops=listed(outside, 60),
    )


def idle_gaps(ops: List[xplane.Event], begin: float,
              end: float) -> List[Tuple[float, float]]:
    """The idle stretches of the device inside [begin, end) as (start_ns,
    dur_ns): the gaps ``xplane.gaps_table`` sums, kept apart."""
    gaps = []
    busy_until = begin
    for _name, start, dur, _stats in sorted(ops, key=lambda e: e[1]):
        if start + dur <= begin:
            continue
        if min(start, end) > busy_until:
            gaps.append((busy_until, min(start, end) - busy_until))
        busy_until = max(busy_until, start + dur)
        if busy_until >= end:
            break
    if end > busy_until:
        gaps.append((busy_until, end - busy_until))
    return gaps


def train_line(space):
    """(plane, line) of the host thread that holds the ``train`` step
    events, or (None, None)."""
    for plane in space.planes:
        if plane.name != HOST_PLANE:
            continue
        ids = {
            i for i, m in plane.event_metadata.items() if m.name == STEP_EVENT
        }
        for line in plane.lines:
            if any(e.metadata_id in ids for e in line.events):
                return plane, line
    return None, None


def clock_check(executions: List[Tuple[float, float]],
                dispatches: List[float],
                waits: List[Tuple[float, float]],
                slack_ns: float = CLOCK_SLACK_NS) -> Dict:
    """Are the host line and the device line on one clock? Given the step
    executions (start, end on the device line), the beginnings of the
    ``dispatch`` spans and the ``device_wait`` spans (begin, end on the
    host line) of a loop that keeps one step in flight:

    - order: the executions' starts and the dispatches' beginnings
      alternate, which pairs each execution with the ``dispatch`` that
      launched it. (An execution that starts before the file's first
      ``dispatch`` was launched before the capture; a ``dispatch`` after
      the last execution launched one the capture cut.)
    - cause before effect, both ways: no execution begins before its
      ``dispatch`` began, and no ``device_wait`` (the one that follows
      ``dispatch`` k waits for execution k - 1) returns before its
      execution ended. The two bound what the host's clock reads more
      than the device's: ``host_less_device_ns``.
    - an anchor: some ``device_wait`` returned within ``slack_ns`` of its
      execution's end, so the bound is that tight on one side.

    What it cannot see is a shift of a whole step's period that also lands
    within the slack."""
    runs, ds = sorted(executions), sorted(dispatches)
    out = {"ok": False, "executions": len(runs), "dispatches": len(ds),
           "device_waits": len(waits), "slack_ns": slack_ns}
    if not runs or not ds or not waits:
        return dict(out, why="no dispatch spans, device_wait spans or "
                             "step executions")
    first = next((i for i, x in enumerate(runs) if x[0] >= ds[0]), len(runs))
    xs = runs[first:]
    every = ds + [float("inf")]
    ds = [d for d in ds if xs and d <= xs[-1][0]]
    leads = [x[0] - d for x, d in zip(xs, ds)]
    gaps = [d - x[0] for x, d in zip(xs, ds[1:])]
    alternate = (
        len(xs) >= xplane.MIN_STEPS and len(xs) == len(ds)
        and min(gaps, default=1.0) > 0
    )
    # execution k - 1's end against the end of the wait that follows
    # dispatch k
    late = []
    for k, d in enumerate(ds):
        before = runs[first + k - 1] if first + k >= 1 else None
        until = every[k + 1]
        for begin, end in waits:
            if before is not None and d <= begin < until:
                late.append(end - before[1])
    out.update(alternate=alternate, pairs=min(len(xs), len(ds)),
               device_waits=len(late))
    if not leads or not late:
        return out
    for key, values in (
        ("execution_after_its_dispatch_ns", sorted(leads)),
        ("device_wait_end_after_its_execution_ns", sorted(late)),
    ):
        # least, median, largest
        out[key] = [values[0], values[len(values) // 2], values[-1]]
    out["host_less_device_ns"] = [-min(leads), min(late)]
    out["ok"] = bool(
        alternate and min(leads) >= -slack_ns
        and -slack_ns <= min(late) <= slack_ns
    )
    return out


def _stacks(spans: List[xplane.Event], times: List[float]) -> List[Tuple]:
    """For each time (ascending), the names of the spans open at it,
    outermost first. Spans of one thread nest."""
    order = sorted(spans, key=lambda s: (s[1], -s[2]))
    out, stack, i = [], [], 0
    for t in times:
        while i < len(order) and order[i][1] <= t:
            s = order[i]
            while stack and stack[-1][1] + stack[-1][2] <= s[1]:
                stack.pop()
            stack.append(s)
            i += 1
        while stack and stack[-1][1] + stack[-1][2] <= t:
            stack.pop()
        out.append(tuple(s[0] for s in stack))
    return out


def host_table(space, device: Dict, modules: List[xplane.Event],
               ops: List[xplane.Event]) -> Optional[Dict]:
    """The idle gaps of ``device``'s stretch (its table, its ``XLA
    Modules`` and ``XLA Ops`` events) by the train thread's spans; None
    where the file holds no such line."""
    plane, line = train_line(space)
    if line is None:
        return None
    spans = [
        ("step" if name == STEP_EVENT else name, start, dur, None)
        for name, start, dur, _ in line_events(plane, line)
    ]
    begin, end = device["begin_ns"], device["end_ns"]
    executions = [
        (start, start + dur) for name, start, dur, _ in modules
        if name in device["step_programs"]
    ]
    check = clock_check(
        executions,
        [s[1] for s in spans if s[0] == "dispatch"],
        [(s[1], s[1] + s[2]) for s in spans if s[0] == "device_wait"],
    )
    out = {
        "line": line.display_name or line.name,
        "events": len(spans),
        "clock_check": check,
    }
    if not check["ok"]:
        out["refused"] = (
            "the host line and the device line are not on one clock: the "
            "step executions and the dispatch spans do not alternate, or an "
            "effect lies before its cause, or no device_wait returns where "
            "its execution ends"
        )
        return out
    gaps = idle_gaps(ops, begin, end)
    by_span: Dict[str, List[float]] = {}
    under: Dict[str, float] = {}
    unnamed_ns = 0.0
    for (_start, dur), stack in zip(
        gaps, _stacks(spans, [g[0] for g in gaps])
    ):
        row = by_span.setdefault(
            stack[-1] if stack else NO_SPAN, [0.0, 0, 0.0]
        )
        row[0] += dur
        row[1] += 1
        row[2] = max(row[2], dur)
        for name in set(stack):
            under[name] = under.get(name, 0.0) + dur
        if not stack or stack[-1] == "step":
            unnamed_ns += dur
    steps = [s for s in spans if s[0] == "step" and begin <= s[1] < end]
    staged = sorted(s[1] for s in spans if s[0] == "ckpt_stage")
    chunk_steps = sum(
        1 for s in steps
        if any(s[1] <= t < s[1] + s[2] for t in staged)
    )
    out.update(
        idle_s=sum(g[1] for g in gaps) / 1e9,
        gaps=len(gaps),
        # [deepest span at the gap's start, idle seconds, gaps, longest]
        gaps_by_span=[
            [name, ns / 1e9, n, longest / 1e9]
            for name, (ns, n, longest) in sorted(
                by_span.items(), key=lambda kv: -kv[1][0])
        ],
        # idle seconds under each span and whatever it holds
        idle_under_s={
            k: v / 1e9 for k, v in sorted(under.items(), key=lambda kv: -kv[1])
        },
        unnamed_idle_s=unnamed_ns / 1e9,
        host_steps=len(steps),
        chunk_steps=chunk_steps,
        # the thread's spans inside the stretch, for the eye and the tests
        spans=[
            [n, s, d] for n, s, d, _ in spans if s < end and s + d > begin
        ],
    )
    return out


def reduce_file(path: str) -> Dict:
    """The first device plane on which anything ran, by scope, and its
    idle gaps by host span."""
    t0 = time.monotonic()
    space = read_space(path)
    t1 = time.monotonic()
    planes = [
        p for p in space.planes
        if p.name.startswith(xplane.DEVICE_PLANE_PREFIX)
        and any(ln.name == xplane.OPS_LINE and ln.events for ln in p.lines)
    ]
    if not planes:
        raise xplane.NoStretch("no device plane on which anything ran")
    modules, ops = device_events(planes[0])
    out = device_table(planes[0].name, modules, ops)
    out["host"] = host_table(space, out, modules, ops)
    out["took_s"] = {
        "parse": t1 - t0, "reduce": time.monotonic() - t1,
    }
    return out


# -- the per-layer metrics that read the table ------------------------------
# Each is (layer, unit, the cells it is read in: a rule on the cell's file and
# its configuration's ``model`` group, the value from the table). Seconds
# become ms a step as ``step.device_ms`` does: over the stretch's whole steps.


def _rows_s(out: Dict, keep) -> Optional[float]:
    rows = [r for r in out["rows"] if keep(*r[:3])]
    return sum(r[3] for r in rows) if rows else None


def _ms_a_step(out: Dict, keep) -> Optional[float]:
    seconds = _rows_s(out, keep)
    return None if seconds is None else 1e3 * seconds / out["steps"]


def _part(part):
    return lambda out: _ms_a_step(out, lambda p, _s, _ph: p == part)


def _under(scope):
    """A scope and whatever opens inside it."""
    return lambda _p, s, _ph: s == scope or s.startswith(scope + "/")


def _host(out: Dict) -> Optional[Dict]:
    host = out.get("host")
    return host if host and "refused" not in host else None


def _stage_idle_ms(out: Dict) -> Optional[float]:
    host = _host(out)
    if not host or not host["chunk_steps"]:
        return None
    return 1e3 * host["idle_under_s"].get("stage", 0.0) / host["chunk_steps"]


def _idle_unnamed_pct(out: Dict) -> Optional[float]:
    host = _host(out)
    if not host or not host["idle_s"]:
        return None
    return 100.0 * host["unnamed_idle_s"] / host["idle_s"]


def _every(cell, model):
    return True


def _sparse(cell, model):
    return bool(cell.get("moe"))


def _has_mixer(cell, model):
    return any(k in (model.get("layer_pattern") or "") for k in "MGS")


def _recomputes(cell, model):
    return bool(model.get("remat"))


def _saves(cell, model):
    return cell["save_memory_interval"] < cell["max_steps"]


STEP, CKPT, LOOP = "step program", "flash checkpoint", "trainer loop"
METRICS = {
    "step.attn_ms": (STEP, "ms", _every, _part("attn")),
    # a dense feed-forward layer whole; of a sparse layer the norm and the
    # residual that _mlp_block runs around its expert block
    "step.mlp_ms": (STEP, "ms", _every, _part("mlp")),
    "step.moe_ms": (STEP, "ms", _sparse, _part("moe")),
    # grouped matmuls and activation; moe_ms less this is router, sort,
    # gather, scatter-add and shared expert
    "step.moe_experts_ms": (
        STEP, "ms", _sparse,
        lambda out: _ms_a_step(out, _under("layer/moe/experts")),
    ),
    "step.mixer_ms": (STEP, "ms", _has_mixer, _part("mixer")),
    # serial pass and chunk kernels; the rest of mixer_ms is projections,
    # convolution, gate and norm
    "step.mixer_scan_ms": (
        STEP, "ms", _has_mixer,
        lambda out: _ms_a_step(
            out, lambda p, s, _ph: p == "mixer" and "scan" in s.split("/")),
    ),
    "step.head_ms": (STEP, "ms", _every, _part("head")),
    # what of gradient sync, norm and optimizer runs as operations of its
    # own: a leaf's update fused into its gradient matmul counts with that
    # leaf's part
    "step.update_ms": (STEP, "ms", _every, _part("update")),
    "step.recompute_ms": (
        STEP, "ms", _recomputes,
        lambda out: _ms_a_step(out, lambda _p, _s, ph: ph == "recompute"),
    ),
    "step.unscoped_pct": (
        STEP, "%", _every,
        lambda out: 100.0 * out["parts"].get(UNSCOPED, 0.0) / out["own_s"],
    ),
    "ckpt.stage_idle_ms_per_chunk_step": (CKPT, "ms", _saves, _stage_idle_ms),
    "loop.idle_unnamed_pct": (LOOP, "%", _saves, _idle_unnamed_pct),
}


def metrics(out: Dict, cell: Dict, model: Dict) -> Dict[str, float]:
    """Every metric whose rule takes the cell and whose part has time in
    the table."""
    values = {}
    for name, (_layer, _unit, rule, value) in METRICS.items():
        if rule(cell, model):
            v = value(out)
            if v is not None:
                values[name] = v
    return values


def takes(metric: str, cell: Dict, data_dir: str = HERE) -> bool:
    """A reader's ``CELLS``: does ``metric``'s rule take the cell, by the
    cell's file and its configuration's ``model`` group alone. A cell of
    another data directory (a rehearsal's) is left to ``read``."""
    try:
        with open(os.path.join(
            data_dir, "configs", f"{cell.get('config')}.json"
        )) as f:
            model = json.load(f)["model"]
    except (OSError, ValueError, KeyError, TypeError):
        return True
    return METRICS[metric][2](cell, model)


def read(run, metric: str) -> Optional[float]:
    """A reader's ``read``: ``metric`` from the table of the run's trace
    file, which the first reader that asks has this file make (and print,
    for the traced run's log) and the others find beside the trace."""
    files = ((run.window or {}).get("trace") or {}).get("files") or []
    if not files:
        return None
    try:
        with open(files[0] + ".scopes.json") as f:
            out = json.load(f)
    except (OSError, ValueError):
        if not os.path.exists(files[0]):
            return None
        out = run_on(files[0])
        if out is not None:
            shown = dict(out, host=_without_spans(out.get("host")))
            print(json.dumps({"device_time_by_scope": shown}), flush=True)
    if not out or "refused" in out:
        return None
    return metrics(out, run.cell, run.config.get("model") or {}).get(metric)


def _without_spans(host: Optional[Dict]) -> Optional[Dict]:
    return host and {k: v for k, v in host.items() if k != "spans"}


def run_on(trace_file: str, timeout: float = 600.0) -> Optional[Dict]:
    """This file as a process of its own, held to the CPU as
    ``run.reduce_trace`` holds ``xplane.py``, its output kept beside the
    trace file; what it wrote, or None."""
    out = trace_file + ".scopes.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    subprocess.run(
        [sys.executable, os.path.abspath(__file__), trace_file, out],
        env=env, timeout=timeout, stdout=subprocess.DEVNULL,
    )
    try:
        with open(out) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 2:
        print(__doc__, file=sys.stderr)
        return 2
    t0 = time.monotonic()
    try:
        out = reduce_file(argv[0])
    except xplane.NoStretch as e:
        out = {"refused": str(e)}
    else:
        out["took_s"]["whole"] = time.monotonic() - t0
    with open(argv[1], "w") as f:
        json.dump(out, f)
    print(json.dumps(dict(out, host=_without_spans(out.get("host")))),
          flush=True)
    return 3 if "refused" in out else 0


if __name__ == "__main__":
    sys.exit(main())
