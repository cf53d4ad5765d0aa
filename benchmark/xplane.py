"""Reduction of a JAX profiler trace (``*.xplane.pb``) to what the
benchmark reports: which planes and lines there are, the traced stretch
(a run of whole step executions on the device's own clock), the device's
busy time and idle gaps inside it, device time by operation name, and the
events of one kernel.

Read with ``jax.profiler.ProfileData`` and nothing else. The benchmark's
parent runs this file as a process of its own, held to the CPU, once the
worker has released the chip:

    python benchmark/xplane.py <trace.xplane.pb> <out.json>

What a TPU v5e trace looks like (looked at by hand, my chip run, PR 24):
planes ``/device:TPU:0`` (lines ``Steps``, ``XLA Modules``, ``XLA Ops``,
``Async XLA Ops``, ``TC Overlay``), ``/host:CPU`` (one line per thread),
``#Chip0 Host Interface``, ``#Chip0 Misc``, ``/device:CUSTOM:Megascale
Trace``, ``/host:metadata`` and ``Task Environment`` (all empty). An
``XLA Ops`` event is named by its whole HLO text; a Pallas kernel is a
``custom-call`` with ``custom_call_target="tpu_custom_call"`` (the flash
attention forward shows as ``%jvp__.N``, its backward as
``%transpose_jvp___.N``; since the kernels carry a ``name=`` they show as
``%flash_attn_*.N``). The ``XLA Modules`` line holds one event per executed
program, named ``jit_<function>(<fingerprint>)``.

**The traced stretch** (``step_stretch``). The capture starts and stops
somewhere inside a step, and the host's clock around ``start_trace()`` and
``stop_trace()`` is another clock, so nothing here is measured from the
file's edges or from the host. The step programs are the programs on the
``XLA Modules`` line whose longest execution is at least half the longest
on the line (the donating step and the safe twin; not staging's slices,
copies and converts). Their first and last executions may be cut by the
capture and do not count. The stretch runs from the start of the first
whole execution to the start of the last execution on the line: ``steps``
whole device periods, each with the gap that follows it. Operations are
clipped to the stretch, so ``0 < busy_s <= window_s`` on any trace that
holds one; a trace that holds fewer than ``MIN_STEPS`` whole steps raises
``NoStretch`` and gives no number.
"""

import json
import re
import sys
from typing import Dict, Iterable, List, Optional, Tuple

# the planes that are chips, and on them the line that holds one event
# per executed HLO operation (children nested inside their parents)
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

# a program is a step program when its longest execution is at least this
# share of the longest execution on the line; a stretch of fewer whole steps
# than MIN_STEPS is not measured
STEP_SHARE = 0.5
MIN_STEPS = 3

Event = Tuple[str, float, float, Dict]  # name, start_ns, dur_ns, stats
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def load(path: str) -> List[Dict]:
    """Planes -> lines -> events as plain Python, stats as a dict."""
    from jax.profiler import ProfileData

    space = ProfileData.from_file(path)
    planes = []
    for plane in space.planes:
        lines = []
        for line in plane.lines:
            events = []
            for e in line.events:
                try:
                    stats = {str(k): v for k, v in e.stats}
                except Exception:
                    stats = {}
                # a TPU operation's event carries its whole HLO text as
                # its name: "%fusion.12 = (f32[...]) fusion(...)"
                name, _, text = e.name.partition(" = ")
                if text:
                    target = _TARGET.search(text)
                    if target:
                        stats["custom_call_target"] = target.group(1)
                    stats["hlo"] = text
                events.append(
                    (name, float(e.start_ns), float(e.duration_ns), stats)
                )
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def union_seconds(intervals: Iterable[Tuple[float, float]]) -> float:
    """Length of the union of [start, end) intervals given in ns."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def self_times(events: List[Event]) -> List[float]:
    """Each event's duration less the part its nested children cover, in
    ns, in the order of ``events``. Events of one line nest properly (a
    while loop holds its body's operations)."""
    order = sorted(
        range(len(events)), key=lambda i: (events[i][1], -events[i][2])
    )
    own = [e[2] for e in events]
    stack: List[int] = []
    for i in order:
        start, end = events[i][1], events[i][1] + events[i][2]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            parent = stack[-1]
            p_end = events[parent][1] + events[parent][2]
            own[parent] -= min(end, p_end) - start
        stack.append(i)
    return [max(v, 0.0) for v in own]


def _describe(stats: Dict) -> str:
    """The event's stats as one searchable string (kernel and scope
    names live here, under keys that vary with the profiler version)."""
    parts = []
    # the kernel's target first: the HLO text after it is cut short
    for k, v in sorted(
        stats.items(), key=lambda kv: kv[0] != "custom_call_target"
    ):
        if isinstance(v, (str, bytes)):
            v = v.decode(errors="replace") if isinstance(v, bytes) else v
            parts.append(f"{k}={v}")
    return " ".join(parts)[:400]


def ops_table(events: List[Event]) -> List[Dict]:
    """Device time by operation name: count, total and self seconds."""
    own = self_times(events)
    table: Dict[str, Dict] = {}
    for (name, _s, dur, stats), self_ns in zip(events, own):
        row = table.get(name)
        if row is None:
            row = table[name] = {
                "name": name, "count": 0, "total_s": 0.0, "self_s": 0.0,
                "about": _describe(stats),
            }
        row["count"] += 1
        row["total_s"] += dur / 1e9
        row["self_s"] += self_ns / 1e9
    return sorted(table.values(), key=lambda r: -r["self_s"])


class NoStretch(ValueError):
    """The trace holds no run of whole step executions to measure."""


def step_stretch(modules: List[Event]) -> Dict:
    """The traced stretch of one device, from its ``XLA Modules`` events
    (see the module docstring): ``begin_ns``, ``end_ns``, ``steps`` and
    what was counted. ``NoStretch`` where there are fewer than
    ``MIN_STEPS`` whole steps."""
    longest: Dict[str, float] = {}
    for name, _start, dur, _stats in modules:
        longest[name] = max(longest.get(name, 0.0), dur)
    top = max(longest.values(), default=0.0)
    programs = sorted(
        n for n, d in longest.items() if top > 0 and d >= STEP_SHARE * top
    )
    runs = sorted(
        (e for e in modules if e[0] in programs), key=lambda e: e[1]
    )
    whole = runs[1:-1]
    counted = {
        "step_programs": programs,
        "step_executions": len(runs),
        "other_executions": len(modules) - len(runs),
        "steps": len(whole),
    }
    if len(whole) < MIN_STEPS or not runs[-1][1] > whole[0][1]:
        raise NoStretch(
            f"{len(whole)} whole step executions, want {MIN_STEPS}: "
            f"{json.dumps(counted)}"
        )
    return dict(counted, begin_ns=whole[0][1], end_ns=runs[-1][1])


def clip(events: List[Event], begin: float, end: float) -> List[Event]:
    """The parts of ``events`` inside [begin, end), those left with no
    length dropped."""
    out = []
    for name, start, dur, stats in events:
        s, e = max(start, begin), min(start + dur, end)
        if e > s:
            out.append((name, s, e - s, stats))
    return out


def gaps_table(events: List[Event], begin: float, end: float,
               keep: int = 20) -> Tuple[List[Dict], float]:
    """Idle stretches of the device inside [begin, end), summed by the
    name of the operation that ended each: where in the program the
    device waits (the first operation of a step ends the host's gap; the
    operation that opens the execution at ``end`` ends the last). Returns
    the ``keep`` largest rows and the idle nanoseconds of all."""
    gaps: List[Tuple[str, float]] = []  # the operation that ended it, ns
    busy_until = begin
    for name, start, dur, _stats in sorted(events, key=lambda e: e[1]):
        if start + dur <= begin:
            continue
        if min(start, end) > busy_until:
            gaps.append((name, min(start, end) - busy_until))
        busy_until = max(busy_until, start + dur)
        if busy_until >= end:
            break
    if end > busy_until:
        gaps.append(("(the trace ends)", end - busy_until))
    table: Dict[str, Dict] = {}
    for name, ns in gaps:
        row = table.setdefault(
            name, {"before": name, "seconds": 0.0, "count": 0,
                   "longest_s": 0.0}
        )
        row["seconds"] += ns / 1e9
        row["count"] += 1
        row["longest_s"] = max(row["longest_s"], ns / 1e9)
    rows = sorted(table.values(), key=lambda r: -r["seconds"])[:keep]
    return rows, sum(ns for _name, ns in gaps)


def reduce_device(plane: Dict) -> Optional[Dict]:
    """One device plane reduced over its traced stretch; None where no
    operation ran on it. ``busy_s`` is the stretch less its idle gaps,
    so it cannot pass ``window_s``, whatever the trace."""
    lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
    events = lines.get(OPS_LINE) or []
    if not events:
        return None
    try:
        stretch = step_stretch(lines.get(MODULES_LINE) or [])
    except NoStretch as e:
        raise NoStretch(f"{plane['name']}: {e}") from None
    begin, end = stretch.pop("begin_ns"), stretch.pop("end_ns")
    gaps, idle_ns = gaps_table(events, begin, end)
    if not idle_ns < end - begin:
        raise NoStretch(
            f"{plane['name']}: no operation ran in the stretch of "
            f"{stretch['steps']} steps"
        )
    inside = clip(events, begin, end)
    first = min(e[1] for e in events)
    last = max(e[1] + e[2] for e in events)
    return dict(
        stretch,
        plane=plane["name"],
        window_s=(end - begin) / 1e9,
        busy_s=(end - begin - idle_ns) / 1e9,
        idle_s=idle_ns / 1e9,
        events=len(inside),
        ops=ops_table(inside),
        gaps=gaps,
        modules=ops_table(clip(lines[MODULES_LINE], begin, end)),
        # the whole file, edges and all, as the reduction before PR 33
        # summed it: for comparison by eye, never for a metric
        whole_file={
            "busy_s": union_seconds((e[1], e[1] + e[2]) for e in events),
            "span_s": (last - first) / 1e9,
            "events": len(events),
            "stretch_begins_s": (begin - first) / 1e9,
            "stretch_ends_before_s": (last - end) / 1e9,
        },
    )


def reduce_planes(planes: List[Dict]) -> Dict:
    """The reduced trace: a listing of what is there and, per device
    plane on which anything ran, its traced stretch. ``steps``,
    ``window_s`` and ``busy_s`` at the top are means over those planes."""
    listing = [
        {
            "plane": p["name"],
            "lines": [
                {"line": ln["name"], "events": len(ln["events"])}
                for ln in p["lines"]
            ],
        }
        for p in planes
    ]
    devices = [
        d for d in (
            reduce_device(p) for p in planes
            if p["name"].startswith(DEVICE_PLANE_PREFIX)
        ) if d is not None
    ]
    out = {"listing": listing, "devices": devices}
    for key in ("steps", "window_s", "busy_s"):
        if devices:
            out[key] = sum(d[key] for d in devices) / len(devices)
    return out


def kernel_seconds(device: Dict, patterns: Iterable[str]) -> Dict:
    """Summed device seconds of the operations whose name or stats hold
    one of ``patterns`` (lower-cased substring match), with the names
    matched."""
    pats = [p.lower() for p in patterns]
    rows = [
        r for r in device["ops"]
        if any(p in (r["name"] + " " + r["about"]).lower() for p in pats)
    ]
    return {
        "seconds": sum(r["total_s"] for r in rows),
        "count": sum(r["count"] for r in rows),
        "names": [r["name"] for r in rows],
    }


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 2:
        print(__doc__, file=sys.stderr)
        return 2
    try:
        reduced = reduce_planes(load(argv[0]))
    except NoStretch as e:
        reduced = {"refused": str(e)}
    with open(argv[1], "w") as f:
        json.dump(reduced, f)
    return 3 if "refused" in reduced else 0


if __name__ == "__main__":
    sys.exit(main())
