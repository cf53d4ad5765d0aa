"""Reduction of a JAX profiler trace (``*.xplane.pb``) to what the
benchmark reports: which planes and lines there are, the device's busy
time, device time by operation name, and the events of one kernel.

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
``%transpose_jvp___.N``).
"""

import json
import re
import sys
from typing import Dict, Iterable, List, Tuple

# the planes that are chips, and on them the line that holds one event
# per executed HLO operation (children nested inside their parents)
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

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


def gaps_table(events: List[Event], keep: int = 20) -> List[Dict]:
    """Idle stretches of the device between operations, summed by the
    name of the operation that ended each: where in the program the
    device waits (the first operation of a step ends the host's gap)."""
    ordered = sorted(events, key=lambda e: e[1])
    table: Dict[str, Dict] = {}
    busy_until = None
    for name, start, dur, _stats in ordered:
        if busy_until is not None and start > busy_until:
            row = table.setdefault(
                name, {"before": name, "seconds": 0.0, "count": 0,
                       "longest_s": 0.0}
            )
            gap = (start - busy_until) / 1e9
            row["seconds"] += gap
            row["count"] += 1
            row["longest_s"] = max(row["longest_s"], gap)
        busy_until = max(busy_until or 0.0, start + dur)
    return sorted(table.values(), key=lambda r: -r["seconds"])[:keep]


def reduce_planes(planes: List[Dict]) -> Dict:
    """The reduced trace: a listing of what is there, and per device
    plane the busy seconds (union of the operation events), the stretch
    the events span, and the operations table."""
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
    devices = []
    for p in planes:
        if not p["name"].startswith(DEVICE_PLANE_PREFIX):
            continue
        ops = [ln for ln in p["lines"] if ln["name"] == OPS_LINE]
        if not ops or not ops[0]["events"]:
            continue
        events = ops[0]["events"]
        first = min(e[1] for e in events)
        last = max(e[1] + e[2] for e in events)
        modules = [
            ln for ln in p["lines"] if ln["name"] == MODULES_LINE
        ]
        devices.append(
            {
                "plane": p["name"],
                "busy_s": union_seconds(
                    (e[1], e[1] + e[2]) for e in events
                ),
                "span_s": (last - first) / 1e9,
                "events": len(events),
                "ops": ops_table(events),
                "gaps": gaps_table(events),
                "modules": ops_table(modules[0]["events"])
                if modules else [],
            }
        )
    out = {"listing": listing, "devices": devices}
    if devices:
        out["busy_s"] = sum(d["busy_s"] for d in devices) / len(devices)
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
    reduced = reduce_planes(load(argv[0]))
    with open(argv[1], "w") as f:
        json.dump(reduced, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
