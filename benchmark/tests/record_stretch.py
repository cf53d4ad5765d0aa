#!/usr/bin/env python3
"""Keep one traced run's device events as a small test file:

    JAX_PLATFORMS=cpu python benchmark/tests/record_stretch.py \\
        <trace.xplane.pb> <window_r0.json> <the run's stdout> <out.json.gz>

Of the first device plane it keeps the ``XLA Modules`` events and the
``XLA Ops`` intervals as ``[name index, ns since the start of the event
before it, dur_ns]`` (whole nanoseconds, in order of start; names kept, HLO
text and stats dropped; ``events`` below reads them back),
with the host's side of the same capture from ``window_r0.json``, the run's
``loop.step_p50_ms`` and the hooks' median period over the traced steps (from
the lines ``run.py`` printed), and beside them what the reduction before PR 33
printed from the two clocks (``old_line``) and what this one reads
(``expect``). ``test_trace_window.py`` reads the file.
"""

import gzip
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import xplane  # noqa: E402


def events(rows, names):
    """The rows of a recorded line back as ``xplane`` events."""
    out, at = [], 0
    for i, since, dur in rows:
        at += since
        out.append((names[i], float(at), float(dur), {}))
    return out


def recorded_plane(rec):
    return {"name": rec["plane"], "lines": [
        {"name": xplane.MODULES_LINE,
         "events": events(rec["modules"], rec["names"])},
        {"name": xplane.OPS_LINE, "events": events(rec["ops"], rec["names"])},
    ]}


def record(plane, host, printed):
    lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
    t0 = min(e[1] for e in lines[xplane.OPS_LINE] + lines[xplane.MODULES_LINE])
    names, index = [], {}

    def rows(events):
        out, before = [], 0
        for name, start, dur, _stats in sorted(events, key=lambda e: e[1]):
            if name not in index:
                index[name] = len(names)
                names.append(name)
            at = round(start - t0)
            out.append([index[name], at - before, round(dur)])
            before = at
        return out

    rec = {
        "plane": plane["name"],
        "modules": rows(lines[xplane.MODULES_LINE]),
        "ops": rows(lines[xplane.OPS_LINE]),
        "names": names,
        "host": {
            "t_begin": host["t_begin"], "t_end": host["t_end"],
            "hooks": host["step_end"] - host["step_begin"],
            "profiler_calls": host["profiler_calls"],
        },
        **printed,
    }
    # what the rounded events read, so that the test holds the file to it
    reduced = xplane.reduce_planes([recorded_plane(rec)])
    d = reduced["devices"][0]
    rec["old_line"] = {
        "busy_s": d["whole_file"]["busy_s"],
        "window_s": host["t_end"] - host["t_begin"],
    }
    rec["expect"] = {k: reduced[k] for k in ("steps", "window_s", "busy_s")}
    return rec


def main(argv) -> int:
    if len(argv) != 4:
        print(__doc__, file=sys.stderr)
        return 2
    planes = [
        p for p in xplane.load(argv[0])
        if p["name"].startswith(xplane.DEVICE_PLANE_PREFIX)
    ]
    with open(argv[1]) as f:
        host = json.load(f)["trace"]
    printed = {}
    with open(argv[2]) as f:
        for ln in f:
            try:
                obj = json.loads(ln)
            except ValueError:
                continue
            if "per_layer" in obj:
                printed["loop.step_p50_ms"] = obj["per_layer"][
                    "loop.step_p50_ms"]
            if "host_clock" in obj:
                printed["step_p50_ms_over_the_hooks"] = obj["host_clock"][
                    "step_p50_ms_over_the_hooks"]
    rec = record(planes[0], host, printed)
    with gzip.open(argv[3], "wt", compresslevel=9) as f:
        json.dump(rec, f, separators=(",", ":"))
    print(json.dumps({
        "bytes": os.path.getsize(argv[3]), "ops": len(rec["ops"]),
        "modules": len(rec["modules"]), "old_line": rec["old_line"],
        "expect": rec["expect"],
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
