#!/usr/bin/env python3
"""One traced run of a cell, with its trace file read by ``scopes.py``
before ``run.py`` throws the trace away: how PERF.md's table "device time
by part" is made (on the chip, by hand).

    python benchmark/tests/scope_table.py <cell> <seed> [<seconds>] \\
        [--out DIR] [--keep-trace]

``run.run_cell`` removes the run's ``trace/`` directory as soon as
``xplane.py`` has reduced it, before any per-layer reader is asked, so no
file under ``layer_metrics/`` can read the ``.xplane.pb`` (PERF.md, Open
questions). This tool wraps ``run.reduce_trace`` for the one run it makes:
``scopes.py`` reads the file as a process of its own right after
``xplane.py`` has. ``DIR/<cell>.<seed>.scopes.json`` (default
``chiprun_out/``) keeps the table, ``DIR/<cell>.<seed>.log`` what the run
printed; the last line holds the metrics of ``scopes.METRICS`` beside
``step.device_ms`` and the two sums that must agree with it.
"""

import contextlib
import gzip
import io
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import run as harness  # noqa: E402
import scopes  # noqa: E402

PART_METRICS = ("attn", "mlp", "moe", "mixer", "head", "update")


def main(argv) -> int:
    out_dir = os.path.join(ROOT, "chiprun_out")
    keep_trace = "--keep-trace" in argv
    argv = [a for a in argv if a != "--keep-trace"]
    if "--out" in argv:
        i = argv.index("--out")
        out_dir = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    cell_name, seed = argv[0], int(argv[1])
    seconds = float(argv[2]) if len(argv) > 2 else 45.0
    os.makedirs(out_dir, exist_ok=True)
    tag = os.path.join(out_dir, f"{cell_name}.{seed}")
    kept = {}
    reduce_trace = harness.reduce_trace

    def reduce_and_read(files, run_dir):
        reduced = reduce_trace(files, run_dir)
        if files:
            t0 = time.monotonic()
            kept["table"] = scopes.run_on(files[0])
            kept["scopes_wall_s"] = time.monotonic() - t0
            kept["trace_bytes"] = os.path.getsize(files[0])
            if keep_trace:
                with open(files[0], "rb") as f, gzip.open(
                    tag + ".xplane.pb.gz", "wb"
                ) as g:
                    shutil.copyfileobj(f, g)
        return reduced

    harness.reduce_trace = reduce_and_read
    said = io.StringIO()
    try:
        with contextlib.redirect_stdout(said):
            result = harness.run_cell(cell_name, seed, seconds, True)
    except harness.Refused as e:
        result = {"refused": str(e), **e.detail}
    finally:
        harness.reduce_trace = reduce_trace
        with open(tag + ".log", "w") as f:
            f.write(said.getvalue())
    table = kept.get("table")
    if table is not None:
        with open(tag + ".scopes.json", "w") as f:
            json.dump(table, f)
    if "refused" in result or not table or "refused" in table:
        print(json.dumps({"cell": cell_name, "seed": seed, "run": result,
                          "scopes": table}))
        return 3
    cell = harness.load_cell(cell_name)
    model = harness.load_config(cell["config"])["model"]
    values = scopes.metrics(table, cell, model)
    device_ms = result["metrics"]["step.device_ms"]["value"]
    parts_ms = 1e3 * sum(table["parts"].values()) / table["steps"]
    print(json.dumps({
        "cell": cell_name, "seed": seed, "correct": result["correct"],
        "steps": table["steps"], "step.device_ms": device_ms,
        "parts_sum_ms": parts_ms,
        "parts_sum_off_pct": 100.0 * (parts_ms / device_ms - 1.0),
        "busy_s": [result["device"]["busy_s"], table["busy_s"],
                   table["own_s"]],
        "metrics": values,
        "unknown_scopes": table["unknown_scopes"],
        "clock_check": (table.get("host") or {}).get("clock_check"),
        "gaps_by_span": (table.get("host") or {}).get("gaps_by_span"),
        "scopes_s": dict(table["took_s"], process=kept["scopes_wall_s"]),
        "trace_bytes": kept["trace_bytes"], "paths": table["paths"],
        "events": table["events"],
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
