#!/usr/bin/env python3
"""Run one cell several times in one call and keep what each run printed:
how the bounds in BENCHMARK.json were measured (PERF.md, section 2).

    python benchmark/tests/measure.py <cell> <seconds> <trace 0|1> \\
        <seed> [<seed> ...] [--out DIR]

Each run is ``benchmark/run.py`` as the driver would start it, one after
the other (one process owns the chip at a time). The full output of every
run goes to ``DIR/<cell>.<trace>.<seed>.log`` (default ``chiprun_out/``);
the last lines and, for each metric, median and quartile spread
(``statistics.quantiles(n=4)``, as a share of the median) are printed at
the end. A traced run also leaves its reduced trace there.
"""

import json
import os
import shutil
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def spread(values):
    if len(values) < 2:
        return None
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv) -> int:
    out = os.path.join(ROOT, "chiprun_out")
    if "--out" in argv:
        i = argv.index("--out")
        out = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    cell, seconds, trace, seeds = argv[0], argv[1], argv[2], argv[3:]
    os.makedirs(out, exist_ok=True)
    results = []
    for seed in seeds:
        t0 = time.monotonic()
        p = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
             "--workload", cell, "--seed", seed, "--seconds", seconds,
             "--trace", trace],
            cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        took = time.monotonic() - t0
        tag = f"{cell}.t{trace}.{seed}"
        with open(os.path.join(out, tag + ".log"), "w") as f:
            f.write(p.stdout)
        reduced = os.path.join(
            ROOT, ".benchmark_run", cell, "trace_reduced.json"
        )
        if trace == "1" and os.path.exists(reduced):
            shutil.copy(reduced, os.path.join(out, tag + ".trace.json"))
        lines = p.stdout.strip().splitlines()
        last = lines[-1] if lines else ""
        print(json.dumps({"seed": seed, "rc": p.returncode,
                          "wall_s": round(took, 1)}), flush=True)
        for ln in lines[-6:-1]:
            print("   ", ln[:1500], flush=True)
        print(last, flush=True)
        if p.returncode == 0:
            try:
                results.append(json.loads(last))
            except ValueError:
                pass
    names = sorted({k for r in results for k in r["metrics"]})
    for name in names:
        vals = [r["metrics"][name]["value"] for r in results
                if name in r["metrics"]]
        print(json.dumps({
            "metric": name, "n": len(vals), "values": vals,
            "median": statistics.median(vals),
            # the first run of a call compiles: set-up apart from it
            "median_after_first": statistics.median(vals[1:])
            if len(vals) > 1 else None,
            "iqr_share": spread(vals),
            "iqr_share_after_first": spread(vals[1:]),
        }), flush=True)
    return 0 if len(results) == len(seeds) else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
