#!/usr/bin/env python3
"""One run of one benchmark cell, through the system's normal entry points:

    launcher (``python -m dlrover_tpu.trainer.run``) -> agent ->
    ``benchmark/worker.py`` -> ``ElasticTrainer.train`` on the chip

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, ``breakdown``); everything
else worth reading is on earlier lines. The exit code is 0 only when the
run reached its end on a TPU with the chips the cell asks for; otherwise
nothing is printed as a result.

This process never imports JAX: a parent that has touched JAX holds the
chip, and the worker that needs it then fails or hangs. It knows no cell,
configuration or metric by name: a cell is ``cells/<name>.json``, a
configuration ``configs/<name>.json``, a per-layer metric
``layer_metrics/<name>.py`` (see README.md).
"""

from __future__ import annotations

import argparse
import contextlib
import glob
import importlib.util
import io
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import time
from types import SimpleNamespace
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import arith  # noqa: E402
import flops  # noqa: E402
import peaks  # noqa: E402

# Why the replay is not bitwise on the chip (chip_smoke.py, PERF.md
# Findings PR 22): the first incarnation runs the steps after a save on
# the non-donating step (staging reads the state), the second replays them
# on the donating twin; the two programs round Adam's moments differently
# in the last bit and the bf16 forward amplifies it. The restore itself is
# held exactly (checksums), the first replayed loss bitwise, the later
# ones to this bound. PR 22 read up to 5e-3 between the twins within ten
# steps of early training (its twin check) and 7.0e-5 over the smoke's
# replay at batch 8; this benchmark's replays, 24 steps at batch 16 from
# step 350 or 400 on, read 1.3e-4, 3.1e-4 and 1.25e-3 (my chip runs,
# PR 24) at a loss near 7.5, so the bound is 5e-3.
REPLAY_ATOL = {"tpu": 5e-3, "cpu": 0.0}
RUN_DEADLINE_S = 1150.0
TAIL_LINES = 30


class Refused(Exception):
    """The run cannot start or did not reach its end: no result line.
    ``detail`` holds the numbers that go into the note beside the reason."""

    def __init__(self, reason: str, detail: Optional[Dict] = None):
        super().__init__(reason)
        self.detail = detail or {}


def note(obj: Dict) -> None:
    print(json.dumps(obj), flush=True)


def read_json(path: str, what: str) -> Dict:
    try:
        with open(path) as f:
            obj = json.load(f)
    except OSError as e:
        raise Refused(f"{what}: cannot read {path}: {e}") from None
    except ValueError as e:
        raise Refused(f"{what}: {path} does not parse: {e}") from None
    if not isinstance(obj, dict):
        raise Refused(f"{what}: {path} is not a JSON object")
    return obj


def maybe_json(path: str) -> Optional[Dict]:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def json_lines(text: str) -> List:
    """Each line of ``text`` as the object it holds, or as it is."""
    out = []
    for line in text.splitlines():
        try:
            out.append(json.loads(line))
        except ValueError:
            out.append(line)
    return out


def is_number(v) -> bool:
    return (
        isinstance(v, (int, float)) and not isinstance(v, bool)
        and math.isfinite(v)
    )


def load_module(name: str, path: str):
    """The Python file at ``path`` as a module of its own."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_text(path: str) -> str:
    try:
        with open(path, errors="replace") as f:
            return f.read()
    except OSError:
        return ""


CELL_KEYS = ("config", "traffic", "batch", "seq", "save_memory_interval",
             "kill", "warmup", "max_steps", "why")
CONFIG_KEYS = ("source", "model", "optimizer", "strategy", "reduced",
               "reference", "reference_check")
# what a configuration's family module (its ``flops`` key) has to define
HOOK = ("count", "step_work")


def load_cell(name: str, data_dir: str = HERE) -> Dict:
    if not re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.\-]*", name or ""):
        raise Refused(f"not a cell name: {name!r}")
    cell = read_json(
        os.path.join(data_dir, "cells", f"{name}.json"), "cell"
    )
    missing = [k for k in CELL_KEYS if k not in cell]
    if missing:
        raise Refused(f"cell {name}: missing keys {missing}")
    return cell


def load_config(name: str, data_dir: str = HERE) -> Dict:
    config = read_json(
        os.path.join(data_dir, "configs", f"{name}.json"), "configuration"
    )
    missing = [k for k in CONFIG_KEYS if k not in config]
    if missing:
        raise Refused(f"configuration {name}: missing keys {missing}")
    ref = os.path.join(HERE, "references", f"{config['reference']}.py")
    if not os.path.exists(ref):
        raise Refused(f"configuration {name}: no plain reference {ref}")
    load_hook(name, config, data_dir)
    return config


def load_hook(name: str, config: Dict, data_dir: str = HERE):
    """The configuration's family module: ``<flops>.py`` (``flops`` where
    the key is absent) beside ``configs/`` or, failing that, here. It
    counts the model's parameters and a step's least work for ``run.py``
    and the trace readers, which reckon no model's shape themselves."""
    module = config.get("flops", "flops")
    if not re.fullmatch(r"[A-Za-z_][A-Za-z0-9_]*", str(module)):
        raise Refused(f"configuration {name}: flops {module!r} names no module")
    paths = [os.path.join(d, f"{module}.py") for d in (data_dir, HERE)]
    path = next((p for p in paths if os.path.exists(p)), None)
    if path is None:
        raise Refused(
            f"configuration {name}: no family module {module}.py "
            f"(looked for {' and '.join(dict.fromkeys(paths))})"
        )
    try:
        hook = load_module("family_" + module, path)
    except Exception as e:
        raise Refused(f"configuration {name}: {path}: {e!r}") from None
    missing = [f for f in HOOK if not callable(getattr(hook, f, None))]
    if missing:
        raise Refused(
            f"configuration {name}: {path} lacks the function(s) {missing} "
            f"of the hook {list(HOOK)}"
        )
    return hook


def ask_hook(hook, name: str, model: Dict, batch: int, seq: int) -> Dict:
    """What the family module's ``count`` says of this cell. Both functions
    are asked before the run starts, so that a module that cannot count the
    model, or answers in another shape than the hook's (``count``'s three
    numbers; ``step_work``'s kinds, each ``None`` or operations and bytes),
    costs no time on the chip."""
    def number(v):
        return is_number(v) and v > 0

    try:
        counted = hook.count(model, seq)
        work = hook.step_work(model, batch, seq)
        sound = all(
            number(counted[k])
            for k in ("params", "active_params", "train_flops_per_token")
        ) and all(
            w is None or (number(w["flops"]) and number(w["bytes"]))
            for w in work.values()
        )
    except Exception as e:
        raise Refused(
            f"configuration {name}: {hook.__file__} cannot count the "
            f"model: {e!r}"
        ) from None
    if not sound:
        raise Refused(
            f"configuration {name}: {hook.__file__} answers in another "
            f"shape than the hook's", {"count": counted, "step_work": work},
        )
    return counted


def load_layer_metrics() -> Dict[str, object]:
    """Every ``layer_metrics/<metric>.py``, by the metric's name."""
    found = {}
    for path in sorted(glob.glob(os.path.join(HERE, "layer_metrics", "*.py"))):
        name = os.path.basename(path)[:-3]
        try:
            mod = load_module(
                "layer_metric_" + re.sub(r"\W", "_", name), path
            )
            for attr in ("LAYER", "UNIT", "MOVES", "CELLS", "read"):
                getattr(mod, attr)
        except Exception as e:
            raise Refused(f"per-layer metric {path}: {e!r}") from None
        found[name] = mod
    return found


def child_env(run_dir: str, sock_dir: str) -> Dict[str, str]:
    """Every path the package would otherwise keep under a fixed global
    name goes into the run directory, through the override each already
    has; nothing of an outer job rides along (chip_smoke.py)."""
    env = {
        k: v for k, v in os.environ.items()
        if not k.startswith("DLROVER_TPU_") and k != "BENCH_RUN"
    }
    env.update({
        "DLROVER_TPU_FLIGHT_DIR": os.path.join(run_dir, "flight"),
        "DLROVER_TPU_PARAL_CONFIG_PATH": os.path.join(
            run_dir, "auto_paral_config.json"),
        "DLROVER_TPU_RUNTIME_METRICS_PATH": os.path.join(
            run_dir, "runtime_metrics.json"),
        "DLROVER_TPU_WORKER_COMMANDS_PATH": os.path.join(
            run_dir, "worker_commands.json"),
        "DLROVER_TPU_TOPOLOGY_CACHE": os.path.join(run_dir, "topology"),
        "DLROVER_TPU_SOCKET_DIR": sock_dir,
        "TPU_LOG_DIR": os.path.join(run_dir, "tpu_logs"),
    })
    return env


def socket_dir() -> str:
    """AF_UNIX paths may hold 108 bytes: a fresh short directory under
    TMPDIR, or under /tmp where TMPDIR itself is too long."""
    d = tempfile.mkdtemp(prefix="dtb")
    if len(d) > 40:
        shutil.rmtree(d, ignore_errors=True)
        d = tempfile.mkdtemp(prefix="dtb", dir="/tmp")
    return d


class Chain:
    """One launcher run under supervision (chip_smoke.py's ``Chain``)."""

    def __init__(self, run_dir: str, device_spec: str, max_restarts: int):
        self.run_dir = run_dir
        self.log_dir = os.path.join(run_dir, "logs")
        self.launcher_log = os.path.join(run_dir, "launcher.log")
        self.device_spec = device_spec
        self.max_restarts = max_restarts
        self.sock_dir = ""
        self.proc: Optional[subprocess.Popen] = None
        self._out = None

    def start(self) -> None:
        os.makedirs(self.log_dir, exist_ok=True)
        self.sock_dir = socket_dir()
        cmd = [
            sys.executable, "-m", "dlrover_tpu.trainer.run",
            "--nnodes=1", "--nproc-per-node=1",
            f"--device-spec={self.device_spec}",
            f"--max-restarts={self.max_restarts}",
            f"--job-name=bench{os.getpid()}x{int(time.time())}",
            f"--log-dir={self.log_dir}",
            os.path.join(HERE, "worker.py"),
            os.path.join(self.run_dir, "spec.json"),
        ]
        self._out = open(self.launcher_log, "ab")
        self.proc = subprocess.Popen(
            cmd, cwd=ROOT, env=child_env(self.run_dir, self.sock_dir),
            stdout=self._out, stderr=subprocess.STDOUT,
            start_new_session=True,
        )

    def wait(self, deadline_s: float) -> Optional[int]:
        try:
            return self.proc.wait(timeout=deadline_s)
        except subprocess.TimeoutExpired:
            return None

    def stop(self) -> None:
        """Leave no process behind, whatever state the run is in."""
        if self.proc is not None:
            for sig in (signal.SIGTERM, signal.SIGKILL):
                try:
                    os.killpg(self.proc.pid, sig)
                except (ProcessLookupError, PermissionError):
                    break
                try:
                    self.proc.wait(timeout=20)
                except subprocess.TimeoutExpired:
                    continue
                # the group may outlive its leader for a moment
                time.sleep(0.2)
            self.proc.wait()
        if self._out is not None:
            self._out.close()
        if self.sock_dir:
            shutil.rmtree(self.sock_dir, ignore_errors=True)


def failure_report(run_dir: str) -> None:
    logs = sorted(glob.glob(os.path.join(run_dir, "logs", "worker_*_r*.log")))
    for path in logs + [os.path.join(run_dir, "launcher.log")]:
        lines = read_text(path).splitlines()[-TAIL_LINES:]
        note({"log": os.path.basename(path),
              "tail": [ln[:400] for ln in lines]})


def reduce_trace(files: List[str], run_dir: str) -> Optional[Dict]:
    """The device trace, reduced by ``xplane.py`` in a process of its
    own that is held to the CPU (the chip is free again by now)."""
    if not files:
        return None
    out = os.path.join(run_dir, "trace_reduced.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    subprocess.run(
        [sys.executable, os.path.join(HERE, "xplane.py"), files[0], out],
        cwd=ROOT, env=env, timeout=600,
    )
    reduced = maybe_json(out)
    if reduced and "refused" in reduced:
        raise Refused(
            "the device trace holds no stretch of whole steps to measure: "
            + str(reduced["refused"])
        )
    return reduced


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             device_spec: str = "tpu", expect_platform: str = "tpu",
             data_dir: str = HERE) -> Dict:
    """Run the cell and return the result object. ``device_spec``,
    ``expect_platform`` and ``data_dir`` (where ``cells/`` and
    ``configs/`` are looked up) are for the CPU rehearsal in ``tests/``
    alone: the command line always asks for the TPU and reads this
    directory."""
    t0 = time.monotonic()
    cell = load_cell(workload, data_dir)
    config = load_config(cell["config"], data_dir)
    hook = load_hook(cell["config"], config, data_dir)
    counted = ask_hook(
        hook, cell["config"], config["model"], int(cell["batch"]),
        int(cell["seq"]),
    )
    metrics_mods = load_layer_metrics()
    if not os.path.isdir(os.path.join(ROOT, "dlrover_tpu")):
        raise Refused("no program here: dlrover_tpu/ is not in the checkout")
    chips = int(cell.get("chips", 1))
    kill = bool(cell["kill"])

    run_dir = os.path.join(ROOT, ".benchmark_run", workload)
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    with open(os.path.join(run_dir, "spec.json"), "w") as f:
        json.dump({
            "cell": cell, "config": config, "seed": seed,
            "seconds": seconds, "trace": bool(trace), "chips": chips,
            "expect_platform": expect_platform,
        }, f)

    chain = Chain(run_dir, device_spec, max_restarts=1 if kill else 0)
    try:
        chain.start()
        rc = chain.wait(RUN_DEADLINE_S)
    finally:
        chain.stop()
        shutil.rmtree(os.path.join(run_dir, "ckpt"), ignore_errors=True)
    t_chain_end = time.monotonic()

    reports = {
        i: maybe_json(os.path.join(run_dir, f"worker_r{i}.json")) or {}
        for i in (0, 1)
    }
    steps = {
        i: arith.read_step_records(
            os.path.join(run_dir, f"steps_r{i}.jsonl")
        )
        for i in (0, 1)
    }
    window = maybe_json(os.path.join(run_dir, "window_r0.json"))
    launcher_text = read_text(chain.launcher_log)
    if rc != 0 or window is None:
        failure_report(run_dir)
        raise Refused(f"launcher rc={rc}, window "
                      f"{'written' if window else 'missing'}")
    dev = {k: reports[0].get(k) for k in ("platform", "kind", "count")}
    if dev["platform"] != expect_platform or dev["count"] != chips:
        raise Refused(f"worker ran on {dev}")

    tokens_per_step = int(cell["batch"]) * int(cell["seq"])
    in_window = arith.window_records(
        steps[0], window["t_open"], window["t_close"]
    )
    summary = arith.window_summary(in_window, tokens_per_step)
    trace_reduced = None
    if trace and window.get("trace"):
        trace_reduced = reduce_trace(window["trace"]["files"], run_dir)
        shutil.rmtree(os.path.join(run_dir, "trace"), ignore_errors=True)

    # -- what the run has to show to be correct ------------------------
    problems: List[str] = []
    failed = sum(1 for r in in_window[1:] if not math.isfinite(r["loss"]))
    if failed:
        problems.append(f"{failed} non-finite losses in the window")
    all_losses = [r["loss"] for i in (0, 1) for r in steps[i]]
    if not all(math.isfinite(v) for v in all_losses):
        problems.append("a non-finite loss outside the window")
    last = (steps[1] or steps[0])[-1]
    first_loss = steps[0][0]["loss"]
    if steps[0][0]["step"] != 1 or not last["loss"] < first_loss:
        problems.append(
            f"loss did not fall: step {steps[0][0]['step']} {first_loss} "
            f"-> step {last['step']} {last['loss']}"
        )
    ref = reports[0].get("checks", {}).get("reference")
    if not ref or not ref["abs_diff"] <= ref["tolerance"]:
        problems.append(f"reference check failed: {ref}")
    compiles_in_window = window["compiles_close"] - window["compiles_open"]
    if compiles_in_window:
        problems.append(f"{compiles_in_window} compiles inside the window")

    recovery = None
    if kill:
        recovery = check_recovery(
            chain.log_dir, launcher_text, reports, steps,
            REPLAY_ATOL.get(expect_platform, 0.0), problems,
        )
        if recovery is None:
            failed += 1
    attempted = summary["steps"] + (1 if kill else 0)

    peak = None
    mfu = None
    if dev["platform"] == "tpu":
        peak = peaks.peaks(dev["kind"])
        mfu = flops.mfu_pct(
            summary["tokens_per_s"], counted["train_flops_per_token"],
            peak["bf16_flops"], chips,
        )

    spans = spans_without_profiler_steps(
        window.get("spans", []),
        (window.get("trace") or {}).get("profiler_calls", []),
    )
    run = SimpleNamespace(
        cell=cell, config=config, seconds=seconds, reports=reports,
        steps=steps, window=window, in_window=in_window, summary=summary,
        spans=spans, recovery=recovery, trace=trace_reduced, peak=peak,
        hook=hook,
    )

    end_to_end = {
        "tokens_per_s": (summary["tokens_per_s"], "tokens/s"),
        "step_p95_ms": (summary["step_p95_ms"], "ms"),
        "setup_s": (window["t_open"] - t0, "s"),
    }

    note({
        "window": {k: v for k, v in summary.items() if k != "step_ms"},
        "step_p95_samples": summary["samples"],
        "mfu_pct": mfu,
        "flops_per_token": counted["train_flops_per_token"],
        "n_params": counted["params"],
        "active_params": counted["active_params"],
        "state_bytes": reports[0].get("state_bytes"),
        "reference_check": ref,
        "compiles_in_window": compiles_in_window,
        "saves_begun_in_window": arith.saves_begun(in_window),
        "memory_stats": window.get("memory_stats"),
        "first_incarnation": {
            "build_s": reports[0].get("build_seconds"),
            "worker_start_to_up_s": reports[0]["t_up"]
            - reports[0]["t_start"],
            "parent_to_worker_start_s": reports[0].get("t_start", t0) - t0,
            "compile_totals_at_window_close": window.get("totals"),
        },
        "recovery": recovery,
        "teardown_s": t_chain_end - (
            (steps[1] or steps[0])[-1]["t"]),
        "problems": problems,
    })

    layer = read_layer_metrics(metrics_mods, run)
    if trace:
        note({"per_layer": {k: v[0] for k, v in layer.items()}})
    else:
        note({"end_to_end": {k: v[0] for k, v in end_to_end.items()}})

    chosen = layer if trace else end_to_end
    device = dict(dev)
    device["memory_peak_bytes"] = max(
        int(window.get("memory_peak_bytes") or 0),
        int(reports[1].get("memory_peak_bytes") or 0),
    )
    result = {
        "correct": not problems,
        "attempted": attempted,
        "failed": failed,
        "metrics": {
            k: {"value": v, "unit": u} for k, (v, u) in chosen.items()
        },
        "device": device,
    }
    if trace:
        if not trace_reduced or not trace_reduced.get("devices"):
            failure_report(run_dir)
            raise Refused("traced run left no device trace to read")
        # both from the device trace's whole steps (xplane.step_stretch):
        # no host clock in either, so busy_s cannot pass window_s
        device["busy_s"] = trace_reduced["busy_s"]
        device["window_s"] = trace_reduced["window_s"]
        d0 = trace_reduced["devices"][0]
        note(stretch_beside_host_clock(d0, window["trace"], in_window))
        result["breakdown"] = {
            "device_ops": [
                [r["name"], r["self_s"]] for r in d0["ops"][:10]
            ],
            # each gap named by the device operation that ended it; what
            # the host was doing in it needs host spans on the profiler's
            # clock, which the program does not write yet (PERF.md, 7)
            "idle_gaps": [
                [g["before"], g["seconds"]] for g in d0["gaps"][:10]
            ],
        }
    return result


def read_layer_metrics(mods: Dict[str, object], run) -> Dict[str, tuple]:
    """``{metric: (value, unit)}`` of every reader whose ``CELLS`` rule takes
    the cell and that finds something to read. A reader may declare a
    ``CEILING`` (a share of a roofline or a peak: 100.0): a value above it
    is never printed, clamped or left out, it is ``Refused`` with what the
    reader printed on its earlier line (the seconds it found, the least
    seconds it set against them) and the step's work the family module
    counted, because that module then counts work the program does not
    run, or the reader misses part of the kernels' time."""
    layer = {}
    for name, mod in mods.items():
        said = io.StringIO()
        try:
            if not mod.CELLS(run.cell):
                continue
            with contextlib.redirect_stdout(said):
                value = mod.read(run)
        except Exception as e:
            note({"per_layer_metric": name, "error": repr(e)})
            continue
        finally:
            print(said.getvalue(), end="", flush=True)
        if value is None or not math.isfinite(value):
            continue
        ceiling = getattr(mod, "CEILING", None)
        if ceiling is not None and value > ceiling:
            raise Refused(
                f"per-layer metric {name} reads {value} {mod.UNIT}, above "
                f"its ceiling of {ceiling}: more work is counted than the "
                f"kernels found could have done in their seconds",
                {"metric": name, "value": value, "ceiling": ceiling,
                 "reader_printed": json_lines(said.getvalue()),
                 "step_work_counted": run.hook.step_work(
                     run.config["model"], run.cell["batch"],
                     run.cell["seq"]),
                 "steps_traced": (run.trace or {}).get("steps")},
            )
        layer[name] = (float(value), mod.UNIT)
    return layer


def stretch_beside_host_clock(d0: Dict, host: Dict,
                              in_window: List[Dict]) -> Dict:
    """The traced stretch of the first device beside what the whole trace
    file sums to and beside the host's clock around the same capture
    (``window_r0.json``'s ``trace``): three clocks to compare by eye. Only
    the first feeds a metric; the host-span readers use the third."""
    hooks = [
        r["t"] for r in in_window
        if host["step_begin"] <= r["step"] <= host["step_end"]
    ]
    periods = [b - a for a, b in zip(hooks, hooks[1:])]
    return {
        "traced_stretch": {
            k: d0[k] for k in (
                "steps", "window_s", "busy_s", "idle_s", "step_programs",
                "step_executions", "other_executions",
            )
        },
        "whole_trace_file": d0["whole_file"],
        "host_clock": {
            "t_end_less_t_begin_s": host["t_end"] - host["t_begin"],
            "hooks": host["step_end"] - host["step_begin"],
            # the same steps' period as the hooks saw it (the whole
            # window's loop.step_p50_ms drifts from it by some 0.3 %)
            "step_p50_ms_over_the_hooks": 1e3 * arith.percentile(
                periods, 50) if periods else None,
            "profiler_calls_s": [b - a for a, b in host["profiler_calls"]],
        },
    }


def line_breaches(result: Dict, traced: bool) -> List[str]:
    """Where ``result`` departs from what the driver reads in a run's
    last line: ``correct``, ``attempted``, ``failed``, ``metrics`` (each a
    value and a unit), ``device`` (``platform``, ``kind``, ``count``,
    ``memory_peak_bytes`` and, traced, ``0 < busy_s <= window_s``) and,
    where it is there, ``breakdown`` (two lists of at most 10 pairs)."""
    number = is_number

    def count(v):
        return isinstance(v, int) and not isinstance(v, bool) and v >= 0

    bad = []
    if not isinstance(result.get("correct"), bool):
        bad.append(f"correct is {result.get('correct')!r}, not a boolean")
    for key in ("attempted", "failed"):
        if not count(result.get(key)):
            bad.append(f"{key} is {result.get(key)!r}, not a count")
    metrics = result.get("metrics")
    if not isinstance(metrics, dict) or not metrics:
        bad.append(f"metrics is {metrics!r}, not a non-empty object")
    else:
        for name, m in metrics.items():
            if not (
                isinstance(m, dict) and number(m.get("value"))
                and isinstance(m.get("unit"), str) and m["unit"]
            ):
                bad.append(f"metric {name} is {m!r}, not a value and a unit")
    device = result.get("device")
    if not isinstance(device, dict):
        return bad + [f"device is {device!r}, not an object"]
    for key in ("platform", "kind"):
        if not (isinstance(device.get(key), str) and device[key]):
            bad.append(f"device.{key} is {device.get(key)!r}")
    for key in ("count", "memory_peak_bytes"):
        if not (count(device.get(key)) and device[key] > 0):
            bad.append(f"device.{key} is {device.get(key)!r}, not above 0")
    if traced:
        busy, win = device.get("busy_s"), device.get("window_s")
        if not (number(busy) and number(win) and 0 < busy <= win):
            bad.append(
                f"device.busy_s {busy!r} is not above 0 and at most "
                f"device.window_s {win!r}"
            )
    for key in ("device_ops", "idle_gaps"):
        rows = (result.get("breakdown") or {}).get(key, [])
        if not (isinstance(rows, list) and len(rows) <= 10 and all(
            isinstance(r, list) and len(r) == 2
            and isinstance(r[0], str) and number(r[1]) for r in rows
        )):
            bad.append(f"breakdown.{key} is not at most 10 [name, seconds]")
    return bad


def spans_without_profiler_steps(spans: List[List], calls: List[List]):
    """The window's host spans less the steps in whose hooks the profiler
    was started and stopped (``calls``: [begin, end] seconds on the spans'
    clock): such a ``step`` span holds the profiler's own seconds, so it
    goes, and every span inside it with it."""
    def overlaps(s):
        return any(s[1] < 1e9 * b and s[1] + s[2] > 1e9 * a for a, b in calls)

    hit = [s for s in spans if s[0] == "step" and overlaps(s)]

    def inside(s):
        return any(
            h[4] == s[4] and h[1] <= s[1] and s[1] + s[2] <= h[1] + h[2]
            for h in hit
        )

    return [s for s in spans if not overlaps(s) and not inside(s)]


def check_recovery(log_dir, launcher_text, reports, steps,
                   replay_atol, problems) -> Optional[Dict]:
    """The smoke's checks on a kill and what came back, and the times of
    the recovery. None where the run did not come back from agent shm."""
    deaths = re.findall(
        r"worker failure: local_rank=0 exitcode=(-?\d+)", launcher_text
    )
    restored = re.findall(
        r"restored step (\d+) from memory",
        read_text(os.path.join(log_dir, "worker_0_0_r1.log")),
    )
    w1 = reports[1]
    if deaths != ["137"]:
        problems.append(f"deaths {deaths}, want one with rc 137")
        return None
    if not restored or w1.get("stage") != "done" or not steps[1]:
        problems.append(
            f"no recovery from memory: restored={restored} "
            f"second incarnation at {w1.get('stage')!r}"
        )
        return None
    k = int(restored[0])
    l0 = {r["step"]: r["loss"] for r in steps[0]}
    l1 = {r["step"]: r["loss"] for r in steps[1]}
    died = steps[0][-1]
    back = arith.time_of_step(steps[1], died["step"])
    if back is None or min(l1) != k + 1:
        problems.append(
            f"restored step {k}, resumed at {min(l1)}, died at "
            f"{died['step']}, replay ended at {max(l1)}"
        )
        return None
    staged = {
        r["step"]: r["state_digest"]
        for r in steps[0] if r.get("state_digest")
    }
    got = w1["restore"]["digest"]
    if not got or staged.get(k) != got:
        problems.append(
            f"restored state is not what was staged at step {k}: "
            f"staged {staged.get(k)}, restored {got}"
        )
    replayed = sorted(s for s in l1 if s in l0)
    worst = max(abs(l0[s] - l1[s]) for s in replayed)
    if l0[k + 1] != l1[k + 1] or worst > replay_atol:
        problems.append(
            f"replayed losses differ by {worst} (bound {replay_atol}; "
            f"first replayed step {l0[k + 1]} vs {l1[k + 1]})"
        )
    return {
        "recover_s": back - died["t"],
        "died_at_step": died["step"], "restored_step": k,
        "replayed_steps": len(replayed), "replay_max_abs_diff": worst,
        "replay_tolerance_abs": replay_atol,
        "detect_respawn_s": w1["t_up"] - died["t"],
        "death_to_worker_start_s": w1["t_start"] - died["t"],
        "build_restart_s": w1["build_seconds"],
        "restore_s": w1["restore"]["seconds"],
        "compile_restart": w1.get("totals"),
        "replay_s": back - w1["t_built"],
        "staged_digest": staged.get(k), "restored_digest": got,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    try:
        result = run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace)
        )
    except Refused as e:
        note({"refused": str(e), **e.detail})
        return 3
    if result["device"].get("platform") != "tpu":
        note({"refused": f"not a TPU run: {result['device']}"})
        return 3
    breaches = line_breaches(result, bool(args.trace))
    if breaches:
        note({"refused": "the result line would not meet its contract",
              "breaches": breaches, "line": result})
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
