"""The training script the agent runs for one benchmark cell: the only file
of the benchmark that touches JAX and the chip.

A copy of ``chip_smoke.py``'s worker (step hook, ``jax.monitoring`` compile
listeners, timed ``load_checkpoint``, kill armed at a clean point, state
digests) with a timed window added. It knows no cell, configuration or
metric by name: what to build and run comes from ``spec.json`` in the run
directory, which ``run.py`` writes from ``cells/<cell>.json`` and
``configs/<config>.json``.

Life of the first incarnation (``DLROVER_TPU_RESTART_COUNT`` 0):

    up -> build -> weights from the seed -> reference check -> train():
    warm-up (until every program the window uses has run once) ->
    window of ``seconds`` opened and closed at a step hook ->
      no kill: stop (the hook raises ``WindowClosed`` out of ``train()``)
      kill:    train on until a save that began after the window has
               committed, then die hard at the next step boundary

and of the second (after a kill): up -> build (restores from agent shm) ->
replay until the step at which the first died -> stop.

All times are ``time.monotonic()``: CLOCK_MONOTONIC is one clock for every
process of the machine, so the parent subtracts them across processes.
"""

from __future__ import annotations

import dataclasses
import glob
import importlib.util
import json
import os
import sys
import time
import zlib
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))


class WindowClosed(SystemExit):
    """Raised by the step hook to leave ``ElasticTrainer.train`` at a step
    boundary once the benchmark has what it came for. A ``SystemExit`` so
    that the trainer does not take it for a crash and dump a flight
    bundle; caught around ``train()``, it ends nothing."""


def write_json(path: str, obj) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)


def _digest(tree) -> str:
    """crc32 over every leaf's bytes, in tree order."""
    import jax
    import numpy as np

    crc = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        crc = zlib.crc32(np.asarray(leaf).reshape(-1).view(np.uint8), crc)
    return f"{crc:08x}"


def state_digest(train_state) -> Dict[str, str]:
    return {
        "params": _digest(train_state.params),
        "opt_state": _digest(train_state.opt_state),
    }


def load_reference(name: str):
    path = os.path.join(HERE, "references", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def memory_peak(devices) -> int:
    """Peak bytes held on the fullest device. The TPU backend counts the
    arrays a process holds (``peak_bytes_in_use``) apart from what it
    reserves for the temporaries of compiled programs
    (``peak_bytes_reserved``: ``bytes_reservable_limit`` is the limit
    less the arrays in use); a deployment's chip holds both."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(
            peak,
            int(stats.get("peak_bytes_in_use", 0))
            + int(stats.get("peak_bytes_reserved", 0)),
        )
    return peak


def main(spec_path: str) -> int:
    t_start = time.monotonic()
    with open(spec_path) as f:
        spec = json.load(f)
    cell, config = spec["cell"], spec["config"]
    out_dir = os.path.dirname(os.path.abspath(spec_path))
    restart = int(os.getenv("DLROVER_TPU_RESTART_COUNT", "0"))
    tag = f"r{restart}"
    report_path = os.path.join(out_dir, f"worker_{tag}.json")
    report = {"stage": "started", "restart": restart, "t_start": t_start,
              "pid": os.getpid()}
    write_json(report_path, report)

    import jax
    import jax.monitoring

    from dlrover_tpu.trainer.elastic.distributed import init_elastic

    # the agent's device spec: asking for the chip and coming up on
    # anything else raises here, before anything is built
    init_elastic()
    devices = jax.devices()
    report.update(
        stage="up", t_up=time.monotonic(),
        platform=devices[0].platform, kind=devices[0].device_kind,
        count=len(devices),
    )
    if report["platform"] != spec["expect_platform"]:
        raise RuntimeError(
            f"worker came up on {report['platform']!r}, the cell needs "
            f"{spec['expect_platform']!r}"
        )
    if len(devices) != spec["chips"]:
        raise RuntimeError(
            f"worker sees {len(devices)} devices, the cell needs "
            f"{spec['chips']}"
        )
    write_json(report_path, report)

    totals = {"backend_compile_s": 0.0, "cache_retrieval_s": 0.0,
              "compiles": 0, "cache_hits": 0, "cache_misses": 0}

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            totals["backend_compile_s"] += secs
            totals["compiles"] += 1
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            totals["cache_retrieval_s"] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            totals["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            totals["cache_misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    from dlrover_tpu.accel.strategy import Strategy
    from dlrover_tpu.ckpt.checkpointer import FlashCheckpointer
    from dlrover_tpu.common import faults
    from dlrover_tpu.models.config import TransformerConfig
    from dlrover_tpu.obs.trace import get_tracer
    from dlrover_tpu.parallel.mesh import MeshConfig
    from dlrover_tpu.trainer.elastic.trainer import (
        ElasticTrainer,
        TrainerConfig,
        build_optimizer,
    )

    sys.path.insert(0, HERE)
    from arith import read_step_records
    from corpus import Corpus

    model_cfg = TransformerConfig(**config["model"])
    batch, seq = int(cell["batch"]), int(cell["seq"])
    kill = bool(cell.get("kill"))
    interval = int(cell["save_memory_interval"])
    seconds = float(spec["seconds"])
    trace_on = bool(spec["trace"]) and restart == 0
    trace_cfg = cell.get("trace", {})
    warm = cell["warmup"]
    max_steps = int(cell["max_steps"])

    restore = {"seconds": None, "step": None, "digest": None}
    load = FlashCheckpointer.load_checkpoint

    def timed_load(self, target):
        t0 = time.perf_counter()
        step, state = load(self, target)
        jax.block_until_ready(state)
        if state is not None:
            restore.update(
                seconds=time.perf_counter() - t0, step=int(step),
                digest=state_digest(state["train"]),
            )
        return step, state

    FlashCheckpointer.load_checkpoint = timed_load

    # what the first incarnation left: the step at which it died
    died_at = None
    if restart > 0:
        prev = read_step_records(os.path.join(out_dir, "steps_r0.jsonl"))
        died_at = prev[-1]["step"] if prev else None

    steps_file = open(os.path.join(out_dir, f"steps_{tag}.jsonl"), "a")
    pending: List[tuple] = []
    holder: Dict = {"phase": "warmup", "digest_step": None, "live": False}
    window: Dict = {}
    tracer = get_tracer()

    def flush():
        for (step, t, loss, commits, staging, block_s, chunks, safe,
             wait_s, compiles, digest) in pending:
            steps_file.write(json.dumps({
                "step": step, "t": t, "loss": float(loss),
                "commits": commits, "staging": staging,
                "stage_block_s": block_s, "stage_chunks": chunks,
                "safe_steps": safe, "prefetch_wait_s": wait_s,
                "compiles": compiles, "state_digest": digest,
            }) + "\n")
        pending.clear()
        steps_file.flush()

    def close_window(now, step, trainer):
        holder["phase"] = "post"
        if holder.get("tracing"):
            stop_trace(now, step)
        t0_ns, t1_ns = window["open_ns"], time.monotonic_ns()
        spans = [
            [name, start, dur, depth, tid]
            for name, tid, start, dur, depth, _a, _s in tracer.drain(0)[0]
            if t0_ns <= start <= t1_ns
        ]
        window.update(
            t_close=now, step_close=step,
            compiles_close=totals["compiles"],
            pipeline=dataclasses.asdict(trainer.pipeline_stats),
            spans=spans, spans_dropped=tracer.dropped,
            memory_peak_bytes=memory_peak(devices),
            memory_stats=devices[0].memory_stats(),
            totals=dict(totals),
        )
        flush()
        write_json(os.path.join(out_dir, f"window_{tag}.json"), window)

    def start_trace():
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(
            os.path.join(out_dir, "trace"), profiler_options=opts
        )
        holder["tracing"] = True

    def stop_trace(now, step):
        jax.profiler.stop_trace()
        holder["tracing"] = False
        window["trace"] = {
            "t_begin": holder["trace_t0"], "t_end": now,
            # the two steps whose hooks started and stopped the profiler
            # carry its seconds: host-span readers leave them out
            "profiler_calls": [
                [holder["trace_call_t"], holder["trace_t0"]],
                [now, time.monotonic()],
            ],
            "step_begin": holder["trace_step0"], "step_end": step,
            "files": glob.glob(os.path.join(
                out_dir, "trace", "plugins", "profile", "*", "*.xplane.pb"
            )),
        }

    def on_step(step, metrics):
        if "loss" not in metrics:
            return  # eval metrics ride the same hook
        now = time.monotonic()
        trainer = holder["trainer"]
        stats = trainer.pipeline_stats
        commits = int(stats.stage_commits)
        staging = stats.stage_backlog_bytes > 0
        phase = holder["phase"]
        digest = None
        chunks = int(stats.stage_chunks)
        if phase == "post" and kill:
            if holder["digest_step"] is not None and not holder["live"]:
                # did the save that fell due after that hook begin? The
                # program skips one while the agent's saver still
                # persists the previous save
                if staging or chunks > holder["digest_chunks"] or (
                    commits > holder["digest_commits"]
                ):
                    holder["live"] = True
                else:
                    holder["digest_step"] = None
            if (
                holder["digest_step"] is None and not staging
                and step % interval == 0
            ):
                # what the save that begins after this hook stages: the
                # state of this step, to hold the restored state to (a
                # fetch of the whole state, so outside the window only)
                digest = state_digest(trainer.state)
                holder.update(
                    digest_step=step, digest_commits=commits,
                    digest_chunks=chunks, live=False,
                )
        pending.append((
            int(step), now, metrics["loss"], commits, staging,
            float(stats.stage_block_s), chunks,
            int(stats.safe_steps), float(stats.prefetch_wait_s),
            totals["compiles"], digest,
        ))
        if phase != "window":
            flush()

        if restart > 0:
            # second incarnation: replay to where the first died
            if died_at is not None and step >= died_at:
                raise WindowClosed()
            return
        if step >= max_steps:
            raise RuntimeError(f"cell ran past max_steps={max_steps}")

        if phase == "warmup":
            if (
                step >= holder["first_step"] + int(warm["min_steps"])
                and commits >= int(warm.get("commits", 0))
                and not staging
            ):
                holder["phase"] = "window"
                window.update(
                    t_open=now, step_open=int(step),
                    open_ns=time.monotonic_ns(),
                    compiles_open=totals["compiles"],
                    pipeline_open=dataclasses.asdict(stats),
                )
        elif phase == "window":
            if trace_on and not holder.get("traced"):
                if step >= window["step_open"] + int(
                    trace_cfg.get("after_steps", 5)
                ) and (trace_cfg.get("start") != "staging" or staging):
                    holder["traced"] = True
                    holder["trace_call_t"] = now
                    start_trace()
                    holder["trace_t0"] = time.monotonic()
                    holder["trace_step0"] = int(step)
            elif holder.get("tracing") and step >= holder[
                "trace_step0"
            ] + int(trace_cfg.get("steps", 20)):
                stop_trace(now, int(step))
            if now - window["t_open"] >= seconds:
                close_window(now, int(step), trainer)
                if not kill:
                    raise WindowClosed()
        elif phase == "post":
            # the kill, once: after a save that began after the window
            # has committed, none is staging and none begins after
            # this hook (a kill mid-staging leaves shm invalid and the
            # restore would rightly come from storage instead)
            if (
                holder["live"]
                and commits > holder["digest_commits"]
                and not staging
                and step % interval != 0
            ):
                holder["phase"] = "armed"
                faults.configure("node.preempt:kill:@1")

    strat = config["strategy"]
    strategy = Strategy(
        mesh=MeshConfig(**strat.get("mesh", {})),
        **{k: v for k, v in strat.items() if k != "mesh"},
    )
    opt = dict(config["optimizer"])
    t0 = time.perf_counter()
    trainer = ElasticTrainer(
        model_cfg=model_cfg,
        tx=build_optimizer(opt.pop("name"), **opt),
        dataset=Corpus(
            rows=batch * max_steps, seq=seq, vocab=model_cfg.vocab_size,
            seed=int(spec["seed"]),
        ),
        trainer_cfg=TrainerConfig(
            batch_size=batch, seq_len=seq,
            ckpt_dir=os.path.join(out_dir, "ckpt"),
            save_memory_interval=interval,
            **cell.get("trainer", {}),
        ),
        strategy=strategy,
        metrics_hook=on_step,
    )
    holder["trainer"] = trainer
    build_s = time.perf_counter() - t0
    # The trainer's span heartbeat (a thread that rewrites the
    # runtime-metrics file every 5 s for hang attribution) and the train
    # loop's own report every ``log_interval`` steps write through ONE
    # temporary name per process (``agent/monitor.atomic_write_json``):
    # when the two meet, the loop's ``os.replace`` finds its file gone and
    # the worker dies of ``FileNotFoundError`` (1 of 23 runs of this
    # benchmark, my chip runs, PR 24). A benchmark run must not fail on a
    # race that has nothing to do with what it measures, and the program
    # is not this PR's to repair (PERF.md, Open questions): the heartbeat
    # thread is stopped; the loop's reports to the agent stay on.
    if getattr(trainer, "_span_heartbeat", None) is not None:
        trainer._span_heartbeat.stop()
    holder["first_step"] = int(trainer.global_step)

    checks: Dict = {}
    if restore["step"] is None:
        # weights from the seed (the trainer's own are from a fixed key)
        key = jax.random.PRNGKey(int(spec["seed"]) % (2**31 - 1))
        trainer.state = None
        trainer.state = trainer.accel.init_fn(key)
        # the plain reference against the program's own forward pass,
        # on the initial parameters and the corpus's first rows
        from dlrover_tpu.models.transformer import loss_fn

        rows = int(config["reference_check"]["rows"])
        data = trainer.dataloader.dataset.data[:rows]
        x, y = data[:, :-1], data[:, 1:]
        ref = load_reference(config["reference"])
        cfg, mesh = trainer.cfg, trainer.mesh
        got = float(jax.jit(
            lambda p, a, b: loss_fn(p, a, b, cfg, mesh)
        )(trainer.state.params, x, y))
        want = float(jax.jit(ref.loss)(trainer.state.params, x, y))
        checks["reference"] = {
            "program_loss": got, "reference_loss": want,
            "abs_diff": abs(got - want),
            "tolerance": float(config["reference_check"]["tolerance"]),
            "rows": rows,
        }
    state_bytes = sum(
        int(leaf.nbytes) for leaf in jax.tree_util.tree_leaves(trainer.state)
    )
    report.update(
        stage="built", t_built=time.monotonic(), build_seconds=build_s,
        restore=restore, checks=checks, state_bytes=state_bytes,
        strategy=trainer.accel.strategy.describe(),
        totals_at_built=dict(totals),
    )
    write_json(report_path, report)

    try:
        trainer.train(num_steps=max_steps)
        stopped = "max_steps"
    except WindowClosed:
        stopped = "window_closed"
    if holder.get("tracing"):
        jax.profiler.stop_trace()
    flush()
    report.update(
        stage="done", t_done=time.monotonic(), stopped=stopped,
        totals=dict(totals), memory_peak_bytes=memory_peak(devices),
        pipeline=dataclasses.asdict(trainer.pipeline_stats),
    )
    write_json(report_path, report)
    trainer.close()
    steps_file.close()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
