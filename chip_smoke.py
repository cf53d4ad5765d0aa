#!/usr/bin/env python3
"""Proof that the elastic training path starts and recovers on the chip.

One run drives the system's main path through its normal entry points:

    launcher (``python -m dlrover_tpu.trainer.run --network-check``)
      -> node check on the chip (8192^2 bf16 matmuls)
      -> ``ElasticTrainer`` on GPT-2 124M, seq 1024, fp32 AdamW state
      -> flash save to agent shm
      -> hard death of the worker process (rc 137), once
      -> the agent restarts the worker, which restores from shm,
         replays the lost steps and trains to the end.

and checks what came out: finite losses, replayed losses equal to the
first incarnation's, a falling loss, launcher rc 0, the worker on a
TPU, the Pallas attention kernel inside the compiled step, and the
second incarnation's compiles served from the persistent cache.

``python chip_smoke.py`` needs one chip. ``--chips 4`` runs the sharded
path (fsdp=4 over one worker's four chips) and the one-device run it is
compared with, and no other phase.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
everything else worth reading is on earlier lines, one JSON object per
phase. The exit code is 0 only when every phase passed on a TPU.

This process never imports JAX: a parent that has touched JAX holds the
chip, and the worker that needs it then fails or hangs. What the device
is comes back from the worker through a file.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import re
import shutil
import subprocess
import sys
import tempfile
import time
import zlib
from dataclasses import asdict, dataclass, field
from typing import Dict, List, Optional

# Why the replay is not bitwise on the chip (my chip runs, PR 22, on a
# TPU v5 lite; see PERF.md, Findings): the first incarnation runs the
# steps after a save on the non-donating step (staging reads the
# state), the second replays them on the donating twin. Each program
# alone is deterministic (same state and batch twice: 0 of 124.4M
# params differ), and from the same state and batch both give the same
# loss; but the in-place update rounds Adam's moments differently in
# the last bit (68.6M of 248.8M elements, by at most 2.3e-9), and the
# bf16 forward amplifies that over the following steps. Over the steps
# this script replays, every one-chip run showed a largest difference
# of 7.0e-5; the bound is about fourteen times that. What the restore
# itself has to prove is held exactly: the restored params and Adam
# moments checksum to what was staged, and the first replayed loss is
# bitwise the first incarnation's.
REPLAY_ATOL_TPU = 1e-3

REPO = os.path.dirname(os.path.abspath(__file__))
RUN_DIR = os.path.join(REPO, ".chip_smoke_run")
TAIL_LINES = 40


@dataclass
class SmokeSpec:
    """What one chain runs: a model the repo supports (by the name of its
    constructor in ``dlrover_tpu.models.config`` — resolving it needs
    JAX, so only the worker does), sizes, and the device it must get."""

    model: str = "gpt2_small"
    model_overrides: Dict = field(default_factory=dict)
    batch: int = 8
    seq: int = 1024
    steps: int = 60
    save_interval: int = 20
    lr: float = 3e-4
    seed: int = 0
    device_spec: str = "tpu"
    expect_platform: str = "tpu"
    expect_devices: int = 1
    # MeshConfig fields of a fixed Strategy; empty = the strategy
    # search, as examples/train_gpt2.py runs it
    mesh: Dict = field(default_factory=dict)
    # losses of the replayed steps against the first incarnation's. The
    # first replayed step is always held to bitwise equality: same
    # restored state, same batch. This bound is for the later ones,
    # which the two incarnations run through two XLA programs (see
    # REPLAY_ATOL_TPU); 0.0 where both agree bitwise, as on the CPU
    replay_atol: float = 0.0
    # the agent's restart budget (0 makes the kill final)
    max_restarts: int = 1
    timeout_s: float = 1000.0


# ----------------------------------------------------------------------
# worker side: the only code in this file that imports JAX
# ----------------------------------------------------------------------
class _Corpus:
    """Seeded stand-in corpus with something to learn: token ids drawn
    from a Zipf-like distribution, so the loss falls within a few steps
    (uniform random tokens would pin it at ln(vocab))."""

    def __init__(self, n: int, seq: int, vocab: int, seed: int):
        import numpy as np

        rng = np.random.default_rng(seed)
        p = 1.0 / np.arange(1, vocab + 1)
        p /= p.sum()
        self.data = rng.choice(vocab, size=(n, seq + 1), p=p).astype(
            np.int32
        )

    def __len__(self):
        return len(self.data)

    def __getitem__(self, i):
        row = self.data[i]
        return {"x": row[:-1], "y": row[1:]}


def _write_json(path: str, obj) -> None:
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


def _state_digest(train_state) -> Dict[str, str]:
    return {
        "params": _digest(train_state.params),
        "opt_state": _digest(train_state.opt_state),
    }


def _spread(arr) -> Dict:
    """Where one array's bytes live: device ids and bytes per shard."""
    shards = arr.addressable_shards
    return {
        "devices": sorted({s.device.id for s in shards}),
        "shard_bytes": [int(s.data.nbytes) for s in shards],
        "total_bytes": int(arr.nbytes),
    }


def worker_main(spec_path: str, reference: bool = False) -> int:
    """Training script the agent runs (``reference``: the plain
    one-device run the sharded chain is compared with, started directly
    once the chain has released the chips)."""
    with open(spec_path) as f:
        spec = SmokeSpec(**json.load(f))
    out_dir = os.path.dirname(os.path.abspath(spec_path))
    restart = int(os.getenv("DLROVER_TPU_RESTART_COUNT", "0"))
    tag = "ref" if reference else f"r{restart}"

    import jax
    import jax.monitoring

    from dlrover_tpu.trainer.elastic.distributed import init_elastic

    # the agent's device spec: asking for the chip and coming up on
    # anything else raises here, before anything is built
    init_elastic()
    devices = jax.devices()
    report = {
        "stage": "up",
        "pid": os.getpid(),
        "restart": restart,
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if report["platform"] != spec.expect_platform:
        raise RuntimeError(
            f"worker came up on {report['platform']!r}, the smoke needs "
            f"{spec.expect_platform!r}"
        )
    report_path = os.path.join(out_dir, f"worker_{tag}.json")
    _write_json(report_path, report)

    compile_s = {"backend_compile_s": 0.0, "cache_retrieval_s": 0.0}
    cache = {"hits": 0, "misses": 0}

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s["backend_compile_s"] += secs
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            compile_s["cache_retrieval_s"] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    import numpy as np

    from dlrover_tpu.accel.strategy import Strategy
    from dlrover_tpu.ckpt.checkpointer import FlashCheckpointer
    from dlrover_tpu.common import faults
    from dlrover_tpu.models import config as model_configs
    from dlrover_tpu.models.train import shard_batch
    from dlrover_tpu.parallel.mesh import MeshConfig
    from dlrover_tpu.trainer.elastic.trainer import (
        ElasticTrainer,
        TrainerConfig,
        build_optimizer,
    )

    cfg = getattr(model_configs, spec.model)(**spec.model_overrides)
    restore = {"seconds": None, "step": None, "digest": None}
    load = FlashCheckpointer.load_checkpoint

    def timed_load(self, target):
        t0 = time.perf_counter()
        step, state = load(self, target)
        jax.block_until_ready(state)
        if state is not None:
            restore.update(
                seconds=time.perf_counter() - t0, step=int(step),
                digest=_state_digest(state["train"]),
            )
        return step, state

    FlashCheckpointer.load_checkpoint = timed_load

    steps_path = os.path.join(out_dir, f"steps_{tag}.jsonl")
    steps_file = open(steps_path, "a")
    holder: Dict = {}
    t_start = time.perf_counter()

    def on_step(step, metrics):
        if "loss" not in metrics:
            return  # eval metrics ride the same hook
        trainer = holder["trainer"]
        stats = trainer.pipeline_stats
        commits = int(stats.stage_commits)
        staging = stats.stage_backlog_bytes > 0
        # what the save that begins after this hook stages: the state
        # of this step, params and Adam moments, to hold the restored
        # state to (first incarnation only: it is a 1.5 GB fetch)
        digest = None
        if (
            not reference
            and restart == 0
            and not staging
            and step % spec.save_interval == 0
        ):
            digest = _state_digest(trainer.state)
        steps_file.write(
            json.dumps(
                {
                    "step": int(step),
                    "loss": float(metrics["loss"]),
                    "stage_commits": commits,
                    "state_digest": digest,
                    "t": round(time.perf_counter() - t_start, 3),
                    # running totals, so that the incarnation that is
                    # killed leaves its compile seconds behind too
                    "compile_s": round(compile_s["backend_compile_s"], 3),
                    "cache_hits": cache["hits"],
                    "cache_misses": cache["misses"],
                }
            )
            + "\n"
        )
        steps_file.flush()
        # the kill, once: armed in the first incarnation only, once a
        # committed save sits in agent shm, none is staging and none
        # begins after this hook (a kill mid-staging leaves shm invalid
        # and the restore would rightly come from storage instead)
        if (
            not reference
            and restart == 0
            and commits >= 1
            and not staging
            and not holder.get("armed")
            and step % spec.save_interval != 0
        ):
            holder["armed"] = True
            faults.configure("node.preempt:kill:@1")  # next step boundary

    strategy = None
    use_devices = None
    if reference:
        strategy = Strategy(mesh=MeshConfig())
        use_devices = devices[:1]
    elif spec.mesh:
        strategy = Strategy(mesh=MeshConfig(**spec.mesh))
    t0 = time.perf_counter()
    trainer = ElasticTrainer(
        model_cfg=cfg,
        tx=build_optimizer(
            "adamw", lr=spec.lr, schedule="cosine", warmup_steps=5,
            total_steps=max(spec.steps, 6), weight_decay=0.01,
        ),
        dataset=_Corpus(
            n=spec.batch * (spec.steps + 8), seq=spec.seq,
            vocab=cfg.vocab_size, seed=spec.seed,
        ),
        trainer_cfg=TrainerConfig(
            batch_size=spec.batch,
            seq_len=spec.seq,
            ckpt_dir="" if reference else os.path.join(out_dir, "ckpt"),
            save_memory_interval=spec.save_interval,
            save_storage_interval=10**9,
            log_interval=1,
        ),
        strategy=strategy,
        devices=use_devices,
        metrics_hook=on_step,
    )
    holder["trainer"] = trainer
    build_s = time.perf_counter() - t0

    # the step program, as the trainer built it, lowered for the live
    # state and a real batch: is the Pallas kernel in it, which
    # collectives did the compiler put in, what does it need in memory
    row = trainer.dataloader.dataset[0]
    x = np.broadcast_to(row["x"], (spec.batch, spec.seq))
    b = shard_batch({"x": x, "y": x}, trainer.mesh)
    lowered = trainer.accel.step_fn.lower(trainer.state, b["x"], b["y"])
    compiled = lowered.compile()
    text = compiled.as_text()
    ma = compiled.memory_analysis()
    program = {
        "tpu_custom_call": "tpu_custom_call" in lowered.as_text(),
        "all_gather": text.count("all-gather"),
        "reduce_scatter": text.count("reduce-scatter"),
        "all_reduce": text.count("all-reduce"),
        "memory_bytes": (
            None
            if ma is None
            else int(
                ma.argument_size_in_bytes
                + ma.output_size_in_bytes
                + ma.temp_size_in_bytes
                - ma.alias_size_in_bytes
            )
        ),
    }

    # where the state's bytes live: the largest parameter and its Adam
    # moments (a state sharded in name only would sit on device 0)
    leaves = jax.tree_util.tree_flatten_with_path(trainer.state.params)[0]
    path, biggest = max(leaves, key=lambda kv: kv[1].nbytes)
    spread = {"param": jax.tree_util.keystr(path)}
    spread["param_spread"] = _spread(biggest)
    moments = [
        leaf
        for leaf in jax.tree_util.tree_leaves(trainer.state.opt_state)
        if getattr(leaf, "shape", None) == biggest.shape
    ]
    spread["moment_spreads"] = [_spread(m) for m in moments]

    report.update(
        stage="built",
        build_seconds=round(build_s, 3),
        restore=restore,
        program=program,
        spread=spread,
        state_bytes=sum(
            int(leaf.nbytes)
            for leaf in jax.tree_util.tree_leaves(trainer.state)
        ),
        strategy=trainer.accel.strategy.describe(),
    )
    _write_json(report_path, report)

    t0 = time.perf_counter()
    trainer.train(num_steps=spec.steps)
    train_s = time.perf_counter() - t0
    trainer.close()
    steps_file.close()
    report.update(
        stage="done",
        train_seconds=round(train_s, 3),
        compile={k: round(v, 3) for k, v in compile_s.items()},
        persistent_cache=cache,
    )
    _write_json(report_path, report)
    return 0


# ----------------------------------------------------------------------
# parent side: no JAX from here on
# ----------------------------------------------------------------------
def emit(obj: Dict) -> None:
    print(json.dumps(obj), flush=True)


def _tail(path: str, n: int = TAIL_LINES) -> List[str]:
    try:
        with open(path, errors="replace") as f:
            return [ln.rstrip("\n")[:400] for ln in f.readlines()[-n:]]
    except OSError as e:
        return [f"<cannot read {path}: {e!r}>"]


def _read_text(path: str) -> str:
    try:
        with open(path, errors="replace") as f:
            return f.read()
    except OSError:
        return ""


def _read_json(path: str) -> Optional[Dict]:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _read_steps(path: str) -> List[Dict]:
    rows = []
    try:
        with open(path) as f:
            for line in f:
                try:
                    rows.append(json.loads(line))
                except ValueError:
                    pass  # a line the kill cut short
    except OSError:
        pass
    return rows


class PhaseFailed(Exception):
    def __init__(self, phase: str, why: str):
        super().__init__(why)
        self.phase = phase


def child_env(run_dir: str, sock_dir: str = "") -> Dict[str, str]:
    """Every path the package would otherwise keep under a fixed global
    name goes into the run directory, through the override each already
    has. AF_UNIX paths may hold 108 bytes, so the sockets alone live in
    a fresh short directory under /tmp."""
    # nothing of an outer job rides along: a master address, a fault
    # spec or a switched-off compile cache in the caller's environment
    # would make this run part of, or unlike, something else
    env = {
        k: v for k, v in os.environ.items()
        if not k.startswith("DLROVER_TPU_")
    }
    env.update(
        {
            "DLROVER_TPU_FLIGHT_DIR": os.path.join(run_dir, "flight"),
            "DLROVER_TPU_PARAL_CONFIG_PATH": os.path.join(
                run_dir, "auto_paral_config.json"
            ),
            "DLROVER_TPU_RUNTIME_METRICS_PATH": os.path.join(
                run_dir, "runtime_metrics.json"
            ),
            "DLROVER_TPU_WORKER_COMMANDS_PATH": os.path.join(
                run_dir, "worker_commands.json"
            ),
            "DLROVER_TPU_TOPOLOGY_CACHE": os.path.join(run_dir, "topology"),
            "TPU_LOG_DIR": os.path.join(run_dir, "tpu_logs"),
        }
    )
    if sock_dir:
        env["DLROVER_TPU_SOCKET_DIR"] = sock_dir
    return env


class Chain:
    """One launcher run under supervision, and the evidence it leaves."""

    def __init__(self, spec: SmokeSpec, run_dir: str):
        self.spec = spec
        self.run_dir = run_dir
        self.log_dir = os.path.join(run_dir, "logs")
        self.launcher_log = os.path.join(run_dir, "launcher.log")
        self.spec_path = os.path.join(run_dir, "spec.json")
        self.sock_dir = ""
        self.proc: Optional[subprocess.Popen] = None
        self._out = None
        self.t0 = time.monotonic()

    def start(self) -> None:
        os.makedirs(self.log_dir, exist_ok=True)
        _write_json(self.spec_path, asdict(self.spec))
        self.sock_dir = tempfile.mkdtemp(prefix="dts", dir="/tmp")
        cmd = [
            sys.executable, "-m", "dlrover_tpu.trainer.run",
            "--nnodes=1", "--nproc-per-node=1", "--network-check",
            f"--device-spec={self.spec.device_spec}",
            f"--max-restarts={self.spec.max_restarts}",
            f"--job-name=chipsmoke{os.getpid()}x{int(time.time())}",
            f"--log-dir={self.log_dir}",
            os.path.abspath(__file__), "--worker", self.spec_path,
        ]
        self._out = open(self.launcher_log, "ab")
        self.proc = subprocess.Popen(
            cmd, cwd=REPO, env=child_env(self.run_dir, self.sock_dir),
            stdout=self._out, stderr=subprocess.STDOUT,
            start_new_session=True,
        )

    def stop(self) -> None:
        """Leave no process behind, whatever state the run is in."""
        if self.proc is not None and self.proc.poll() is None:
            import signal

            for sig in (signal.SIGTERM, signal.SIGKILL):
                try:
                    os.killpg(self.proc.pid, sig)
                except ProcessLookupError:
                    break
                try:
                    self.proc.wait(timeout=20)
                    break
                except subprocess.TimeoutExpired:
                    continue
        if self._out is not None:
            self._out.close()
        if self.sock_dir:
            shutil.rmtree(self.sock_dir, ignore_errors=True)

    # -- evidence ------------------------------------------------------
    def launcher_text(self) -> str:
        return _read_text(self.launcher_log)

    def wait_for(self, phase: str, what: str, cond):
        """Poll ``cond`` until it returns something truthy; the launcher
        exiting first, or the deadline, fails ``phase``."""
        deadline = self.t0 + self.spec.timeout_s
        while True:
            got = cond()
            if got:
                return got
            rc = self.proc.poll()
            if rc is not None:
                got = cond()
                if got:
                    return got
                raise PhaseFailed(
                    phase, f"launcher exited rc={rc} before {what}"
                )
            if time.monotonic() > deadline:
                raise PhaseFailed(
                    phase,
                    f"no {what} within {self.spec.timeout_s:.0f}s",
                )
            time.sleep(0.2)


def failure_report(run_dir: str) -> None:
    """The end of every log a failure could be explained by: the driver
    keeps only the end of the output, so these come before the last
    line."""
    logs = sorted(glob.glob(os.path.join(run_dir, "logs", "worker_*_r*.log")))
    for path in logs + [
        os.path.join(run_dir, "reference.log"),
        os.path.join(run_dir, "launcher.log"),
    ]:
        if os.path.exists(path):
            emit({"log": os.path.basename(path), "tail": _tail(path)})


def _phase(name: str, t0: float, **extra) -> None:
    emit(
        {
            "phase": name,
            "passed": True,
            "seconds": round(time.monotonic() - t0, 3),
            **extra,
        }
    )


def run_chain(spec: SmokeSpec, run_dir: str) -> Dict:
    """launcher -> node check -> train -> save -> kill -> restore ->
    checks, each a named phase. Returns the evidence; raises
    ``PhaseFailed`` on the first phase that fails."""
    chain = Chain(spec, run_dir)
    try:
        return _run_chain(chain)
    finally:
        chain.stop()


def _run_chain(chain: Chain) -> Dict:
    spec, rd = chain.spec, chain.run_dir

    # 1. launcher: up, with its local master
    t = time.monotonic()
    chain.start()
    chain.wait_for(
        "launcher", "a local master",
        lambda: "spawned local master" in chain.launcher_text(),
    )
    _phase("launcher", t, pid=chain.proc.pid)

    # 2. node check: both paired rounds on the device
    t = time.monotonic()
    rounds = re.compile(
        r"check round (\d): success=(\w+) elapsed=([0-9.]+)s"
    )

    def checked():
        found = rounds.findall(chain.launcher_text())
        return found if len(found) >= 2 else None

    found = chain.wait_for("node_check", "two check rounds", checked)
    if any(ok != "True" for _, ok, _ in found):
        raise PhaseFailed("node_check", f"a round failed: {found}")
    _phase(
        "node_check", t,
        round_elapsed_s=[float(e) for _, _, e in found],
    )

    # 3. train: the first incarnation holds the device and steps
    t = time.monotonic()
    steps0_path = os.path.join(rd, "steps_r0.jsonl")
    first = chain.wait_for(
        "train", "a first training step",
        lambda: _read_steps(steps0_path),
    )[0]
    w0 = _read_json(os.path.join(rd, "worker_r0.json")) or {}
    _phase(
        "train", t, device=_device(w0),
        build_seconds=w0.get("build_seconds"),
        seconds_to_first_step=first["t"], first_loss=first["loss"],
    )

    # 4. save: a flash save committed to agent shm inside the steps
    t = time.monotonic()
    saved = chain.wait_for(
        "save", "a committed flash save",
        lambda: [
            r for r in _read_steps(steps0_path) if r["stage_commits"]
        ],
    )
    _phase("save", t, commit_seen_at_step=saved[0]["step"])

    # 5. kill: the worker process dies hard, once
    t = time.monotonic()
    died = re.compile(r"worker failure: local_rank=0 exitcode=(-?\d+)")
    deaths = chain.wait_for(
        "kill", "the worker's death",
        lambda: died.findall(chain.launcher_text()),
    )
    if deaths[0] != "137":
        raise PhaseFailed("kill", f"worker died with rc {deaths[0]}")
    steps0 = _read_steps(steps0_path)
    _phase(
        "kill", t, rc=137, last_step_before_death=steps0[-1]["step"],
        first_incarnation_compile_seconds=steps0[-1]["compile_s"],
        first_incarnation_persistent_cache={
            "hits": steps0[-1]["cache_hits"],
            "misses": steps0[-1]["cache_misses"],
        },
    )

    # 6. restore: the agent restarts the worker, which reopens the
    # device, restores from shm, replays and trains to the end
    t = time.monotonic()
    chain.wait_for(
        "restore", "the launcher's exit",
        lambda: chain.proc.poll() is not None,
    )
    launcher_rc = chain.proc.returncode
    deaths = died.findall(chain.launcher_text())
    w1 = _read_json(os.path.join(rd, "worker_r1.json")) or {}
    restored = re.findall(
        r"restored step (\d+) from memory",
        _read_text(os.path.join(chain.log_dir, "worker_0_0_r1.log")),
    )
    if len(deaths) != 1:
        raise PhaseFailed("restore", f"{len(deaths)} deaths, want 1")
    if launcher_rc != 0:
        raise PhaseFailed("restore", f"launcher rc {launcher_rc}")
    if w1.get("stage") != "done":
        raise PhaseFailed(
            "restore", f"second incarnation stopped at {w1.get('stage')!r}"
        )
    if not restored:
        raise PhaseFailed("restore", "no 'restored step K from memory'")
    _phase(
        "restore", t, launcher_rc=0, restored_step=int(restored[0]),
        restore_seconds=w1["restore"]["seconds"],
        second_incarnation_compile_seconds=w1["compile"][
            "backend_compile_s"
        ],
        second_incarnation_persistent_cache=w1["persistent_cache"],
        second_device=_device(w1),
    )
    return {
        "worker": w1,
        "restored_step": int(restored[0]),
        "steps0": steps0,
        "steps1": _read_steps(os.path.join(rd, "steps_r1.jsonl")),
    }


def _device(info: Dict) -> Dict:
    return {k: info.get(k) for k in ("platform", "kind", "count")}


def check_chain(spec: SmokeSpec, ev: Dict) -> Dict:
    """The checks on what the chain left; returns the device the worker
    reported. Raises ``PhaseFailed``."""
    t = time.monotonic()
    w1, k = ev["worker"], ev["restored_step"]
    l0 = {r["step"]: r["loss"] for r in ev["steps0"]}
    l1 = {r["step"]: r["loss"] for r in ev["steps1"]}

    def fail(why):
        raise PhaseFailed("checks", why)

    losses = list(l0.values()) + list(l1.values())
    if not losses or not all(math.isfinite(v) for v in losses):
        fail(f"non-finite loss among {losses}")
    if max(l1, default=-1) != spec.steps:
        fail(f"training ended at step {max(l1, default=-1)}")
    if min(l1) != k + 1:
        fail(f"restored step {k} but resumed at step {min(l1)}")
    replayed = sorted(s for s in l1 if s in l0)
    if not replayed:
        fail(f"no replayed step: died at {max(l0)}, restored {k}")
    emit(
        {
            "replay_tolerance_abs": spec.replay_atol,
            "replay_first_step_tolerance_abs": 0.0,
        }
    )
    staged = {
        r["step"]: r["state_digest"]
        for r in ev["steps0"] if r.get("state_digest")
    }
    restored = w1["restore"]["digest"]
    if not restored or staged.get(k) != restored:
        fail(
            f"restored state is not what was staged at step {k}: "
            f"staged {staged.get(k)}, restored {restored}"
        )
    worst = max(abs(l0[s] - l1[s]) for s in replayed)
    if l0[k + 1] != l1[k + 1] or worst > spec.replay_atol:
        fail(
            "replayed losses differ: "
            + str({s: (l0[s], l1[s]) for s in replayed})
        )
    if not l1[spec.steps] < l0[1]:
        fail(f"loss did not fall: {l0[1]} -> {l1[spec.steps]}")
    dev = _device(w1)
    if dev["platform"] != spec.expect_platform:
        fail(f"worker ran on {dev['platform']!r}")
    if dev["count"] != spec.expect_devices:
        fail(f"worker saw {dev['count']} devices")
    if spec.expect_platform == "tpu" and not w1["program"][
        "tpu_custom_call"
    ]:
        fail("no tpu_custom_call in the step: the Pallas kernel is out")
    if w1["persistent_cache"]["hits"] < 1:
        fail(f"second incarnation compiled cold: {w1['persistent_cache']}")
    _phase(
        "checks", t,
        losses_first=[l0[s] for s in sorted(l0)],
        losses_second_from_step=min(l1),
        losses_second=[l1[s] for s in sorted(l1)],
        restored_state_digest=restored, staged_state_digest=staged[k],
        replayed_steps=replayed, replay_max_abs_diff=worst,
        loss_step_1=l0[1], loss_end=l1[spec.steps],
        program=w1["program"], state_bytes=w1["state_bytes"],
        strategy=w1["strategy"],
    )
    return dev


def run_reference(spec: SmokeSpec, run_dir: str) -> List[Dict]:
    """The same seed and batches on a one-device mesh, in a process of
    its own once the chain has released the chips."""
    t = time.monotonic()
    out = os.path.join(run_dir, "reference.log")
    with open(out, "ab") as f:
        rc = subprocess.run(
            [
                sys.executable, os.path.abspath(__file__),
                "--reference-worker", os.path.join(run_dir, "spec.json"),
            ],
            cwd=REPO,
            # what the agent exports to its workers
            env={
                **child_env(run_dir),
                "DLROVER_TPU_DEVICE_SPEC": spec.device_spec,
            },
            stdout=f, stderr=subprocess.STDOUT,
            timeout=spec.timeout_s,
        ).returncode
    rows = _read_steps(os.path.join(run_dir, "steps_ref.jsonl"))
    if rc != 0 or not rows or rows[-1]["step"] != spec.steps:
        raise PhaseFailed("reference", f"one-device run failed rc={rc}")
    _phase("reference", t, losses=[r["loss"] for r in rows])
    return rows


def check_sharded(
    spec: SmokeSpec, ev: Dict, ref: List[Dict], loss_atol: float
) -> None:
    """What exists only across chips: the sharded run against the
    one-device run, where the state's bytes live, and the collectives
    in the compiled step."""
    t = time.monotonic()
    w1 = ev["worker"]

    def fail(why):
        raise PhaseFailed("sharded", why)

    n = spec.expect_devices
    emit({"sharded_vs_one_device_loss_tolerance_abs": loss_atol})
    lr = {r["step"]: r["loss"] for r in ref}
    ls = {r["step"]: r["loss"] for r in ev["steps0"]}
    ls.update({r["step"]: r["loss"] for r in ev["steps1"]})
    worst = max(abs(ls[s] - lr[s]) for s in lr)
    if sorted(ls) != sorted(lr) or worst > loss_atol:
        fail(f"losses apart by {worst}: sharded {ls} one-device {lr}")
    spreads = [w1["spread"]["param_spread"]] + w1["spread"][
        "moment_spreads"
    ]
    if len(spreads) < 3:
        fail(f"no Adam moments found for {w1['spread']['param']}")
    for sp in spreads:
        share = [b / sp["total_bytes"] for b in sp["shard_bytes"]]
        if len(sp["devices"]) != n or any(
            abs(x - 1 / n) > 0.02 for x in share
        ):
            fail(f"{w1['spread']['param']} is not spread over {n}: {sp}")
    prog = w1["program"]
    # XLA:CPU writes the gradient leg of ZeRO as all-reduce + slice
    reduced = prog["reduce_scatter"] or (
        spec.expect_platform != "tpu" and prog["all_reduce"]
    )
    if not (prog["all_gather"] and reduced):
        fail(f"no all-gather/reduce-scatter in the step: {prog}")
    _phase(
        "sharded", t, max_abs_loss_diff=worst, param=w1["spread"]["param"],
        spreads=spreads, program=prog,
    )


def run_smoke(spec: SmokeSpec, run_dir: str, sharded_atol=None) -> Dict:
    """Every phase in order. Returns the result object of the last line;
    ``ok`` is true only if every phase passed."""
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    result = {"ok": False, "device": None}
    try:
        ev = run_chain(spec, run_dir)
        result["device"] = _device(ev["worker"])
        check_chain(spec, ev)
        if sharded_atol is not None:
            check_sharded(
                spec, ev, run_reference(spec, run_dir), sharded_atol
            )
        result["ok"] = True
    except Exception as e:
        # anything else than a phase's own verdict (a reference run
        # past its time limit, a report the kill cut short) is a
        # failure of the script: it still names itself, brings the
        # logs, and leaves the last line to main
        known = isinstance(e, PhaseFailed)
        phase = e.phase if known else "internal"
        result["failed_phase"] = phase
        emit(
            {
                "phase": phase,
                "passed": False,
                "error": str(e) if known else repr(e),
            }
        )
        failure_report(run_dir)
    return result


def final_result(result: Dict, chips: int) -> Dict:
    """The last line: ``ok`` only if every phase passed AND the worker
    reported the TPU with the chip count asked for."""
    dev = result["device"] or {}
    result["ok"] = bool(
        result["ok"]
        and dev.get("platform") == "tpu"
        and dev.get("count") == chips
    )
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--worker", metavar="SPEC", help=argparse.SUPPRESS)
    p.add_argument(
        "--reference-worker", metavar="SPEC", help=argparse.SUPPRESS
    )
    args = p.parse_args(argv)
    if args.worker:
        return worker_main(args.worker)
    if args.reference_worker:
        return worker_main(args.reference_worker, reference=True)

    # always the published shape of GPT-2 124M at seq 1024, on the TPU
    if args.chips == 1:
        result = run_smoke(SmokeSpec(replay_atol=REPLAY_ATOL_TPU), RUN_DIR)
    else:
        result = run_smoke(
            SmokeSpec(
                replay_atol=REPLAY_ATOL_TPU, expect_devices=4,
                mesh={"fsdp": 4},
            ),
            RUN_DIR, sharded_atol=0.05,
        )
    result = final_result(result, args.chips)
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
