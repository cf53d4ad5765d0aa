#!/usr/bin/env python
"""Deterministic chaos harness: scripted kill/evict/outage scenarios
with hard recovery gates.

`common/faults.py` gives single fault POINTS deterministic triggering;
this harness composes them into end-to-end SCENARIOS — the sequences a
hostile fleet actually produces — and gates each one on the survival
contract instead of "it didn't crash":

- **loss continuity**: training resumed from the surviving checkpoint
  reproduces the uninterrupted run's losses bitwise;
- **bounded loss of progress**: a hard kill loses at most one commit
  interval of steps;
- **goodput attribution**: an eviction drain books its wall time to the
  ``eviction`` category, not ``other``;
- **no wedged processes**: every scenario ends with the process tree
  (or thread set) it started with.

Scenarios (each takes a seed; the same seed replays the same run):

| name                     | what it scripts                             |
|--------------------------|---------------------------------------------|
| eviction_during_save     | eviction notice lands while a chunked save  |
|                          | is staged: graceful drain, emergency commit |
|                          | of the CURRENT step, bitwise resume         |
| sigkill_mid_step         | `node.preempt:kill:@K` hard-exits a real    |
|                          | trainer subprocess mid-run; the restarted   |
|                          | process loses <= one commit interval        |
| master_restart_mid_plan  | the master dies holding a pending Brain     |
|                          | cluster-plan slice; the restarted executor  |
|                          | redelivers and the plan converges to acked  |
| brain_outage_mid_plan    | the Brain goes dark mid-plan; the executor  |
|                          | degrades to warnings and the redelivered    |
|                          | slice executes when the Brain returns       |
| serving_crc_retry        | a weight commit rots in shm (seeded bit     |
|                          | flip after the writer's checksum); the      |
|                          | serving subscriber names the record, skips  |
|                          | the generation, adopts the next clean commit|
| sdc_quarantine           | one chip computes wrong-but-finite numbers  |
|                          | (`device.sdc:scale`); fence detects, paired |
|                          | audit convicts exactly that chip, verified  |
|                          | rollback + permanent rendezvous quarantine, |
|                          | bitwise resume on the surviving devices     |

Usage:

    python tools/chaos.py --list
    python tools/chaos.py --scenario eviction_during_save --seed 7
    python tools/chaos.py --all --seed 7          # the full matrix
    # any invocation: --json for machine-readable gate output

Exit codes: 0 = every gate passed; 1 = a gate failed; 2 = usage.

The full matrix lives in ``tests/test_chaos_harness.py`` (tier-1 runs
the fast scenarios through :func:`run_scenario`, the trainer-bearing and
subprocess legs are ``slow``).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional

try:  # script execution (`python tools/chaos.py`) without an
    import dlrover_tpu  # noqa: F401  # installed package: fall back to
except ImportError:  # the repo root next to this file
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    import dlrover_tpu  # noqa: F401

# scenario tuning: small enough for CI, large enough that the kill and
# the eviction land mid-run with real checkpoints on both sides
TOTAL_STEPS = 16
SAVE_MEMORY_INTERVAL = 4
# the commit interval the SIGKILL gate is bounded by (storage commits
# in the subprocess leg; the sync engine commits every memory save too)
COMMIT_INTERVAL = 4
EVICT_STEP = 8  # a save-interval step: a chunked stage is in flight
KILL_STEP = 7  # node.preempt evaluations are step boundaries (1-based)


# ---------------------------------------------------------------------------
# shared tiny-trainer scaffolding
# ---------------------------------------------------------------------------
class _Tokens:
    def __init__(self, n=2048, seq=32, vocab=256, seed=11):
        import numpy as np

        rng = np.random.default_rng(seed)
        self.data = rng.integers(0, vocab, (n, seq + 1), dtype=np.int32)

    def __len__(self):
        return len(self.data)

    def __getitem__(self, i):
        return {"x": self.data[i][:-1], "y": self.data[i][1:]}


def _make_trainer(ckpt_dir: str, seed: int, metrics_hook=None):
    import jax
    import optax

    from dlrover_tpu.accel.strategy import Strategy
    from dlrover_tpu.models import tiny
    from dlrover_tpu.parallel.mesh import MeshConfig
    from dlrover_tpu.trainer.elastic.trainer import (
        ElasticTrainer,
        TrainerConfig,
    )

    return ElasticTrainer(
        model_cfg=tiny(num_layers=1),
        tx=optax.adamw(1e-2),
        dataset=_Tokens(seed=seed),
        trainer_cfg=TrainerConfig(
            batch_size=8,
            seq_len=32,
            ckpt_dir=ckpt_dir,
            save_memory_interval=SAVE_MEMORY_INTERVAL,
            save_storage_interval=10_000,  # memory-path commits only
            report_metrics=False,
            log_interval=4,
            prefetch=2,
            donation_aware=False,
            speculative_compile=False,
            eviction_grace_s=20.0,
        ),
        strategy=Strategy(mesh=MeshConfig(dp=1), dtype="float32"),
        devices=list(jax.devices())[:1],
        metrics_hook=metrics_hook,
    )


def _loss_recorder(losses: Dict[int, float], on_step=None):
    """metrics_hook that materializes every step's loss (the host sync
    makes the trajectory comparable bitwise) and optionally fires a
    scripted per-step action."""

    def hook(step, metrics):
        if "loss" in metrics:
            losses[step] = float(metrics["loss"])
        if on_step is not None:
            on_step(step)

    return hook


def _thread_names() -> List[str]:
    return sorted(
        t.name for t in threading.enumerate() if t.is_alive()
    )


# ---------------------------------------------------------------------------
# scenario: eviction during chunked save
# ---------------------------------------------------------------------------
def eviction_during_save(seed: int, workdir: str) -> Dict:
    """An eviction notice lands at a save-interval step — a chunked
    stage of that step is in flight — and the trainer drains: aborts
    the stale stage, emergency-commits the CURRENT step inside the
    grace window, books the drain to the ``eviction`` goodput
    category, and a fresh trainer resumes bitwise."""
    from dlrover_tpu.common import faults
    from dlrover_tpu.obs import flight_recorder as obs_flight

    faults.reset()
    golden_dir = os.path.join(workdir, "golden_ckpt")
    ckpt_dir = os.path.join(workdir, "evict_ckpt")
    out: Dict = {"scenario": "eviction_during_save", "seed": seed}

    # the drain dumps an `eviction` flight bundle: keep the artifact
    # inside the scenario workdir (and gate on its existence below)
    prev_flight = os.environ.get(obs_flight.ENV_FLIGHT_DIR)
    os.environ[obs_flight.ENV_FLIGHT_DIR] = os.path.join(
        workdir, "flight"
    )
    threads_before = _thread_names()

    # golden: the uninterrupted trajectory (same data seed, same save
    # cadence — checkpoint activity must not be a variable)
    golden: Dict[int, float] = {}
    t = _make_trainer(golden_dir, seed, _loss_recorder(golden))
    try:
        t.train(TOTAL_STEPS)
    finally:
        t.close()

    # run A: evict at EVICT_STEP, mid-save
    losses_a: Dict[int, float] = {}
    stager_live = {"at_evict": False}

    def maybe_evict(step):
        if step == EVICT_STEP:
            stager_live["at_evict"] = trainer._stager is not None
            trainer.request_eviction(20.0, reason="chaos")

    trainer = _make_trainer(
        ckpt_dir, seed, _loss_recorder(losses_a, maybe_evict)
    )
    try:
        trainer.train(TOTAL_STEPS)
        out["evicted"] = trainer.evicted
        out["drain_ms"] = round(trainer.eviction_drain_ms, 1)
        gp = trainer._goodput.snapshot()
        out["goodput_eviction_s"] = round(
            gp.seconds.get("eviction", 0.0), 4
        )
        out["goodput_other_s"] = round(gp.seconds.get("other", 0.0), 4)
        verified = trainer._ckptr.latest_verified_step()
        out["verified_step"] = verified
    finally:
        trainer.close()

    # run B: resume from the emergency checkpoint, finish the run
    losses_b: Dict[int, float] = {}
    t2 = _make_trainer(ckpt_dir, seed, _loss_recorder(losses_b))
    try:
        out["resumed_step"] = t2.global_step
        t2.train(TOTAL_STEPS)
    finally:
        t2.close()

    flight_dir = os.path.join(workdir, "flight")
    out["flight_bundle"] = bool(
        os.path.isdir(flight_dir)
        and any("eviction" in d for d in os.listdir(flight_dir))
    )
    if prev_flight is None:
        os.environ.pop(obs_flight.ENV_FLIGHT_DIR, None)
    else:
        os.environ[obs_flight.ENV_FLIGHT_DIR] = prev_flight

    # let trainer daemon threads (heartbeats, watchdogs) finish dying
    deadline = time.time() + 10
    while _thread_names() != threads_before and time.time() < deadline:
        time.sleep(0.1)
    wedged = [
        n for n in _thread_names() if n not in threads_before
    ]
    out["wedged_threads"] = wedged

    resumed_steps = sorted(losses_b)
    out["loss_bitwise"] = bool(resumed_steps) and all(
        losses_b[s] == golden.get(s) for s in resumed_steps
    )
    out["lost_steps"] = TOTAL_STEPS  # pessimistic default
    if "resumed_step" in out:
        out["lost_steps"] = EVICT_STEP - out["resumed_step"]
    out["ok"] = bool(
        out.get("evicted")
        and out.get("verified_step", -1) == EVICT_STEP
        and out.get("resumed_step", -1) == EVICT_STEP
        and out["loss_bitwise"]
        and out["goodput_eviction_s"] > 0
        and out["flight_bundle"]
        and not wedged
    )
    return out


# ---------------------------------------------------------------------------
# scenario: SIGKILL mid-step (real process death, subprocess leg)
# ---------------------------------------------------------------------------
def _worker_train(args) -> int:
    """Subprocess body: a real trainer that dies (or not) per the
    DLROVER_TPU_FAULTS env the parent armed. Writes a progress file so
    the parent can gate on resumed/final steps."""
    progress = {"start_step": -1, "end_step": -1, "losses": {}}

    def hook(step, metrics):
        if "loss" in metrics:
            progress["losses"][str(step)] = float(metrics["loss"])
        progress["end_step"] = step
        with open(args.progress + ".tmp", "w") as f:
            json.dump(progress, f)
        # graftlint: disable=durable-rename reason=harness progress telemetry at step cadence; the parent only needs atomic reads, and the scripted kill losing the last write is the scenario under test
        os.replace(args.progress + ".tmp", args.progress)

    t = _make_trainer(args.ckpt_dir, args.seed, hook)
    # the kill leg gates on the STORAGE commit interval: shm does not
    # outlive this single-process scenario, disk does
    t.tcfg.save_storage_interval = COMMIT_INTERVAL
    t.tcfg.save_memory_interval = 10_000
    try:
        progress["start_step"] = t.global_step
        hook(t.global_step, {})
        t.train(TOTAL_STEPS)
    finally:
        t.close()
    return 0


def _spawn_worker(
    ckpt_dir: str, progress: str, seed: int, fault_spec: str = ""
) -> subprocess.Popen:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["DLROVER_TPU_FAULTS"] = fault_spec
    return subprocess.Popen(
        [
            sys.executable,
            os.path.abspath(__file__),
            "--worker",
            "--ckpt-dir", ckpt_dir,
            "--progress", progress,
            "--seed", str(seed),
        ],
        env=env,
        stdout=subprocess.DEVNULL,
        stderr=subprocess.STDOUT,
    )


def sigkill_mid_step(seed: int, workdir: str) -> Dict:
    """A real trainer process hard-exits (``node.preempt:kill:@K`` —
    the in-process stand-in for SIGKILL/OOM-kill/hard preemption) at a
    scripted step boundary; the restarted process must resume from a
    verified checkpoint losing at most one commit interval of steps,
    finish, and stay loss-continuous with its own pre-kill history."""
    ckpt_dir = os.path.join(workdir, "kill_ckpt")
    progress = os.path.join(workdir, "kill_progress.json")
    out: Dict = {"scenario": "sigkill_mid_step", "seed": seed}

    # leg 1: scripted death at the KILL_STEP-th step boundary
    spec = f"node.preempt:kill:@{KILL_STEP + 1}:{seed}"
    p = _spawn_worker(ckpt_dir, progress, seed, fault_spec=spec)
    try:
        rc = p.wait(timeout=600)
    except subprocess.TimeoutExpired:
        p.kill()
        out["ok"] = False
        out["error"] = "killed worker wedged (timeout)"
        return out
    out["kill_rc"] = rc
    try:
        with open(progress) as f:
            prog1 = json.load(f)
    except (OSError, ValueError):
        prog1 = {}
    kill_step = int(prog1.get("end_step", -1))
    out["killed_at_step"] = kill_step

    # leg 2: restart, resume, finish
    p2 = _spawn_worker(ckpt_dir, progress, seed, fault_spec="")
    try:
        rc2 = p2.wait(timeout=600)
    except subprocess.TimeoutExpired:
        p2.kill()
        out["ok"] = False
        out["error"] = "restarted worker wedged (timeout)"
        return out
    out["restart_rc"] = rc2
    try:
        with open(progress) as f:
            prog2 = json.load(f)
    except (OSError, ValueError):
        prog2 = {}
    resumed = int(prog2.get("start_step", -1))
    out["resumed_step"] = resumed
    out["final_step"] = int(prog2.get("end_step", -1))
    out["lost_steps"] = kill_step - resumed if resumed >= 0 else -1
    # continuity across the kill: where the histories overlap, the
    # replayed steps must reproduce the pre-kill losses bitwise
    l1 = prog1.get("losses", {})
    l2 = prog2.get("losses", {})
    overlap = sorted(set(l1) & set(l2), key=int)
    out["overlap_steps"] = len(overlap)
    out["loss_bitwise"] = all(l1[s] == l2[s] for s in overlap)
    out["ok"] = bool(
        rc == 137  # the injected hard exit, not an incidental crash
        and rc2 == 0
        and kill_step >= KILL_STEP - 1
        and 0 <= out["lost_steps"] <= COMMIT_INTERVAL
        and out["final_step"] >= TOTAL_STEPS
        and out["loss_bitwise"]
    )
    return out


# ---------------------------------------------------------------------------
# scenario: master restart with a pending cluster-plan slice
# ---------------------------------------------------------------------------
class _FakeScaler:
    """Minimal platform scaler: records plans (the PR-9 test pattern)."""

    def __init__(self):
        self.plans: List = []
        self.exclude: tuple = ()

    def scale(self, plan):
        self.plans.append(plan)

    def relaunch_node(self, old, new):
        pass

    def set_exclude_hosts(self, hosts):
        self.exclude = tuple(hosts)


def _brain_with_plan(workdir: str, job: str, count: int):
    """A serving Brain holding one pending plan slice for ``job``."""
    from dlrover_tpu.brain.service import start_brain_service

    db = os.path.join(workdir, "brain.db")
    server, ds, addr = start_brain_service(db_path=db)
    version = ds.next_plan_version()
    ds.record_cluster_plan(
        version,
        [
            {
                "job": job,
                "worker_count": count,
                "prev_count": 2,
                "reason": "chaos",
                "exclude_hosts": [],
            }
        ],
        time.time(),
    )
    return server, ds, addr, version


def _executor(addr: str, job: str, target: int = 2):
    from dlrover_tpu.brain.plan_exec import PlanExecutor
    from dlrover_tpu.brain.service import BrainClient
    from dlrover_tpu.master.job_auto_scaler import JobAutoScaler
    from dlrover_tpu.master.job_manager import JobManager

    jm = JobManager(scaler=_FakeScaler())
    jm.create_initial_nodes(target)
    scaler = JobAutoScaler(
        jm, scaler=_FakeScaler(), target_nodes=target
    )
    client = BrainClient(addr, job, retry_budget_s=3.0, retries=1)
    return PlanExecutor(client, scaler), scaler, client


def master_restart_mid_plan(seed: int, workdir: str) -> Dict:
    """The master dies between the Brain emitting a plan slice and the
    executor acting on it (the PR-9 robustness gap): the restarted
    master's fresh ``PlanExecutor`` (ack watermark 0) must be
    redelivered the pending slice, execute it, and converge the plan
    to acked — no slice is ever silently dropped."""
    out: Dict = {"scenario": "master_restart_mid_plan", "seed": seed}
    job = f"chaos-mrp-{seed}"
    server, ds, addr, version = _brain_with_plan(workdir, job, 4)
    try:
        # incarnation 1: built, never got to poll (died mid-window)
        ex1, _, c1 = _executor(addr, job)
        c1.close()
        del ex1

        # incarnation 2: fresh watermark -> redelivery -> ack
        ex2, scaler2, c2 = _executor(addr, job)
        try:
            executed = ex2.poll_once()
            out["executed_version"] = executed
            out["target_after"] = scaler2.target
            counts = ds.plan_status_counts()
            out["plan_status"] = dict(counts)
            out["ok"] = bool(
                executed == version
                and scaler2.target == 4
                and counts.get("acked", 0) >= 1
                and counts.get("pending", 0) == 0
            )
        finally:
            c2.close()
    finally:
        server.stop(grace=0)
    return out


# ---------------------------------------------------------------------------
# scenario: Brain outage mid-plan
# ---------------------------------------------------------------------------
def brain_outage_mid_plan(seed: int, workdir: str) -> Dict:
    """The Brain goes dark while a plan slice is pending: the executor
    must degrade to warnings (training untouched), and the redelivered
    slice must execute once the Brain returns on the same store."""
    from dlrover_tpu.brain.service import start_brain_service

    out: Dict = {"scenario": "brain_outage_mid_plan", "seed": seed}
    job = f"chaos-bom-{seed}"
    server, ds, addr, version = _brain_with_plan(workdir, job, 4)
    port = int(addr.rsplit(":", 1)[1])
    ex, scaler, client = _executor(addr, job)
    try:
        # outage BEFORE the first poll: the slice is pending server-side
        server.stop(grace=0).wait(timeout=5)
        got = ex.poll_once()  # must swallow the outage, not raise
        out["poll_during_outage"] = got
        out["target_during_outage"] = scaler.target

        # Brain returns on the same port + store
        server2, ds2, _ = start_brain_service(
            port=port, db_path=os.path.join(workdir, "brain.db")
        )
        try:
            deadline = time.time() + 30
            executed = None
            while executed is None and time.time() < deadline:
                executed = ex.poll_once()
                if executed is None:
                    time.sleep(0.2)
            out["executed_version"] = executed
            counts = ds2.plan_status_counts()
            out["plan_status"] = dict(counts)
            out["ok"] = bool(
                got is None
                and out["target_during_outage"] == 2
                and executed == version
                and scaler.target == 4
                and counts.get("acked", 0) >= 1
            )
        finally:
            server2.stop(grace=0)
    finally:
        client.close()
        server.stop(grace=0)
    return out


def serving_crc_retry(seed: int, workdir: str) -> Dict:
    """A weight commit rots in flight (`ckpt.shm_stage` bit flip,
    applied AFTER the writer's checksum): the serving subscriber must
    name the rotten record, skip that generation WITHOUT crashing,
    keep serving its previous weights, and adopt the next clean
    commit — the retry-next-commit contract of ISSUE 17."""
    import numpy as np

    from dlrover_tpu.common import faults
    from dlrover_tpu.ckpt.shm_handler import ShmHandler, ShmSubscriber
    from dlrover_tpu.ckpt.sharding import host_shard_records

    out: Dict = {"scenario": "serving_crc_retry", "seed": seed}
    faults.reset()
    old_job = os.environ.get("DLROVER_TPU_JOB_NAME")
    os.environ["DLROVER_TPU_JOB_NAME"] = f"chaos-scr-{seed}"
    writer = sub = None
    try:
        writer = ShmHandler(0, create=True)
        rng = np.random.default_rng(seed)
        state = {
            "w": rng.normal(size=(32, 16)).astype(np.float32),
            "b": rng.normal(size=(16,)).astype(np.float32),
        }
        writer.save_records(1, host_shard_records(state), {})
        sub = ShmSubscriber(0)
        f1 = sub.poll()
        out["adopted_step"] = f1.step if f1 is not None else -1
        # commit 2 rots in flight: one seeded bit flips in the first
        # chunk, after the record checksum was computed
        faults.configure(f"ckpt.shm_stage:bit_flip:@1:{seed}")
        writer.save_records(2, host_shard_records(state), {})
        faults.reset()
        f2 = sub.poll()  # must skip the rotten generation, not raise
        out["poll_after_rot_none"] = f2 is None
        # repolling the SAME rotten generation must not spin the
        # counter — the subscriber waits for the next commit
        sub.poll()
        out["crc_retries"] = sub.crc_retries
        out["rotten_record"] = sub.last_crc_record
        writer.save_records(3, host_shard_records(state), {})
        f3 = sub.poll()
        out["recovered_step"] = f3.step if f3 is not None else -1
        out["torn_retries"] = sub.torn_retries
        del f1, f2, f3  # drop shm views before the mappings close
        out["ok"] = bool(
            out["adopted_step"] == 1
            and out["poll_after_rot_none"]
            and out["crc_retries"] == 1
            and out["rotten_record"] is not None
            and out["recovered_step"] == 3
        )
    finally:
        faults.reset()
        if sub is not None:
            sub.close()
        if writer is not None:
            writer.close(unlink=True)
        if old_job is None:
            os.environ.pop("DLROVER_TPU_JOB_NAME", None)
        else:
            os.environ["DLROVER_TPU_JOB_NAME"] = old_job
    return out


# ---------------------------------------------------------------------------
# scenario: silent data corruption -> audit conviction -> quarantine
# ---------------------------------------------------------------------------
SDC_ONSET = 6  # 1-based step the injected chip starts lying at


def _make_sdc_trainer(ckpt_dir: str, seed: int, metrics_hook=None):
    """dp=4 variant of :func:`_make_trainer`: the SDC detector needs
    replica peers to vote against, so the scenario runs four lanes on
    four (virtual) devices with the tier-1 fences armed."""
    import jax
    import optax

    from dlrover_tpu.accel.strategy import Strategy
    from dlrover_tpu.models import tiny
    from dlrover_tpu.parallel.mesh import MeshConfig
    from dlrover_tpu.trainer.elastic.trainer import (
        ElasticTrainer,
        TrainerConfig,
    )

    return ElasticTrainer(
        model_cfg=tiny(num_layers=1),
        tx=optax.adamw(1e-2),
        dataset=_Tokens(seed=seed),
        trainer_cfg=TrainerConfig(
            batch_size=8,
            seq_len=32,
            ckpt_dir=ckpt_dir,
            save_memory_interval=SAVE_MEMORY_INTERVAL,
            # the rollback target must survive the halted incarnation:
            # commit to storage at the same cadence
            save_storage_interval=SAVE_MEMORY_INTERVAL,
            report_metrics=False,
            log_interval=4,
            prefetch=0,
            donation_aware=False,
            speculative_compile=False,
            comm_overlap=True,
            sdc_detect=True,
        ),
        strategy=Strategy(mesh=MeshConfig(dp=4), dtype="float32"),
        devices=list(jax.devices())[:4],
        metrics_hook=metrics_hook,
    )


def _sdc_cleanup():
    from dlrover_tpu.common import faults
    from dlrover_tpu.parallel import sdc as sdc_mod

    faults.reset()
    sdc_mod.set_enabled(False)


def sdc_convict_only(seed: int, workdir: str) -> Dict:
    """Light leg (no golden / no resume): arm ``device.sdc`` against
    lane ``seed % 4`` and gate that the audit convicts EXACTLY that
    lane. ``tests/test_sdc.py::TestSdcSoak`` runs this across extra seeds
    as the innocent-conviction sweep."""
    from dlrover_tpu.common import faults

    faults.reset()
    expected = seed % 4
    out: Dict = {
        "scenario": "sdc_convict_only",
        "seed": seed,
        "expected_lane": expected,
    }
    faults.configure(f"device.sdc:scale:@{SDC_ONSET}:{seed}")
    tr = _make_sdc_trainer(
        os.path.join(workdir, f"sdc_only_{seed}"), seed
    )
    try:
        tr.train(TOTAL_STEPS)
        out["convicted"] = list(tr.sdc_convicted)
        out["detect_step"] = tr.sdc_detect_step
        out["halted_step"] = tr.global_step
    finally:
        tr.close()
        _sdc_cleanup()
    out["detect_steps"] = (
        out["detect_step"] - SDC_ONSET + 1
        if out.get("detect_step") is not None
        else TOTAL_STEPS
    )
    out["innocent_convictions"] = sum(
        1 for lane in out.get("convicted", []) if lane != expected
    )
    out["ok"] = bool(
        out.get("convicted") == [expected]
        and out["innocent_convictions"] == 0
        and out["detect_steps"] <= 10
    )
    return out


def sdc_quarantine(seed: int, workdir: str) -> Dict:
    """One chip silently computes wrong-but-finite numbers
    (``device.sdc:scale:@{onset}:{seed}`` scales lane ``seed % 4``'s
    local gradient by a large finite factor): the tier-1 fence flags
    the lane within 10 steps, the paired audit probe convicts exactly
    the injected chip, the trainer rolls back to the last verified
    checkpoint (replay booked to ``restart_replay``) and halts the
    incarnation; the master quarantines the convicted rank out of the
    next rendezvous world PERMANENTLY; a fresh trainer — the convicted
    chip replaced, fault disarmed — resumes from the verified step and
    reproduces the uninterrupted run's losses bitwise."""
    from dlrover_tpu.common import faults
    from dlrover_tpu.common.constants import NodeExitReason
    from dlrover_tpu.master.job_manager import JobManager
    from dlrover_tpu.master.rdzv_manager import (
        ElasticTrainingRendezvousManager,
    )
    from dlrover_tpu.obs import flight_recorder as obs_flight

    faults.reset()
    lane = seed % 4
    out: Dict = {
        "scenario": "sdc_quarantine",
        "seed": seed,
        "injected_lane": lane,
    }
    prev_flight = os.environ.get(obs_flight.ENV_FLIGHT_DIR)
    os.environ[obs_flight.ENV_FLIGHT_DIR] = os.path.join(
        workdir, "flight"
    )
    threads_before = _thread_names()
    golden_dir = os.path.join(workdir, "golden_ckpt")
    ckpt_dir = os.path.join(workdir, "sdc_ckpt")

    try:
        # golden: the uninterrupted dp=4 trajectory, detector armed but
        # nothing to find (the step graph must be the same one the
        # faulted and resumed runs trace)
        golden: Dict[int, float] = {}
        t = _make_sdc_trainer(golden_dir, seed, _loss_recorder(golden))
        try:
            t.train(TOTAL_STEPS)
        finally:
            t.close()

        # the in-process master: conviction events fan out to permanent
        # rendezvous quarantine, exactly as LocalJobMaster wires it
        jm = JobManager()
        jm.create_initial_nodes(4)
        rdzv = ElasticTrainingRendezvousManager()
        rdzv.update_rdzv_params(
            min_nodes=1, max_nodes=4, waiting_timeout=0.0
        )
        jm.add_sdc_listener(
            lambda nt, nid, detail: rdzv.quarantine_node(nid)
        )
        events: List[str] = []

        def reporter(event: str, detail: str):
            events.append(event)
            if event != "sdc_conviction":
                return
            for convicted in json.loads(detail).get("convicted", []):
                jm.handle_sdc_conviction(
                    "worker", int(convicted), detail="chaos sdc"
                )

        # run A: the chip goes bad at SDC_ONSET; detect -> audit ->
        # convict -> rollback -> halt
        faults.configure(f"device.sdc:scale:@{SDC_ONSET}:{seed}")
        losses_a: Dict[int, float] = {}
        tr = _make_sdc_trainer(ckpt_dir, seed, _loss_recorder(losses_a))
        tr.set_event_reporter(reporter)
        try:
            tr.train(TOTAL_STEPS)
            out["convicted"] = list(tr.sdc_convicted)
            out["detect_step"] = tr.sdc_detect_step
            out["halted_step"] = tr.global_step
            out["verified_step"] = tr._ckptr.latest_verified_step()
            gp = tr._goodput.snapshot()
            out["goodput_replay_s"] = round(
                gp.seconds.get("restart_replay", 0.0), 4
            )
        finally:
            tr.close()
        faults.reset()

        out["events"] = events
        out["detect_steps"] = (
            out["detect_step"] - SDC_ONSET + 1
            if out.get("detect_step") is not None
            else TOTAL_STEPS
        )
        node = jm.get_node("worker", lane)
        out["exit_reason"] = node.exit_reason if node else ""
        out["quarantined"] = [
            list(q) for q in jm.quarantined_nodes()
        ]

        # the next rendezvous world: every rank re-joins, the convicted
        # rank's join is parked and the frozen world excludes it
        for rank in range(4):
            rdzv.join_rendezvous(rank, 1, addr=f"host-{rank}")
        _, _, world, _ = rdzv.get_comm_world(
            (lane + 1) % 4
        )
        out["world_ranks"] = sorted(world)
        out["excluded_ranks"] = rdzv.excluded_ranks()

        # run B: the convicted chip is gone (fault disarmed = hardware
        # replaced); resume from the verified checkpoint and finish
        losses_b: Dict[int, float] = {}
        t2 = _make_sdc_trainer(ckpt_dir, seed, _loss_recorder(losses_b))
        try:
            out["resumed_step"] = t2.global_step
            t2.train(TOTAL_STEPS)
        finally:
            t2.close()

        flight_dir = os.path.join(workdir, "flight")
        out["flight_bundle"] = bool(
            os.path.isdir(flight_dir)
            and any(
                "sdc_conviction" in d for d in os.listdir(flight_dir)
            )
        )

        resumed_steps = sorted(losses_b)
        out["loss_bitwise"] = bool(resumed_steps) and all(
            losses_b[s] == golden.get(s) for s in resumed_steps
        )
        out["innocent_convictions"] = sum(
            1 for c in out.get("convicted", []) if c != lane
        )

        deadline = time.time() + 10
        while (
            _thread_names() != threads_before
            and time.time() < deadline
        ):
            time.sleep(0.1)
        wedged = [
            n for n in _thread_names() if n not in threads_before
        ]
        out["wedged_threads"] = wedged

        out["ok"] = bool(
            out.get("convicted") == [lane]
            and out["innocent_convictions"] == 0
            and out["detect_steps"] <= 10
            and out.get("verified_step", -1) >= 0
            and out.get("halted_step", -1)
            == out.get("verified_step", -2)
            and out.get("resumed_step", -1)
            == out.get("verified_step", -2)
            and out.get("goodput_replay_s", 0.0) > 0
            and out.get("exit_reason") == NodeExitReason.SDC_QUARANTINED
            and lane in out.get("excluded_ranks", [])
            and lane not in out.get("world_ranks", [lane])
            and len(out.get("world_ranks", [])) == 3
            and "sdc_conviction" in events
            and out["flight_bundle"]
            and out["loss_bitwise"]
            and not wedged
        )
    finally:
        _sdc_cleanup()
        if prev_flight is None:
            os.environ.pop(obs_flight.ENV_FLIGHT_DIR, None)
        else:
            os.environ[obs_flight.ENV_FLIGHT_DIR] = prev_flight
    return out


# ---------------------------------------------------------------------------
# registry / CLI
# ---------------------------------------------------------------------------
SCENARIOS = {
    "eviction_during_save": eviction_during_save,
    "sigkill_mid_step": sigkill_mid_step,
    "master_restart_mid_plan": master_restart_mid_plan,
    "brain_outage_mid_plan": brain_outage_mid_plan,
    "serving_crc_retry": serving_crc_retry,
    "sdc_quarantine": sdc_quarantine,
}


def run_scenario(
    name: str, seed: int = 7, workdir: Optional[str] = None
) -> Dict:
    """Run one scenario; returns its gate dict (``ok`` is the verdict).
    A replay with the same name+seed reproduces the same run."""
    if name not in SCENARIOS:
        raise ValueError(
            f"unknown scenario {name!r} (known: {sorted(SCENARIOS)})"
        )
    own_tmp = workdir is None
    workdir = workdir or tempfile.mkdtemp(prefix=f"dlrover_chaos_{name}_")
    os.makedirs(workdir, exist_ok=True)
    try:
        return SCENARIOS[name](seed, workdir)
    finally:
        if own_tmp:
            import shutil

            shutil.rmtree(workdir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser("dlrover-tpu chaos harness")
    ap.add_argument("--scenario", default="")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--json", action="store_true")
    # internal: the subprocess leg of sigkill_mid_step
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--progress", default="")
    args = ap.parse_args(argv)

    if args.worker:
        return _worker_train(args)
    if args.list:
        for name in sorted(SCENARIOS):
            print(name)
        return 0
    names = (
        sorted(SCENARIOS)
        if args.all
        else ([args.scenario] if args.scenario else [])
    )
    if not names:
        ap.print_usage()
        return 2
    results = []
    for name in names:
        res = run_scenario(name, seed=args.seed)
        results.append(res)
        if args.json:
            print(json.dumps(res))
        else:
            print(
                f"{name}: {'PASS' if res.get('ok') else 'FAIL'} "
                f"({json.dumps({k: v for k, v in res.items() if k not in ('scenario',)})})"
            )
    return 0 if all(r.get("ok") for r in results) else 1


if __name__ == "__main__":
    sys.exit(main())
