"""Per-host elastic agent: master-driven rendezvous, training-process
supervision, restart policy, membership-change handling.

Parity: dlrover/python/elastic_agent/torch/training.py:347
(``ElasticTrainingAgent`` with ``_invoke_run:548``, ``_rendezvous:389``,
``_restart_workers:652``, ``_monitor_workers``) and
``MasterRendezvousHandler:166`` — re-built from scratch for JAX (there is no
torchelastic to inherit): the agent spawns training processes with the JAX
distributed bootstrap env (coordinator address, process id, process count)
computed from the master-assigned comm world, monitors them, and implements
the goodput-critical state machine:

  HEALTHY --(proc fails)--> FAILED: report, save-at-breakpoint hook,
      restart workers (counts against max_restarts)
  HEALTHY --(num_nodes_waiting > 0)--> membership change: restart workers
      WITHOUT counting against max_restarts (training.py:606-610)
  HEALTHY --(master heartbeat action)--> restart/stop on master's order
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
from dataclasses import dataclass, field
from enum import Enum
from typing import Dict, List, Optional

from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.common import comm
from dlrover_tpu.common.constants import (
    NodeEnv,
    RendezvousName,
    TrainingExceptionLevel,
)
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.obs.trace import TimedSpan, span
from dlrover_tpu.utils.env import ensure_framework_on_pythonpath


# bound at import time: a preexec hook runs between fork and exec in a
# multithreaded parent, where an import/dlopen can deadlock on a lock
# whose owner doesn't exist in the child (subprocess docs warn exactly
# this for preexec_fn)
try:
    import ctypes

    _libc_prctl = ctypes.CDLL("libc.so.6", use_errno=True).prctl
except Exception:  # non-Linux
    _libc_prctl = None
_PR_SET_PDEATHSIG = 1


def _die_with_parent(expected_ppid: int = 0):
    """preexec hook: SIGKILL this worker if its agent dies.

    A SIGKILL'd agent (chaos, OOM-killer) cannot reap its training
    procs; orphaned workers then fight the relaunched node's workers
    for the job's shm segments and checkpoint locks and hang the job
    (found by the chaos soak). On k8s the pod cgroup provides this
    guarantee; the local/process platform needs PR_SET_PDEATHSIG.
    Linux-only; a no-op elsewhere. Only calls pre-bound symbols and
    syscalls — nothing here may allocate, import, or lock.

    Classic pdeathsig race: the parent can die between fork and prctl,
    in which case the signal never fires — so after arming it, verify
    the parent is still the process that forked us (callers bind their
    own pid into the hook before spawning) and exit if it changed.
    """
    if _libc_prctl is not None:
        _libc_prctl(_PR_SET_PDEATHSIG, signal.SIGKILL)
        if expected_ppid and os.getppid() != expected_ppid:
            os._exit(1)


def die_with_parent_hook():
    """Build a preexec_fn with the spawning process's pid bound in."""
    import functools

    return functools.partial(_die_with_parent, os.getpid())


class WorkerState(str, Enum):
    INIT = "INIT"
    HEALTHY = "HEALTHY"
    FAILED = "FAILED"
    SUCCEEDED = "SUCCEEDED"
    STOPPED = "STOPPED"


@dataclass
class WorkerSpec:
    """What to run on this host."""

    entrypoint: str  # script path, or "-m module" style handled by args
    args: List[str] = field(default_factory=list)
    nproc_per_node: int = 1
    max_restarts: int = 3
    monitor_interval: float = 3.0
    rdzv_name: str = RendezvousName.ELASTIC_TRAINING
    log_dir: str = ""
    env: Dict[str, str] = field(default_factory=dict)
    # device spec forwarded to workers ("cpu:2" for CPU-hosted tests)
    device_spec: str = ""


@dataclass
class RunResult:
    state: WorkerState
    restarts: int = 0
    message: str = ""


def _rounded(timeline: Dict[str, object]) -> Dict[str, object]:
    """A recovery's timeline as it is shown: seconds to the millisecond."""
    return {
        k: round(v, 3) if isinstance(v, float) else v
        for k, v in timeline.items()
    }


def _host_ip() -> str:
    try:
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.connect(("8.8.8.8", 53))
        ip = s.getsockname()[0]
        s.close()
        return ip
    except OSError:
        return "127.0.0.1"


class ElasticTrainingAgent:
    def __init__(
        self,
        node_rank: int,
        spec: WorkerSpec,
        client: MasterClient,
        node_id: Optional[int] = None,
    ):
        self._node_rank = node_rank
        self._spec = spec
        self._client = client
        self._node_id = node_id if node_id is not None else node_rank
        self._workers: List[subprocess.Popen] = []
        self._restart_count = 0
        self._membership_restarts = 0
        self._stop_event = threading.Event()
        self._worker_log_files: List = []
        # the port offered to the master as this host's JAX coordinator
        self._coordinator_port = comm.find_free_port()
        self._host_addr = os.getenv("DLROVER_TPU_HOST_IP", "") or _host_ip()
        self._current_world: Optional[comm.CommWorld] = None
        self._ckpt_hook = None  # set by the flash-ckpt integration

    # ------------------------------------------------------------------
    # rendezvous
    # ------------------------------------------------------------------
    def _rendezvous(self, timeout: float = 600.0) -> comm.CommWorld:
        """Join the master rendezvous and poll for the comm world.

        Parity: MasterRendezvousHandler.next_rendezvous (training.py:237).
        """
        # fresh coordinator port per rendezvous: the old one may still be
        # held in TIME_WAIT by the previous round's process 0
        self._coordinator_port = comm.find_free_port()
        self._client.register_node_addr(
            self._node_rank, f"{self._host_addr}:{self._coordinator_port}"
        )
        self._client.join_rendezvous(
            self._node_rank,
            self._spec.nproc_per_node,
            rdzv_name=self._spec.rdzv_name,
        )
        deadline = time.time() + timeout
        while time.time() < deadline and not self._stop_event.is_set():
            world = self._client.get_comm_world(
                self._spec.rdzv_name, self._node_rank
            )
            if world.world and self._node_rank in world.world:
                self._current_world = world
                logger.info(
                    f"node {self._node_rank}: joined round {world.round} "
                    f"world={sorted(world.world)} "
                    f"coordinator={world.coordinator_addr}"
                )
                return world
            time.sleep(1)
        raise TimeoutError(
            f"rendezvous {self._spec.rdzv_name} timed out on node "
            f"{self._node_rank}"
        )

    def _worker_env(
        self,
        local_rank: int,
        world: comm.CommWorld,
        timeline: Optional[Dict[str, object]] = None,
    ) -> Dict[str, str]:
        """The environment of one training process. ``timeline`` is the
        restart's record as it stands (``_restart_workers``); it rides
        to the worker with the instant of its start, so that the
        worker's own record says where the time since the death went."""
        ranks = sorted(world.world)
        base = sum(world.world[r] for r in ranks if r < self._node_rank)
        num_processes = sum(world.world.values())
        env = dict(os.environ)
        env.update(self._spec.env)
        env.update(
            {
                NodeEnv.MASTER_ADDR: self._client._master_addr,
                NodeEnv.NODE_ID: str(self._node_id),
                NodeEnv.NODE_RANK: str(self._node_rank),
                NodeEnv.NODE_NUM: str(len(ranks)),
                NodeEnv.COORDINATOR_ADDR: world.coordinator_addr,
                NodeEnv.PROCESS_ID: str(base + local_rank),
                NodeEnv.NUM_PROCESSES: str(num_processes),
                NodeEnv.RESTART_COUNT: str(self._restart_count),
                "DLROVER_TPU_LOCAL_RANK": str(local_rank),
                "DLROVER_TPU_LOCAL_WORLD_SIZE": str(
                    self._spec.nproc_per_node
                ),
                "DLROVER_TPU_RDZV_ROUND": str(world.round),
            }
        )
        if self._spec.device_spec:
            env["DLROVER_TPU_DEVICE_SPEC"] = self._spec.device_spec
        ensure_framework_on_pythonpath(env)
        # last, so that the instant is the one just before Popen
        env[NodeEnv.SPAWN_TIMELINE] = json.dumps(
            {**_rounded(timeline or {}), "t_spawn": time.monotonic()}
        )
        return env

    # ------------------------------------------------------------------
    # worker process management
    # ------------------------------------------------------------------
    def _start_workers(
        self,
        world: comm.CommWorld,
        timeline: Optional[Dict[str, object]] = None,
    ):
        self._close_log_files()
        self._workers = []
        log_dir = self._spec.log_dir
        if log_dir:
            os.makedirs(log_dir, exist_ok=True)
        for local_rank in range(self._spec.nproc_per_node):
            cmd = [sys.executable, self._spec.entrypoint, *self._spec.args]
            if log_dir:
                path = os.path.join(
                    log_dir,
                    f"worker_{self._node_rank}_{local_rank}"
                    f"_r{self._restart_count + self._membership_restarts}.log",
                )
                out = open(path, "ab")
                self._worker_log_files.append(out)
                stdout = stderr = out
            else:
                stdout = stderr = None
            proc = subprocess.Popen(
                cmd,
                env=self._worker_env(local_rank, world, timeline),
                stdout=stdout,
                stderr=stderr,
                preexec_fn=die_with_parent_hook(),
            )
            self._workers.append(proc)
        logger.info(
            f"node {self._node_rank}: started {len(self._workers)} workers "
            f"(restart {self._restart_count})"
        )

    def _stop_workers(self, timeout: float = 15.0):
        for p in self._workers:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        deadline = time.time() + timeout
        for p in self._workers:
            remaining = max(0.1, deadline - time.time())
            try:
                p.wait(timeout=remaining)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
        self._close_log_files()

    def _close_log_files(self):
        for f in self._worker_log_files:
            try:
                f.close()
            except OSError:
                pass
        self._worker_log_files = []

    def _monitor_workers(self) -> WorkerState:
        states = [p.poll() for p in self._workers]
        if any(rc is not None and rc != 0 for rc in states):
            return WorkerState.FAILED
        if all(rc == 0 for rc in states):
            return WorkerState.SUCCEEDED
        return WorkerState.HEALTHY

    def _failed_worker_info(self) -> str:
        infos = []
        for i, p in enumerate(self._workers):
            rc = p.poll()
            if rc is not None and rc != 0:
                infos.append(f"local_rank={i} exitcode={rc}")
        return "; ".join(infos)

    # ------------------------------------------------------------------
    # main loop
    # ------------------------------------------------------------------
    def run(self) -> RunResult:
        """Parity: _invoke_run training.py:548."""
        try:
            return self._run_loop()
        except BaseException:
            # never leave training processes orphaned (they would keep the
            # TPU chip locked and hang in collectives)
            self._stop_workers()
            raise

    def _run_loop(self) -> RunResult:
        spec = self._spec
        world = self._rendezvous()
        self._start_workers(world)
        last_heartbeat = 0.0
        last_poll = time.monotonic()
        while not self._stop_event.is_set():
            time.sleep(spec.monitor_interval)
            state = self._monitor_workers()
            # how long the workers went unwatched before this poll: a
            # death it finds happened somewhere in that tick
            now = time.monotonic()
            tick_s, last_poll = now - last_poll, now

            if time.time() - last_heartbeat > 15:
                last_heartbeat = time.time()
                try:
                    action = self._client.report_heartbeat()
                except ConnectionError:
                    action = ""
                if action == "stop":
                    self._stop_workers()
                    return RunResult(WorkerState.STOPPED, self._restart_count)
                if action == "restart":
                    self._restart_workers(
                        count_restart=False, reason="master_request"
                    )
                    continue

            if state == WorkerState.SUCCEEDED:
                logger.info(f"node {self._node_rank}: workers succeeded")
                return RunResult(WorkerState.SUCCEEDED, self._restart_count)

            if state == WorkerState.FAILED:
                err = self._failed_worker_info()
                logger.warning(
                    f"node {self._node_rank}: worker failure: {err}"
                )
                try:
                    self._client.report_failure(
                        err,
                        TrainingExceptionLevel.PROCESS_ERROR,
                        restart_count=self._restart_count,
                        node_rank=self._node_rank,
                    )
                except ConnectionError:
                    pass
                if self._restart_count >= spec.max_restarts:
                    self._stop_workers()
                    return RunResult(
                        WorkerState.FAILED, self._restart_count, err
                    )
                self._restart_workers(
                    count_restart=True, reason="worker_failure",
                    tick_s=tick_s,
                )
                continue

            # membership change: new nodes waiting => restart into a bigger
            # (or smaller) world; does NOT consume the restart budget
            try:
                waiting = self._client.num_nodes_waiting(spec.rdzv_name)
            except ConnectionError:
                waiting = 0
            if waiting > 0:
                logger.info(
                    f"node {self._node_rank}: membership change "
                    f"({waiting} nodes waiting); restarting workers"
                )
                self._restart_workers(
                    count_restart=False, reason="membership_change"
                )

        self._stop_workers()
        return RunResult(WorkerState.STOPPED, self._restart_count)

    def _restart_workers(
        self,
        count_restart: bool,
        reason: str = "",
        tick_s: Optional[float] = None,
    ):
        """Parity: _restart_workers training.py:652 + save-at-breakpoint
        (training.py:614-623): persist any in-memory checkpoint first.

        The restart is one ``recover`` span with a child per leg, and
        the legs' seconds go to the log as ONE line (``recovery
        timeline: {...}``) once the new workers are started: where the
        time between a death and the new worker's start went. The new
        workers are handed the same record as it stands at their start
        (``_worker_env``), and carry it on in theirs.
        ``tick_s`` is the monitor tick in which the failure was found
        (the detection's own share, spent before this call)."""
        timeline: Dict[str, object] = {
            "reason": reason, "restart": self._restart_count,
        }
        if tick_s is not None:
            timeline["detect_tick_s"] = round(tick_s, 3)
        t0 = time.monotonic()

        def leg(name):
            return TimedSpan(timeline, name + "_s")

        with span("recover", **timeline):
            if self._ckpt_hook is not None:
                with leg("persist_before_restart"):
                    try:
                        logger.info(
                            f"node {self._node_rank}: save-at-breakpoint"
                        )
                        self._ckpt_hook()
                    except Exception as e:
                        logger.warning(
                            f"save-at-breakpoint failed: {e!r}"
                        )
            logger.info(
                f"node {self._node_rank}: stopping workers for restart"
            )
            with leg("stop_workers"):
                self._stop_workers()
            logger.info(f"node {self._node_rank}: workers stopped")
            # a worker killed mid-staging leaves its shm shard lock
            # held; release orphaned locks before the new generation
            # starts saving (parity: reset_shared_memory
            # ckpt_saver.py:527)
            with leg("shm_lock_reset"):
                try:
                    from dlrover_tpu.ckpt.saver import (
                        AsyncCheckpointSaver,
                    )

                    AsyncCheckpointSaver.reset_shared_memory_if_any()
                except Exception as e:
                    logger.warning(f"shard-lock reset failed: {e!r}")
            if count_restart:
                self._restart_count += 1
            else:
                self._membership_restarts += 1
            with leg("rendezvous"):
                world = self._rendezvous()
            with leg("start_workers"):
                self._start_workers(world, timeline)
        timeline["total_s"] = time.monotonic() - t0
        logger.info(f"recovery timeline: {json.dumps(_rounded(timeline))}")

    def stop(self):
        self._stop_event.set()
        self._stop_workers()

    def set_checkpoint_hook(self, hook):
        self._ckpt_hook = hook
