"""Training-process bootstrap: wire a JAX process into the elastic job.

The TPU analog of torch's ``init_process_group`` + env:// rendezvous
(reference: the env torchelastic exports and training.py:462 rank
assignment): the agent exports ``NodeEnv`` vars computed from the
master-assigned comm world; ``init_elastic()`` consumes them and calls
``jax.distributed.initialize``. Our master owns coordinator address
assignment and restart, which is the elasticity seam JAX itself lacks
(SURVEY.md §5 "Distributed communication backend").
"""

from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass
from typing import Dict

from dlrover_tpu.common.constants import NodeEnv
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.obs.trace import TimedSpan
from dlrover_tpu.utils.device import check_devices, configure_devices
from dlrover_tpu.utils.env import framework_root


@dataclass
class ElasticContext:
    process_id: int = 0
    num_processes: int = 1
    node_rank: int = 0
    node_num: int = 1
    local_rank: int = 0
    local_world_size: int = 1
    restart_count: int = 0
    rdzv_round: int = 0
    coordinator_addr: str = ""
    master_addr: str = ""

    @property
    def is_distributed(self) -> bool:
        return self.num_processes > 1

    @property
    def in_elastic_job(self) -> bool:
        return bool(self.master_addr)


def elastic_context() -> ElasticContext:
    return ElasticContext(
        process_id=int(os.getenv(NodeEnv.PROCESS_ID, "0")),
        num_processes=int(os.getenv(NodeEnv.NUM_PROCESSES, "1")),
        node_rank=int(os.getenv(NodeEnv.NODE_RANK, "0")),
        node_num=int(os.getenv(NodeEnv.NODE_NUM, "1")),
        local_rank=int(os.getenv("DLROVER_TPU_LOCAL_RANK", "0")),
        local_world_size=int(os.getenv("DLROVER_TPU_LOCAL_WORLD_SIZE", "1")),
        restart_count=int(os.getenv(NodeEnv.RESTART_COUNT, "0")),
        rdzv_round=int(os.getenv("DLROVER_TPU_RDZV_ROUND", "0")),
        coordinator_addr=os.getenv(NodeEnv.COORDINATOR_ADDR, ""),
        master_addr=os.getenv(NodeEnv.MASTER_ADDR, ""),
    )


_initialized = False
# this process's way up, under the names ``PipelineStats`` gives it
# (``startup_import_s``, ``startup_backend_s``, ``recover_*_s``): written
# by the first ``init_elastic()``, folded in by every trainer built after
_startup: Dict[str, float] = {}


def startup_record() -> Dict[str, float]:
    return dict(_startup)


def _handed_over(now: float) -> Dict[str, float]:
    """What the agent handed this process at its start
    (``NodeEnv.SPAWN_TIMELINE``): the seconds from its ``Popen`` to
    ``now`` (the interpreter's start and every import so far) and,
    after a restart, the legs the agent had timed by then. Empty where
    no agent handed anything (a script run by hand) or what it handed
    does not parse."""
    try:
        handed = json.loads(os.getenv(NodeEnv.SPAWN_TIMELINE, ""))

        def seconds(*legs):
            return sum(float(handed.get(leg, 0.0)) for leg in legs)

        return {
            "startup_import_s": now - float(handed["t_spawn"]),
            "recover_detect_tick_s": seconds("detect_tick_s"),
            "recover_persist_s": seconds("persist_before_restart_s"),
            "recover_respawn_s": seconds(
                "stop_workers_s", "shm_lock_reset_s", "rendezvous_s"
            ),
        }
    except (ValueError, TypeError, KeyError):
        return {}


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache.

    The elasticity hard part SURVEY.md §7 calls out: a restarted worker's
    first step recompiles the whole train program (tens of seconds to
    minutes at scale) — pure goodput loss. With the persistent cache, a
    restart into the SAME world size replays the compiled executable from
    disk, and each previously-seen world size after a scale event is a
    cache hit too (entries are keyed on the program, which includes mesh
    shape).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that
    directory and no other is set here. Otherwise the cache lives at one
    fixed path inside the checkout: the path is part of what a restarted
    worker must find again, so it is never a temporary name. Returns the
    directory in use, "" when disabled via
    ``DLROVER_TPU_COMPILE_CACHE=off``.
    """
    if os.getenv("DLROVER_TPU_COMPILE_CACHE", "") == "off":
        return ""
    import jax

    cache_dir = os.getenv("JAX_COMPILATION_CACHE_DIR", "")
    if not cache_dir:
        cache_dir = os.path.join(framework_root(), ".compile_cache")
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache everything that took meaningful compile time, not only the
    # multi-minute programs (defaults skip sub-second compiles)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


def init_elastic(timeout_secs: int = 300) -> ElasticContext:
    """Configure devices and join the JAX distributed system, once per
    process (later calls only return the context).

    The one place a training process is set up before it touches a
    device: ``ElasticTrainer`` calls it, and a script that uses JAX
    before it builds a trainer calls it first. Safe for single-process
    jobs (no distributed init). Fast re-init after a restart is just
    process re-exec + this call — the agent already re-assigned
    ``process_id``/``coordinator_addr`` for the new world; the
    persistent compilation cache turns the post-restart recompile into
    a disk read.

    It also times this process's way up, once: the seconds since the
    agent started it (``startup_import_s``; 0 where no agent did) and
    the ``backend_up`` span around what brings the backend up
    (``startup_backend_s``), beside the restart's legs the agent handed
    over. ``startup_record()`` keeps them for the trainer's
    ``PipelineStats``.
    """
    global _initialized
    ctx = elastic_context()
    if _initialized:
        return ctx
    _startup.clear()
    _startup.update(_handed_over(time.monotonic()))
    with TimedSpan(_startup, "startup_backend_s", name="backend_up"):
        # configuration first, nothing here may bring the backend up:
        # jax.distributed.initialize refuses to run after it
        configure_devices()  # honors DLROVER_TPU_DEVICE_SPEC
        enable_compile_cache()
        if ctx.is_distributed:
            import jax

            logger.info(
                f"jax.distributed.initialize(coordinator="
                f"{ctx.coordinator_addr}, n={ctx.num_processes}, "
                f"id={ctx.process_id})"
            )
            jax.distributed.initialize(
                coordinator_address=ctx.coordinator_addr,
                num_processes=ctx.num_processes,
                process_id=ctx.process_id,
                initialization_timeout=timeout_secs,
            )
        check_devices()  # the backend may come up now
    _initialized = True
    return ctx
