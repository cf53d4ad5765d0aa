"""ElasticTrainer: the user-facing training loop.

Parity: dlrover/trainer/torch/elastic/trainer.py:48 (ElasticTrainer
wrapping model/optimizer/dataloader for elasticity) and ATorch's
HF-style ``AtorchTrainer`` (atorch/trainer/atorch_trainer.py:127). One
facade owns the full elastic story so a user train script collapses to
~30 lines:

- strategy: an explicit ``Strategy`` or the auto_accelerate search picks
  the mesh/remat/microbatching (donation off — flash staging reads the
  state after the step);
- data: ``ElasticDataLoader`` + ``ElasticDistributedSampler`` (resumes
  mid-epoch across world-size changes, honors master-retuned batch size);
- checkpoint: flash save every ``save_memory_interval`` steps (ms-scale,
  shm), persisted every ``save_storage_interval`` steps; sampler state
  rides the train state so restore is exactly-once over the data;
- monitoring: every step publishes the global step for the agent's
  TrainingMonitor (feeds master hang detection / auto-scaling).
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import numpy as np

from dlrover_tpu.accel.accelerate import AccelerateResult, auto_accelerate
from dlrover_tpu.accel.strategy import Strategy
from dlrover_tpu.agent.monitor import report_runtime_metrics
from dlrover_tpu.common import faults, trace_counts
from dlrover_tpu.ckpt.checkpointer import FlashCheckpointer, StorageType
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.models.config import TransformerConfig
from dlrover_tpu.models.train import (
    fold_diffusion_report,
    fold_exit_report,
    shard_batch,
)
from dlrover_tpu.obs.flight_recorder import (
    ProfilerCapture,
    default_recorder,
)
from dlrover_tpu.obs.audit import (
    StepAuditor,
    StepBudget,
    install_default_auditor,
    load_audit_calibration,
)
from dlrover_tpu.obs.goodput import GoodputLedger, install_default_ledger
from dlrover_tpu.obs.metrics import default_registry, fold_pipeline_stats
from dlrover_tpu.obs.trace import SpanHeartbeat, span
from dlrover_tpu.parallel import transfer_sched
from dlrover_tpu.parallel.moe import fold_routing_report
from dlrover_tpu.trainer.elastic.dataloader import ElasticDataLoader
from dlrover_tpu.trainer.elastic.evaluation import Evaluator
from dlrover_tpu.trainer.elastic.optimizer import build_optimizer  # noqa: F401
from dlrover_tpu.trainer.elastic.sampler import ElasticDistributedSampler
from dlrover_tpu.trainer.elastic.sdc_fence import SdcFence
from dlrover_tpu.trainer.elastic.step_programs import (
    StepPrograms,
    aot_supported,
)


# what ``_first_build`` hands back once a program is built
_NO_BUILD = contextlib.nullcontext()
# the eviction drain skips its DISK persist when less of the grace window
# remains after the shm commit: the agent's shm handoff covers it
_EVICTION_PERSIST_FLOOR_S = 5.0
# wall-clock cap per candidate batch of the speculative compiler's thread
_SPEC_COMPILE_BUDGET_S = 120.0


@dataclass
class TrainerConfig:
    batch_size: int = 8
    seq_len: int = 128
    ckpt_dir: str = ""
    save_memory_interval: int = 50
    save_storage_interval: int = 500
    report_metrics: bool = True
    log_interval: int = 10
    # eval loop: 0 disables; otherwise run ``eval_steps`` batches of the
    # eval dataset every ``eval_interval`` optimizer steps
    eval_interval: int = 0
    eval_steps: int = 50
    # >1: split each batch into K sequential microbatches per optimizer
    # update (batch_size must divide by K)
    grad_accum: int = 1
    # save-strategy / early-stop hooks (ref atorch_trainer.py save_
    # strategy + EarlyStoppingCallback): save_best persists the best-
    # eval checkpoint to its OWN directory (ckpt_dir/best — the
    # periodic saves must never supersede it) with the best loss in a
    # sidecar so restarts don't regress it; early_stopping_patience
    # stops training after that many consecutive evals without
    # improvement (0 = never). Both need eval_interval + eval_dataset.
    save_best: bool = False
    # best-saves block on the disk commit; during the steep-improvement
    # phase evals improve every time, so persist at most this often
    save_best_min_interval_s: float = 60.0
    early_stopping_patience: int = 0
    # -- overlapped host<->device pipeline -----------------------------
    # device prefetch depth (0 disables): a producer thread pulls batch
    # N+1 from the dataloader and places it on device while batch N
    # computes; 2 = classic double buffering
    prefetch: int = 2
    # in-memory flash saves stage device->shm in fixed-size chunks
    # interleaved between steps instead of one big drain (the commit
    # barrier is the only blocking point)
    stage_chunk_mb: int = 64
    # critical-path budget per step for draining stage chunks
    stage_budget_ms: float = 5.0
    # run the state+input-donating train step whenever no checkpoint
    # staging is reading the state buffers (HBM reuse; the safe
    # non-donating twin runs while staging is in flight)
    donation_aware: bool = True
    # -- elastic-resize fast path --------------------------------------
    # pre-lower the train step for the master's predicted next world
    # sizes (candidate_worker_counts in the paral config) on a
    # background thread, so the resize that lands finds its executable
    # already in the compile cache
    speculative_compile: bool = True
    # -- overlap-scheduled gradient sync (parallel/grad_sync.py) -------
    # bucketed per-bucket collectives under shard_map (pure-dp RS+AG,
    # dp x fsdp ZeRO reduce-scatter into the shard layout, dp x tp/sp
    # bucketed dp sync under the GSPMD submesh): independent
    # collectives XLA can overlap with backward compute, and
    # grad_accum syncs once per optimizer step
    comm_overlap: bool = False
    # "none" | "int8" | "int8_topk" | "auto": compressed collective
    # payloads with error feedback (implies comm_overlap's explicit
    # sync path; dp/fsdp plans only — tp plans run uncompressed).
    # "int8_topk" also ships only the top-k blocks of the cross-slice
    # DCN shard; "auto" resolves per mesh from the measured ICI:DCN
    # ratio (grad_sync.resolve_auto_compress)
    grad_compress: str = "none"
    # requested DCN block density under int8_topk/auto
    grad_topk_density: float = 0.25
    # target sync bucket size, MiB; 0 = auto-size per link from the
    # measured topology.LinkModel (DCN-leg target on multi-slice
    # meshes, ICI otherwise)
    grad_bucket_mb: int = 4
    # micro-batch rebalance on indivisible worker counts (ISSUE 13):
    # instead of idling surplus ranks, pad the batch with zero-weight
    # rows so it divides over ALL ranks — the dry-runner prices both
    # options (accel/dry_runner.price_rebalance_options) and the
    # cheaper wins; the pads land on the trailing ranks (the elastic
    # data layer's slice_throughput_weights dealing already skews the
    # REAL rows toward the faster slices). grad_accum>1 keeps the
    # idle-ranks behavior (pads would multiply across microbatches).
    mb_rebalance: bool = True
    # -- eviction grace-window drain -----------------------------------
    # default grace window (seconds) for an eviction notice that does
    # not carry its own (SIGTERM, an `evict` command with arg=0);
    # DLROVER_TPU_EVICTION_DEADLINE_S overrides at construction
    eviction_grace_s: float = 30.0
    # -- silent-data-corruption defense (parallel/sdc.py, ISSUE 20) ----
    # tier-1 fence: per-lane local grad norms ride the sync out-spec
    # and a robust median+MAD detector classifies each step (data
    # spike: skip-and-log; device suspect: escalate to the paired
    # audit probe; conviction: verified rollback + quarantine halt).
    # DLROVER_TPU_SDC=1 enables without the knob; explicit dp-family
    # sync plans only (comm_overlap/grad_compress — the per-lane
    # vector falls out of the bucket walk there); thresholds: parallel/
    # sdc.SdcConfig; DLROVER_TPU_SDC_AUDIT_STEPS=N audits every N steps
    sdc_detect: bool = False


class ElasticTrainer:
    """The elastic training loop over one model, optimizer and dataset.

    ``metrics_hook(step, metrics)``, when given, is called once per
    optimizer step, in order, from the train thread. The loop keeps one
    step in flight (it dispatches step N+1 before it waits for step N),
    so at the call ``trainer.state`` is step ``step``'s state
    (``int(trainer.state.step) == step``) and ``metrics`` that step's
    arrays, and neither has been computed yet: a hook that stores them
    costs nothing, a hook that reads them (``float(metrics["loss"])``,
    a digest of ``trainer.state``) waits for that step and loses the
    overlap for it, nothing else. Exactly one device step completes
    between two calls. The hook is called again after an eval pass with
    the eval scalars (no ``"loss"`` key). An exception it raises ends
    ``train()`` at that step boundary. ``docs/observability.md`` has the
    whole contract."""

    def __init__(
        self,
        model_cfg: TransformerConfig,
        tx,
        dataset,
        trainer_cfg: Optional[TrainerConfig] = None,
        strategy: Optional[Strategy] = None,
        devices=None,
        collate_fn: Optional[Callable] = None,
        metrics_hook: Optional[Callable[[int, Dict], None]] = None,
        eval_dataset=None,
    ):
        import jax

        from dlrover_tpu.trainer.elastic.distributed import (
            init_elastic,
            startup_record,
        )

        # before anything touches a device: the agent's device spec
        # (asking for the chip and not getting it is an error), the
        # persistent compile cache, so a restarted worker does not
        # recompile, and the distributed system. A no-op where the
        # script has called it already
        init_elastic()
        from dlrover_tpu.accel.profiler import (
            compile_meter,
            install_profiler_mirror,
        )

        # this process holds the chip: its spans get their twin on the
        # profiler's clock, so whoever starts jax.profiler later (the
        # master's `profile` command, a benchmark) finds the host's
        # spans beside the device's operations in one trace
        install_profiler_mirror()
        # build:<what> spans bracket every site that may compile, with
        # XLA's compile seconds and the persistent cache's hits and
        # misses across each; the list is logged at the end of __init__
        # (the steps' own programs build at their first call in train())
        self._builds = compile_meter()
        self._builds_first = self._builds_logged = len(self._builds.builds)
        self._built: set = set()
        # common/trace_counts as a train step's build began
        self._counts_before_step = None
        self.tcfg = trainer_cfg or TrainerConfig()
        self._metrics_hook = metrics_hook
        # kept for the resize path: a new mesh rebuilds the accel
        # artifacts from the SAME model config and optimizer
        self._model_cfg = model_cfg
        self._tx = tx
        # SDC defense must be switched on BEFORE the step is built:
        # build_train_step reads the module switch at trace time to
        # decide whether the per-lane norm vector rides the sync (the
        # module-level switch covers the donating twin, the dry-runner
        # and resize rebuilds consistently — no signature threading)
        if self.tcfg.sdc_detect:
            from dlrover_tpu.parallel import sdc as _sdc

            _sdc.set_enabled(True)
        # async flash staging reads state buffers after the step returns,
        # so the production step must NOT donate them
        with self._builds.build("strategy"):
            self.accel: AccelerateResult = auto_accelerate(
                model_cfg,
                tx,
                batch=self.tcfg.batch_size,
                seq=self.tcfg.seq_len,
                devices=devices,
                strategy=strategy,
                donate=False,
                grad_accum=self.tcfg.grad_accum,
                optimizations=self._grad_sync_opt_names(),
                # bucket size only when the trainer's knobs own the
                # sync config — an explicit Strategy's own
                # grad_bucket_mb wins otherwise
                grad_bucket_mb=(
                    self.tcfg.grad_bucket_mb
                    if self._grad_sync_opt_names()
                    else None
                ),
            )
        self.cfg = self.accel.cfg
        self.mesh = self.accel.mesh
        from dlrover_tpu.accel.profiler import PipelineStats

        self.pipeline_stats = PipelineStats()
        # this process's way up as init_elastic() timed it, and the
        # restart's legs the agent handed it
        self.pipeline_stats.set_startup(startup_record())
        # which program runs a step, and the compile cache behind them
        self._programs = StepPrograms(
            self.accel, self.pipeline_stats, self.tcfg.donation_aware
        )
        self._spec_compiler = None
        self._last_candidates = None
        self._prefetcher = None
        self._stager = None
        # -- unified telemetry (obs/): spans + metrics registry --------
        self._registry = default_registry()
        self._step_time_hist = self._registry.histogram(
            "dlrover_step_time_seconds", "optimizer-step wall time"
        )
        self._step_time_sum = 0.0
        self._step_time_n = 0
        self._train_tid: Optional[int] = None
        # hang attribution: a background heartbeat publishes the train
        # thread's current open span into the runtime-metrics file even
        # while the loop is wedged inside one (obs/trace.SpanHeartbeat →
        # agent TrainingMonitor → master hang report)
        self._span_heartbeat = (
            SpanHeartbeat(tid_fn=lambda: self._train_tid)
            if self.tcfg.report_metrics
            else None
        )
        if self._span_heartbeat is not None:
            self._span_heartbeat.start()
        # -- goodput ledger + crash forensics (obs/goodput, obs/
        # flight_recorder): every second of this trainer's wall time is
        # attributed to the closed taxonomy and exported at log
        # cadence; the flight recorder dumps a bundle on crash, hang
        # (its own watchdog thread) or degraded-mode entry, and the
        # master can request dumps/profiles via the command file
        self._goodput = install_default_ledger(
            GoodputLedger(tid_fn=lambda: self._train_tid)
        )
        # step-budget auditor (obs/audit): reconciles the pricing
        # side's per-component StepBudget against the span stream each
        # step — drift reprices, sustained regressions alarm with the
        # component named and a flight bundle captured
        self._auditor = install_default_auditor(
            StepAuditor(
                tid_fn=lambda: self._train_tid,
                on_alarm=self._on_audit_alarm,
            )
        )
        self._replay_until_step: Optional[int] = None
        self._flight = default_recorder()
        self._flight.set_identity(
            node_id=int(os.getenv("DLROVER_TPU_NODE_ID", "0") or 0),
            job_name=os.getenv("DLROVER_TPU_JOB_NAME", ""),
            mesh=str(self.accel.strategy.mesh.axis_sizes()),
            model=type(model_cfg).__name__,
        )
        if self.tcfg.report_metrics:
            self._flight.start_watchdog(
                hang_dump_after_s=float(
                    os.getenv("DLROVER_TPU_HANG_DUMP_AFTER_S", "120")
                ),
                tid_fn=lambda: self._train_tid,
            )
        self._profiler_capture = ProfilerCapture()
        # the command file outlives a worker restart, but its commands
        # target the PREVIOUS incarnation (dump THAT process, profile
        # THAT hang) — start past them instead of replaying stale
        # forensics against a healthy fresh process
        from dlrover_tpu.agent.monitor import last_command_id

        self._last_command_id = last_command_id()
        # -- eviction grace-window drain -------------------------------
        # a preemption notice (SIGTERM / env deadline / master `evict`
        # command) flips the event; the train loop drains at the next
        # step boundary: finish the step, emergency shm checkpoint,
        # report + flush forensics, exit clean (docs/fault-injection.md)
        env_grace = os.getenv("DLROVER_TPU_EVICTION_DEADLINE_S", "")
        if env_grace:
            try:
                self.tcfg.eviction_grace_s = float(env_grace)
            except ValueError:
                logger.warning(
                    f"bad DLROVER_TPU_EVICTION_DEADLINE_S={env_grace!r};"
                    f" keeping {self.tcfg.eviction_grace_s}s"
                )
        self._evict_event = threading.Event()
        self._evict_deadline: Optional[float] = None  # monotonic
        self._evict_grace_s = 0.0
        self._evict_reason = ""
        self.evicted = False
        self.eviction_drain_ms = 0.0
        # event-reporter seam (the PR-5 saver pattern): in the agent
        # architecture the monitor file carries the notice; in-process
        # callers (chaos harness, tests) wire this to
        # MasterClient.report_failure / report_eviction_notice directly
        self._event_reporter: Optional[Callable[[str, str], None]] = None
        if env_grace:
            # a platform that exports the deadline env expects SIGTERM
            # to mean "drain now" — install the handler automatically
            self.install_eviction_handler()
        with self._builds.build("init"):
            self.state = self.accel.init_fn(jax.random.PRNGKey(0))
        self._grad_sync_plan = None
        # measured link-cost model (parallel/topology.py): probe once
        # per device fingerprint (warm restarts hit the JSON cache);
        # the dry-runner and the auto bucket sizer price wire time
        # from it instead of the flat-ICI constant
        self._link_fp: Optional[str] = None
        with self._builds.build("link_probe"):
            self._setup_link_model()
        with self._builds.build("grad_sync"):
            self._setup_grad_sync()
        self.sampler = ElasticDistributedSampler(
            len(dataset), shuffle=True
        )
        # the SDC fence (parallel/sdc.py, ISSUE 20) on this world's lanes
        self._sdc = SdcFence(
            self._grad_sync_plan, self.mesh, self.sampler,
            rollback=self._sdc_rollback, requested=self.tcfg.sdc_detect,
        )
        self._audit_cal_loaded = False
        self._setup_audit_budget()
        from dlrover_tpu.ops.quantized_optim import int8_moments_on

        # where the state's int8 moments lie, if it has any
        stats = self.pipeline_stats
        stats.opt_q8_tiles_elems, stats.opt_q8_blocks_elems = (
            int8_moments_on(self.state.opt_state, self.accel.strategy.mesh)
        )
        self.dataloader = ElasticDataLoader(
            dataset,
            batch_size=self.tcfg.batch_size,
            sampler=self.sampler,
            collate_fn=collate_fn,
        )
        self._ckptr: Optional[FlashCheckpointer] = None
        if self.tcfg.ckpt_dir:
            self._ckptr = FlashCheckpointer(self.tcfg.ckpt_dir)
            # the restore's own programs (the packed put's unpack, the
            # residual's zeros) build here
            with self._builds.build("restore"):
                self._maybe_restore()
        # evaluation, best-eval checkpoint (made after the restore), early stop
        self._eval = Evaluator(
            self.tcfg, eval_dataset, collate_fn,
            place=lambda batch: self._device_batch(batch, for_eval=True),
            first_build=self._first_build, ckpt_state=self._ckpt_state,
        )
        self._log_builds("at start")

    def _log_builds(self, when: str):
        """One line for the build:<what> spans since the last such line:
        what this incarnation compiled, and what the cache gave it."""
        from dlrover_tpu.accel.profiler import describe_builds

        rows = self._builds.builds[self._builds_logged:]
        self._builds_logged = len(self._builds.builds)
        if rows:
            self._fold_first_step()
            # beside the build that made the state: where its int8
            # moments lie (ops/quantized_optim.py), if it has any
            tiles = self.pipeline_stats.opt_q8_tiles_elems
            blocks = self.pipeline_stats.opt_q8_blocks_elems
            q8 = (
                f"; int8 moments: {tiles} elements in tiles, "
                f"{blocks} in blocks"
                if tiles + blocks and any(b["what"] == "init" for b in rows)
                else ""
            )
            logger.info(
                f"programs built {when}: {describe_builds(rows)}{q8}"
                f"{self._fold_trace_counts()}"
            )

    def _fold_first_step(self):
        """This trainer's build rows up to and including its first
        step's into the stats (``startup_first_step_s``,
        ``startup_compile_s``, ``startup_cache_misses``): what the way
        to the first step compiled, and what the cache gave it. Nothing
        before a step has built; the same numbers ever after."""
        upto = []
        for row in self._builds.builds[self._builds_first:]:
            upto.append(row)
            if row["what"] in ("step_donating", "step_safe"):
                break
        else:
            return
        stats = self.pipeline_stats
        stats.startup_first_step_s = upto[-1]["seconds"]
        stats.startup_compile_s = sum(
            b["compile_s"] + b["retrieval_s"] for b in upto
        )
        stats.startup_cache_misses = int(sum(b["cache_misses"] for b in upto))

    def _fold_trace_counts(self) -> str:
        """What the modules counted while programs were traced
        (``common/trace_counts``: a counter's name is the stats field it
        lands in, its two scopes are stated there) into the stats, and
        what this line adds, in words: ``+n`` a running total's growth
        since the last such line, ``=n`` a count of the train step built
        since. Of the latter nothing where no step was built since the
        last line, or the step came whole out of a cache of executables
        (not traced)."""
        stats = self.pipeline_stats
        now = trace_counts.snapshot()
        said = []
        for name in trace_counts.RUNNING_TOTALS:
            # the field is the total as the last line left it
            if now[name] != getattr(stats, name):
                said.append(f"{name} +{now[name] - getattr(stats, name)}")
                setattr(stats, name, now[name])
        before, self._counts_before_step = self._counts_before_step, None
        if before is not None:
            step = {
                name: n for name, n in trace_counts.since(before).items()
                if name not in trace_counts.RUNNING_TOTALS
            }
            if any(step.values()):
                for name, n in step.items():
                    setattr(stats, name, n)
                said += [f"{name} ={n}" for name, n in step.items() if n]
        return "; traced: " + ", ".join(said) if said else ""

    def _first_build(self, what: str):
        """``build:<what>`` around the FIRST call of a jitted program
        (jit compiles, or loads from the cache, inside that call);
        afterwards nothing: no span, no lookup beyond one set test."""
        if what in self._built:
            return _NO_BUILD
        self._built.add(what)
        if what.startswith("step_"):
            self._counts_before_step = trace_counts.snapshot()
        return self._builds.build(what)

    # -- measured link-cost model (parallel/topology.py) ----------------
    def _setup_link_model(self):
        """Probe (or reuse) the per-link bandwidth model for the
        CURRENT device world. Called at startup and after every
        resize; the probe itself runs only when the device fingerprint
        actually changed (docs/elastic-resize.md invalidation rule) —
        a resize back onto the same hardware, and any warm restart,
        reuses the persisted cache without touching the devices."""
        from dlrover_tpu.parallel import topology

        try:
            devices = list(self.mesh.devices.flatten())
            fp = topology.device_fingerprint(devices)
            if fp == self._link_fp:
                logger.info(
                    f"link model: device fingerprint unchanged ({fp}),"
                    f" keeping the current probe"
                )
                return
            model = topology.probe_link_model(
                mesh_config=self.accel.strategy.mesh, devices=devices
            )
            self._link_fp = fp
            topology.export_link_metrics(model, self._registry)
            # same fingerprint discipline for the arbiter calibration:
            # measure (or reuse) the per-rail hidden fraction so the
            # dry-runner prices host traffic from observation instead
            # of the documented constant
            transfer_sched.ensure_calibrated()
        except Exception as e:  # the probe must never kill training
            logger.warning(f"link-model probe failed: {e!r}")

    def apply_slice_throughput(self, step_times_s) -> None:
        """Heterogeneous per-slice data weighting (arXiv 2602.18007):
        per-slice step times → normalized throughput weights → unequal
        per-replica shards in the elastic sampler (a slice twice as
        fast consumes twice the data, so the fast slices stop waiting
        at the sync point). ``step_times_s``: one entry per DCN slice,
        e.g. from the master's straggler attribution. No-op (reset to
        equal shards) when the mesh has no multi-slice structure."""
        from dlrover_tpu.parallel import topology

        slices = self.accel.strategy.mesh.dp_slices()
        reps = self.sampler.num_replicas
        if slices <= 1 or len(step_times_s) != slices or reps % slices:
            # NOT silent: the in-process trainer's own sampler is
            # single-replica (one process consumes the whole global
            # batch — there are no per-replica shards to reweight;
            # multi-worker data planes construct per-rank samplers and
            # call set_throughput_weights on those), and a mismatched
            # slice count means the caller's view of the mesh is stale
            if slices > 1:
                logger.warning(
                    f"slice throughput weighting not applied: "
                    f"{slices} slices, {len(step_times_s)} step times, "
                    f"{reps} sampler replicas (need len(times) == "
                    f"slices and slices | replicas); resetting to "
                    f"equal shards"
                )
            self.sampler.set_throughput_weights(None)
            return
        w = topology.slice_throughput_weights(step_times_s)
        per = reps // slices
        # replicas are slice-major (mesh.py hybrid dp layout): replica
        # r lives in slice r // per and splits its slice's share evenly
        self.sampler.set_throughput_weights(
            [w[r // per] / per for r in range(reps)]
        )
        logger.info(
            f"slice throughput weights applied: {[round(x, 3) for x in w]}"
        )

    # -- overlap-scheduled gradient sync -------------------------------
    def _grad_sync_opt_names(self) -> tuple:
        """Named optimizations the trainer's grad-sync knobs translate
        to (accel/opt_lib.py) — stamped onto the explicit strategy or
        every search candidate by ``auto_accelerate``."""
        names = ()
        if self.tcfg.comm_overlap:
            names += ("comm_overlap",)
        if self.tcfg.grad_compress == "auto":
            names += ("grad_compress_auto",)
        elif self.tcfg.grad_compress != "none":
            names += ("grad_compress",)
        return names

    def _setup_grad_sync(self, measure: bool = True):
        """(Re)plan the bucketed sync for the CURRENT mesh: resolve the
        plan, attach the error-feedback residual when compressing, and
        surface the plan's wire accounting through PipelineStats. A
        resize re-runs this — bucket padding and the residual's shapes
        depend on the dp degree, so the plan is per-world —
        with ``measure=False``: the timing probe compiles a standalone
        sync program, which must not ride the resize downtime window."""
        from dlrover_tpu.parallel.grad_sync import (
            ensure_residual,
            estimate_overlap_pct,
            export_compress_metrics,
            measure_sync_legs_ms,
            measure_sync_ms,
            resolve_plan,
        )

        plan = resolve_plan(self.cfg, self.accel.strategy)
        self._grad_sync_plan = plan
        stats = self.pipeline_stats
        # the chosen path is visible state, not an HLO-only fact: the
        # metrics registry (grad_sync_explicit gauge via
        # fold_pipeline_stats) sees a mesh losing the fast path
        stats.grad_sync_path = "explicit" if plan is not None else "gspmd"
        # mode/density gauges cover the plan-None case too (mode 0 =
        # uncompressed GSPMD), so a downgrade is visible as a gauge
        # step-change rather than a silently missing series
        export_compress_metrics(plan, self._registry)
        if plan is None:
            # resolve_plan already emitted the once-per-mesh fallback
            # log when the explicit path was requested — the single
            # gate owns that visibility
            return
        self.state = ensure_residual(self.state, plan, self.mesh)
        stats.grad_bytes_raw = plan.raw_bytes
        stats.grad_bytes_wire = plan.wire_bytes
        stats.comm_overlap_pct = estimate_overlap_pct(
            self.accel.strategy
        )
        if measure:
            try:
                # the sync's standalone roofline (one small compile;
                # the in-step cost is this minus what the scheduler
                # overlaps), split per link class for two-level plans.
                # Two-level: the legs probe already times the full
                # sync for its "all" leg — reuse ici+dcn as the total
                # instead of compiling and timing it a second time
                if plan.two_level:
                    stats.grad_sync_ici_ms, stats.grad_sync_dcn_ms = (
                        measure_sync_legs_ms(plan, self.mesh, iters=3)
                    )
                    stats.grad_sync_ms = (
                        stats.grad_sync_ici_ms + stats.grad_sync_dcn_ms
                    )
                else:
                    stats.grad_sync_ms = measure_sync_ms(
                        plan, self.mesh, iters=3
                    )
                    stats.grad_sync_ici_ms = stats.grad_sync_ms
                    stats.grad_sync_dcn_ms = 0.0
            except Exception as e:
                logger.warning(
                    f"grad-sync timing probe failed: {e!r}"
                )
        logger.info(f"grad sync: {plan.describe()}")

    # -- step-budget audit (obs/audit.py) -------------------------------
    def _setup_audit_budget(self):
        """Assemble the per-component :class:`StepBudget` for the
        CURRENT world and hand it to the auditor. Called at startup and
        after every resize (the ici/dcn split and the host-transfer
        demand are per-world facts). Components the trainer cannot
        price cheaply (compute, data_wait) stay 0.0 — the auditor
        adopts their warmup-mean observation as the budget instead."""
        import jax

        from dlrover_tpu.parallel.grad_sync import (
            OVERLAP_HIDDEN_FRACTION,
            comm_time_legs_s,
        )

        try:
            if not self._audit_cal_loaded and self._link_fp:
                # warm restart on the same hardware: start from the
                # persisted per-component drift instead of re-learning
                cal = load_audit_calibration(self._link_fp)
                if cal is not None:
                    self._auditor.apply_calibration(cal)
                self._audit_cal_loaded = True
            budget = StepBudget()
            param_bytes = 0
            itemsize = 4
            for x in jax.tree_util.tree_leaves(self.state.params):
                if hasattr(x, "dtype"):
                    param_bytes += x.size * x.dtype.itemsize
                    itemsize = x.dtype.itemsize
            ici_s, dcn_s = comm_time_legs_s(
                param_bytes,
                self.accel.strategy,
                grad_itemsize=itemsize,
            )
            # the explicit bucketed path overlaps most of the wire time
            # behind compute; only the exposed remainder is step time
            exposed = (
                1.0 - OVERLAP_HIDDEN_FRACTION
                if self._grad_sync_plan is not None
                else 1.0
            )
            budget.set_component("ici_sync", ici_s * exposed, "priced")
            budget.set_component("dcn_sync", dcn_s * exposed, "priced")
            budget.set_component(
                "host_xfer",
                transfer_sched.aggregate_host_exposed_s(),
                "priced",
            )
            self._auditor.set_budget(budget)
            # the sync legs run inside the jitted step (no per-step
            # spans) — feed the probe-measured wall times as the
            # standing observation for those components
            stats = self.pipeline_stats
            if stats.grad_sync_ici_ms:
                self._auditor.set_measured(
                    "ici_sync", stats.grad_sync_ici_ms / 1e3 * exposed
                )
            if stats.grad_sync_dcn_ms:
                self._auditor.set_measured(
                    "dcn_sync", stats.grad_sync_dcn_ms / 1e3 * exposed
                )
        except Exception as e:
            logger.warning(f"audit budget assembly failed: {e!r}")

    def _on_audit_alarm(self, component: str, ratio: float, detail: str):
        """Sustained regression: capture forensics at the moment the
        detector fires, and leave a breadcrumb in the recorder's event
        log so later dumps carry the attribution too."""
        self._flight.note_event("audit_regression", detail)
        self._flight.dump(
            "audit_regression",
            extra={
                "component": component,
                "ratio": round(ratio, 3),
                "detail": detail,
            },
        )

    # -- silent-data-corruption defense (parallel/sdc.py, ISSUE 20) ----
    # what the fence found, under the names tools/chaos.py reads
    @property
    def sdc_convicted(self) -> tuple:
        return self._sdc.convicted

    @property
    def sdc_detect_step(self) -> Optional[int]:
        return self._sdc.detect_step

    @property
    def _sdc_halt(self) -> bool:
        return self._sdc.halt

    def _sdc_rollback(self, step: int, evidence: Dict) -> int:
        """What a conviction asks of the trainer: the ``sdc_conviction``
        event to the master/Brain, then the verified rollback (downtime
        booked to ``restart_replay``). Returns its step, -1 for none."""
        from dlrover_tpu.parallel.grad_sync import ensure_residual

        if self._event_reporter is not None:
            try:
                self._event_reporter("sdc_conviction", json.dumps(evidence))
            except Exception as e:
                logger.warning(f"sdc conviction report failed: {e!r}")
        # PR-19 interop: the rollback stall and the replayed window are
        # deliberate — the hang watchdog must not dump a bundle for
        # them, and the step auditor must not reconcile pre-rollback
        # spans against the post-rollback budget
        self._flight.suppress_watchdog(120.0)
        rolled_to = -1
        if self._ckptr is not None:
            self._goodput.replay_begin()
            try:
                tgt, restored = self._load_checkpoint(
                    self._ckpt_state()
                )
                if restored is not None and tgt >= 0:
                    self.state = ensure_residual(
                        restored["train"], self._grad_sync_plan, self.mesh
                    )
                    self.sampler.load_state_dict(restored["sampler"])
                    rolled_to = tgt
                    lost = max(0, step - tgt)
                    self._registry.gauge(
                        "dlrover_sdc_rollback_steps_lost",
                        "steps discarded by the last SDC rollback",
                    ).set(lost)
                else:
                    logger.error(
                        "sdc conviction: no verified checkpoint to "
                        "roll back to — halting with corrupt state "
                        "DISCARDED by the restart"
                    )
            finally:
                self._goodput.replay_end()
        # the auditor's recorded spans are the pre-rollback incarnation's
        self._auditor.skip_to_now()
        return rolled_to

    # -- checkpoint ----------------------------------------------------
    def _rewound_sampler_state(self, samp: Dict, buffered: int) -> Dict:
        """Sampler state rewound by ``buffered`` prefetched batches: the
        prefetcher's source cursor ran ahead of what actually trained,
        so a restore (or a resize that drops the buffer) must replay
        those batches instead of skipping them."""
        samp = dict(samp)
        # owned samples to replay; the sampler converts to global
        # positions per its dealing mode (equal round-robin vs
        # throughput-weighted)
        completed = self.sampler.rewound_completed(
            samp["completed_num"],
            buffered * self.dataloader.batch_size,
        )
        if completed < 0 and samp["epoch"] > 0:
            # the sampler already rolled over (its iterator exhausts
            # depth batches before the consumer does) but the buffered
            # epoch-tail has not trained: rewind ACROSS the rollover,
            # or a restore would skip it
            samp["epoch"] -= 1
            completed += self.sampler._epoch_total()
        # a short final batch makes the rewind an over-estimate;
        # clamping repeats a few samples, which is the safe direction
        # (never skip)
        samp["completed_num"] = max(0, completed)
        return samp

    def _ckpt_state(self):
        from dlrover_tpu.parallel.grad_sync import strip_residual

        samp = self.sampler.state_dict()
        buffered = (
            self._prefetcher.buffered_batches()
            if self._prefetcher is not None
            else 0
        )
        if buffered:
            # rewind the SNAPSHOT (never the live sampler)
            samp = self._rewound_sampler_state(samp, buffered)
        # the error-feedback residual never enters checkpoints: it is
        # per-device noise state tied to the current bucket plan, and
        # dropping it costs one EF-less step after restore, not
        # correctness — while keeping every checkpoint readable by
        # runs with different (or no) grad-sync settings
        return {"train": strip_residual(self.state), "sampler": samp}

    def _load_checkpoint(self, target):
        """``load_checkpoint``, with the phases the engine timed folded
        into ``pipeline_stats`` (``restore_*``)."""
        out = self._ckptr.load_checkpoint(target)
        self.pipeline_stats.set_restore(self._ckptr.engine.last_restore)
        return out

    def _maybe_restore(self):
        from dlrover_tpu.agent.monitor import read_runtime_metrics
        from dlrover_tpu.parallel.grad_sync import ensure_residual

        step, restored = self._load_checkpoint(self._ckpt_state())
        if restored is not None and step >= 0:
            self.state = ensure_residual(
                restored["train"], self._grad_sync_plan, self.mesh
            )
            self.sampler.load_state_dict(restored["sampler"])
            logger.info(f"resumed from flash checkpoint step {step}")
            # restart-replay accounting: the runtime-metrics file
            # outlives the previous incarnation, so the step it had
            # already published tells us how much progress this restore
            # lost — steps up to it re-earn old work and the goodput
            # ledger books that wall time as restart_replay, not
            # productive_compute
            if self.tcfg.report_metrics:
                prev_step = int(
                    read_runtime_metrics().get("global_step", -1) or -1
                )
                if prev_step > step:
                    self._replay_until_step = prev_step
                    self._goodput.replay_begin()
                    logger.info(
                        f"replaying lost progress: steps {step}.."
                        f"{prev_step} count as restart_replay"
                    )

    def save(self, storage: StorageType = StorageType.MEMORY) -> bool:
        if self._ckptr is None:
            return False
        ok = self._ckptr.save_checkpoint(
            self.global_step, self._ckpt_state(), storage
        )
        self._fold_save_begin()
        return ok

    def _fold_save_begin(self):
        """The shard lock's side of the save just asked for, as the
        engine counted it, into ``pipeline_stats``."""
        self.pipeline_stats.set_save_begin(self._ckptr.engine.save_begin)

    # -- eviction grace-window drain -----------------------------------
    def set_event_reporter(self, reporter: Callable[[str, str], None]):
        """``reporter(event, detail)`` mirrors trainer incidents (the
        ``eviction`` node event) to the master — same seam shape as the
        checkpoint saver's (``MasterClient.report_failure`` at WARNING
        level, or ``report_eviction_notice``)."""
        self._event_reporter = reporter

    def install_eviction_handler(self, grace_s: Optional[float] = None):
        """Register a SIGTERM handler that enters the drain state
        machine (signal-safe: it only sets flags; all real work happens
        at the next step boundary on the train thread). Chains to any
        previous handler. No-op off the main thread — the platform
        signal lands on the main thread anyway."""
        import signal

        grace = (
            float(grace_s)
            if grace_s is not None
            else self.tcfg.eviction_grace_s
        )
        try:
            prev = signal.getsignal(signal.SIGTERM)

            def _handler(signum, frame):
                self.request_eviction(grace, reason="sigterm")
                if callable(prev) and prev not in (
                    signal.SIG_IGN, signal.SIG_DFL
                ):
                    prev(signum, frame)

            signal.signal(signal.SIGTERM, _handler)
            logger.info(
                f"eviction SIGTERM handler installed (grace {grace}s)"
            )
        except ValueError:
            # signal.signal only works on the main thread; a trainer
            # constructed elsewhere still drains via the command
            # channel / request_eviction
            logger.warning(
                "not on the main thread: SIGTERM eviction handler not "
                "installed (the `evict` worker command still works)"
            )

    def request_eviction(
        self, grace_s: Optional[float] = None, reason: str = "notice"
    ):
        """Enter the drain state machine at the next step boundary.
        Idempotent (the first notice's deadline stands — a second,
        tighter notice may shorten it but never extend it); safe to
        call from signal handlers and foreign threads."""
        grace = (
            float(grace_s)
            if grace_s is not None and grace_s > 0
            else self.tcfg.eviction_grace_s
        )
        deadline = time.monotonic() + grace
        if self._evict_deadline is None or deadline < self._evict_deadline:
            self._evict_deadline = deadline
            self._evict_grace_s = grace
        if not self._evict_event.is_set():
            self._evict_reason = reason
            self._evict_event.set()
            logger.warning(
                f"eviction notice ({reason}): draining within "
                f"{grace:.1f}s"
            )

    @property
    def eviction_pending(self) -> bool:
        return self._evict_event.is_set() and not self.evicted

    def _drain_for_eviction(self):
        """The drain itself, run on the train thread once the in-flight
        step finished: (1) suppress the hang watchdog — the long stall
        ahead is deliberate; (2) announce the notice (metrics file +
        event seam) so the master can pre-arm the resize while we
        drain; (3) emergency shm checkpoint of the CURRENT step via the
        ChunkedStager fast path, budgeted to the grace window; (4) DISK
        persist only if the window comfortably allows (shm handoff
        covers the tight case); (5) book the whole window to the
        ``eviction`` goodput category and flush flight recorder +
        runtime metrics before returning control to the caller."""
        t0 = time.perf_counter()
        t0_ns = time.monotonic_ns()
        deadline = self._evict_deadline or (
            time.monotonic() + self.tcfg.eviction_grace_s
        )
        grace = self._evict_grace_s or self.tcfg.eviction_grace_s
        step = self.global_step
        self._goodput.eviction_begin()
        try:
            # the drain runs no train compute: mark the arbiter idle so
            # a co-located serving plane may soak the grace window (its
            # transfers stay BACKGROUND — the emergency stage's
            # EMERGENCY chunks still preempt them on the rails)
            transfer_sched.note_compute(False)
            self._flight.suppress_watchdog(grace + 60.0)
            self._flight.note_event(
                "eviction",
                f"{self._evict_reason}: grace={grace:.1f}s step={step}",
            )
            # announce FIRST: the master's proactive resize (rendezvous
            # exclusion, speculative n-1 compile) runs while we drain
            if self.tcfg.report_metrics:
                report_runtime_metrics(
                    step,
                    eviction_pending=1.0,
                    eviction_grace_s=float(grace),
                )
            if self._event_reporter is not None:
                try:
                    self._event_reporter(
                        "eviction",
                        f"grace={grace:.1f}s step={step} "
                        f"reason={self._evict_reason}",
                    )
                except Exception as e:
                    logger.warning(f"eviction event report failed: {e!r}")
            # the prefetcher's lookahead dies with us; the checkpoint's
            # sampler snapshot rewinds it (same contract as _ckpt_state)
            committed = False
            persisted = False
            if self._ckptr is not None:
                # a half-staged OLDER step holds the shard lock; the
                # emergency save wants the CURRENT step (nobody saw the
                # stale stage — abort is safe)
                self._abort_stager()
                try:
                    # EMERGENCY link priority: this drain races a platform
                    # kill — its chunks preempt any in-flight background
                    # spill/stage at their next chunk boundary
                    stager = self._ckptr.begin_chunked_save(
                        step,
                        self._ckpt_state(),
                        chunk_bytes=self.tcfg.stage_chunk_mb << 20,
                        priority=transfer_sched.Priority.EMERGENCY,
                    )
                    self._fold_save_begin()
                    if stager is not None:
                        # leave a commit-sized margin before the deadline
                        while (
                            not stager.done
                            and time.monotonic() < deadline - 0.5
                        ):
                            stager.advance(
                                budget_s=0.05, stats=self.pipeline_stats
                            )
                        if stager.done:
                            committed = stager.commit(
                                stats=self.pipeline_stats
                            )
                        else:
                            # the window closed mid-stage: commit() would
                            # drain the whole backlog UNBOUNDED and the
                            # platform's kill would land mid-commit —
                            # losing not just this checkpoint but the
                            # forensics flush below. Abort; the previous
                            # committed step stands (bounded loss <= one
                            # save interval, the same contract as a hard
                            # kill)
                            stager.abort()
                            logger.warning(
                                f"eviction: emergency stage incomplete at "
                                f"the deadline; aborted — the previous "
                                f"committed step stands"
                            )
                    else:
                        # saver busy with an uncommitted save: the plain
                        # memory save path skips-never-blocks too
                        committed = self.save(StorageType.MEMORY)
                except Exception as e:
                    logger.error(f"eviction emergency save failed: {e!r}")
                remaining = deadline - time.monotonic()
                if committed and not self._ckptr.engine._agent_mode:
                    # the sync (no-agent) engine's commit already wrote
                    # storage — the shm/persist split only exists under an
                    # agent saver
                    persisted = True
                elif committed and remaining > _EVICTION_PERSIST_FLOOR_S:
                    try:
                        persisted = self.save(StorageType.DISK)
                    except Exception as e:
                        logger.warning(
                            f"eviction persist skipped ({e!r}); shm "
                            f"handoff covers it"
                        )
                elif committed:
                    logger.info(
                        f"eviction: {remaining:.1f}s left of the grace "
                        f"window — skipping the DISK persist (shm handoff "
                        f"covers it)"
                    )
            self._close_prefetcher()
        finally:
            # the episode MUST close on every path (graftlint
            # span-leak): an exception escaping the drain used to
            # leak the eviction episode open, and the goodput
            # ledger then booked every later second to `eviction`
            drain_ms = (time.perf_counter() - t0) * 1e3
            self.eviction_drain_ms = drain_ms
            self._goodput.eviction_end()
            self.evicted = True
        # flush: goodput + registry + the final runtime-metrics write
        # (carries the measured drain latency the master forwards to
        # the Brain's dwell pricing)
        self._report_metrics(
            step,
            {
                "eviction_pending": 1.0,
                "eviction_grace_s": float(grace),
                "eviction_drain_ms": round(drain_ms, 1),
            },
        )
        if self._event_reporter is not None:
            try:
                self._event_reporter(
                    "eviction",
                    f"grace={grace:.1f}s step={step} "
                    f"drain_ms={drain_ms:.0f} "
                    f"committed={int(committed)} "
                    f"persisted={int(persisted)}",
                )
            except Exception as e:
                logger.warning(f"eviction event report failed: {e!r}")
        self._flight.note_event(
            "eviction_drained",
            f"step={step} drain_ms={drain_ms:.0f} "
            f"committed={int(committed)} persisted={int(persisted)}",
        )
        self._flight.dump(
            "eviction",
            extra={
                "step": step,
                "grace_s": grace,
                "drain_ms": drain_ms,
                "committed": committed,
                "persisted": persisted,
                "eviction_interval": [t0_ns, time.monotonic_ns()],
            },
            force=True,
        )
        logger.warning(
            f"eviction drain complete at step {step}: "
            f"{drain_ms:.0f} ms of a {grace:.1f}s window "
            f"(shm commit={'ok' if committed else 'FAILED'}, "
            f"persist={'ok' if persisted else 'skipped'})"
        )

    # -- loop ----------------------------------------------------------
    @property
    def global_step(self) -> int:
        return int(self.state.step)

    def _device_batch(self, batch, for_eval: bool = False):
        if isinstance(batch, dict):
            bx, by = batch["x"], batch["y"]
        else:  # tuple/list samples from the default collate
            bx, by = batch[0], batch[1]
        pad = self.accel.strategy.batch_pad
        if pad and for_eval:
            # the eval loss takes no row weights, so zero-pad rows
            # would bias it (and save-best/early-stopping built on
            # it); TRIM to the largest shardable row count instead —
            # unbiased, a few samples lighter
            m = self.accel.strategy.mesh
            shards = max(m.dp * m.fsdp, 1)
            n = (int(np.asarray(bx).shape[0]) // shards) * shards
            if n > 0:
                bx = np.asarray(bx)[:n]
                by = np.asarray(by)[:n]
        elif pad:
            # micro-batch rebalance: zero rows appended so the batch
            # divides over ALL ranks; the step's pad_row_weights zero
            # them out of the loss, so gradients match the real batch
            from dlrover_tpu.models.train import pad_batch_rows

            n = int(np.asarray(bx).shape[0]) + pad
            bx = pad_batch_rows(bx, n)
            by = pad_batch_rows(by, n)
        if self.accel.strategy.mesh.pp > 1:
            return bx, by  # pipeline step takes host arrays
        sharded = shard_batch({"x": bx, "y": by}, self.mesh)
        return sharded["x"], sharded["y"]

    def evaluate(self, max_batches: Optional[int] = None) -> Dict[str, float]:
        """Run the eval set through a grad-free sharded loss step.
        Returns {"eval_loss": mean NLL, "eval_ppl": exp(mean NLL)}."""
        return self._eval.evaluate(
            self.accel, self._programs.cache, self.state.params, max_batches
        )

    def current_lr(self) -> Optional[float]:
        """The live EFFECTIVE learning rate (schedule value x the
        master's retune scale) when the optimizer was built with
        ``build_optimizer`` / ``optax.inject_hyperparams``."""
        return self._lr_value(self._lr_parts())

    def _lr_parts(self) -> Optional[list]:
        """The device scalars whose product is ``current_lr`` (they
        live in the state: the next donating step takes them away)."""
        hp = getattr(self.state.opt_state, "hyperparams", None)
        if hp and "learning_rate" in hp:
            return [
                hp[k] for k in ("learning_rate", "retune_scale") if k in hp
            ]
        return None

    @staticmethod
    def _lr_value(parts: Optional[list]) -> Optional[float]:
        if parts is None:
            return None
        lr = float(parts[0])
        for p in parts[1:]:
            lr *= float(p)
        return lr

    # -- pipelined transfers -------------------------------------------
    def _epoch_batches(self, num_steps: int):
        """One epoch's (x, y) device batches, prefetched when enabled.

        The prefetcher's source is capped at the steps remaining so its
        lookahead never pulls samples past the run's end from the
        sampler; what it does buffer is rewound in ``_ckpt_state``."""
        import itertools

        src = iter(self.dataloader)
        if self.tcfg.prefetch <= 0:
            self._prefetcher = None
            return (self._device_batch(b) for b in src)
        from dlrover_tpu.data.prefetch import DevicePrefetcher

        self._prefetcher = DevicePrefetcher(
            itertools.islice(
                src, max(num_steps - self.global_step, 0)
            ),
            placement=self._device_batch,
            depth=self.tcfg.prefetch,
            stats=self.pipeline_stats,
        )
        return self._prefetcher

    def _close_prefetcher(self):
        if self._prefetcher is not None:
            self._prefetcher.close()
            self._prefetcher = None

    def _run_step(self, x, y):
        """One optimizer step, donation-aware: donate the state and the
        batch whenever no checkpoint staging is reading the buffers."""
        programs = self._programs
        donate = programs.donates(staging=self._staging_active())
        # each twin is built by its first call (the safe one ahead of
        # time through the AOT cache, the donating one inside jit)
        with self._first_build("step_donating" if donate else "step_safe"):
            step_fn = programs.step_for(self.state, x, y, donate)
            # the call of the step function, to its return: argument
            # handling on the host and the launch queued on the device
            with span("dispatch"):
                self.state, metrics = step_fn(self.state, x, y)
        if self._builds_logged < len(self._builds.builds):
            self._log_builds("by a first step")
        return metrics

    def _advance_stager(self):
        """Drain one budget's worth of checkpoint chunks off the step
        cadence; commit (cheap: metadata publish + agent notify) once
        the backlog is empty."""
        if self._stager is None:
            return
        self._stager.advance(
            budget_s=self.tcfg.stage_budget_ms / 1e3,
            stats=self.pipeline_stats,
        )
        if self._stager.done:
            self._stager.commit(stats=self.pipeline_stats)
            self._stager = None

    def _finish_stager(self):
        """The commit barrier: drain whatever is left and publish."""
        if self._stager is not None:
            self._stager.commit(stats=self.pipeline_stats)
            self._stager = None

    def _abort_stager(self):
        if self._stager is not None:
            self._stager.abort()
            self._stager = None

    def _maybe_save(self, step: int):
        if self._ckptr is None:
            return
        if step % self.tcfg.save_storage_interval == 0:
            # the disk save supersedes any half-staged older step:
            # abort it (nobody saw it — metadata is still invalid) so
            # the shard lock is free for the synchronous staging
            self._abort_stager()
            self.save(StorageType.DISK)
        elif (
            step % self.tcfg.save_memory_interval == 0
            and self._stager is None
        ):
            # a previous stage still draining keeps draining — skip
            # this interval rather than stall on a forced commit
            # (same skip-never-block contract as save_to_memory)
            with span("ckpt_snapshot"):
                snapshot = self._ckpt_state()
            # the engine names the legs of the begin (ckpt_begin_*)
            self._stager = self._ckptr.begin_chunked_save(
                step,
                snapshot,
                chunk_bytes=self.tcfg.stage_chunk_mb << 20,
            )
            self._fold_save_begin()

    # -- elastic resize (fast path) ------------------------------------
    def _strategy_for_exact(self, n_devices: int) -> Optional[Strategy]:
        """Strategy using EXACTLY ``n_devices``, or None. Model-
        parallel axes (tp/sp/ep/pp) are divisibility choices of the
        MODEL and keep their sizes; the data axes (dp, fsdp) absorb
        the device delta. When the current shape cannot scale, falls
        back to full candidate enumeration."""
        from dataclasses import replace as dc_replace

        s = self.accel.strategy
        m = s.mesh
        fixed = m.tp * m.sp * m.ep * m.pp
        if n_devices <= 0:
            return None
        if n_devices % fixed == 0:
            rem = n_devices // fixed
            if m.fsdp == 1:
                dp, fsdp = rem, 1
            elif m.dp == 1:
                dp, fsdp = 1, rem
            else:
                # mixed split: keep as much fsdp (the memory win) as
                # divides the remainder
                fsdp = min(m.fsdp, rem)
                while rem % fsdp:
                    fsdp -= 1
                dp = rem // fsdp
            unit = self.tcfg.batch_size // max(self.tcfg.grad_accum, 1)
            if unit % (dp * fsdp) == 0:
                return dc_replace(
                    s, mesh=dc_replace(m, dp=dp, fsdp=fsdp)
                )
        from dlrover_tpu.accel.candidates import candidate_strategies

        cands = [
            c
            for c in candidate_strategies(
                self._model_cfg,
                n_devices,
                self.tcfg.batch_size,
                self.tcfg.seq_len,
                grad_accum=self.tcfg.grad_accum,
            )
            if c.mesh.pp == 1
        ]
        if not cands:
            return None
        return dc_replace(
            cands[0],
            dtype=s.dtype,
            remat=s.remat,
            opts=s.opts,
            offload_opt=s.offload_opt,
            # field-carried grad-sync knobs survive the fallback too
            # (opts cover the trainer-knob path; an explicit Strategy
            # may carry them ONLY as fields)
            comm_overlap=s.comm_overlap,
            grad_compress=s.grad_compress,
            grad_bucket_mb=s.grad_bucket_mb,
            grad_topk_density=s.grad_topk_density,
        )

    def _rebalanced_strategy_for(
        self, n_devices: int
    ) -> Optional[Strategy]:
        """Micro-batch-rebalanced strategy using ALL ``n_devices`` on
        an indivisible count: the data axes absorb the delta and the
        batch is padded with ``batch_pad`` zero-weight rows so it
        divides (heavier ranks effectively take one extra micro-batch
        row; the pads land on the trailing ranks and carry loss
        weight 0, so gradients are those of the real batch). None
        when the count is exactly divisible (the exact path owns it),
        the model axes don't divide ``n_devices``, or the trainer
        runs grad_accum (pads would multiply across microbatches)."""
        from dataclasses import replace as dc_replace

        if not self.tcfg.mb_rebalance or self.tcfg.grad_accum > 1:
            return None
        if self._model_cfg.num_experts:
            # pad rows would contaminate the router's aux losses (see
            # build_train_step's batch_pad guard)
            return None
        s = self.accel.strategy
        m = s.mesh
        fixed = m.tp * m.sp * m.ep * m.pp
        if n_devices <= 0 or n_devices % fixed or m.pp > 1:
            return None
        rem = n_devices // fixed
        if m.fsdp == 1:
            dp, fsdp = rem, 1
        elif m.dp == 1:
            dp, fsdp = 1, rem
        else:
            fsdp = min(m.fsdp, rem)
            while rem % fsdp:
                fsdp -= 1
            dp = rem // fsdp
        shards = dp * fsdp
        pad = (-self.tcfg.batch_size) % shards
        if pad == 0:
            return None  # divisible: _strategy_for_exact handles it
        return dc_replace(
            s,
            mesh=dc_replace(m, dp=dp, fsdp=fsdp),
            batch_pad=pad,
        )

    def _strategy_for(self, n_devices: int) -> Strategy:
        """Strategy for a resized world, degrading gracefully: on a
        non-divisible count (e.g. 6 of 8 devices at batch 16) the
        trainer prices BOTH alternatives through the dry-runner —
        (a) the largest valid mesh <= ``n_devices`` with the surplus
        ranks idle, and (b) the micro-batch rebalance using every
        rank with a padded batch (``_rebalanced_strategy_for``) —
        and the cheaper wins. ``resize`` trims the device list, logs
        the choice and sets the ``dlrover_resize_idle_ranks`` /
        ``dlrover_resize_mb_pad`` gauges (NOT set here — this is also
        the speculative-compile path, and a hypothetical candidate
        must not corrupt the live metric). The descending scan is
        pure-Python candidate enumeration (no compiles), so even an
        exhaustive miss costs milliseconds. Raises a clear ValueError
        only when NO device count down to 1 admits a valid mesh
        (never a crash deep inside ``build_mesh``)."""
        from dataclasses import replace as dc_replace

        for n in range(n_devices, 0, -1):
            s = self._strategy_for_exact(n)
            if s is None:
                continue
            # the current strategy may carry a pad from a previous
            # rebalance; an exact fit needs none
            if s.batch_pad:
                s = dc_replace(s, batch_pad=0)
            if n < n_devices:
                reb = self._rebalanced_strategy_for(n_devices)
                if reb is not None:
                    from dlrover_tpu.accel.dry_runner import (
                        price_rebalance_options,
                    )

                    measured = (
                        self._step_time_sum / self._step_time_n
                        if self._step_time_n
                        else None
                    )
                    idle_s, reb_s = price_rebalance_options(
                        self._model_cfg,
                        self.tcfg.batch_size,
                        self.tcfg.seq_len,
                        s,
                        reb,
                        measured_step_s=measured,
                        current_strategy=self.accel.strategy,
                    )
                    if reb_s < idle_s:
                        logger.info(
                            f"micro-batch rebalance: padding the "
                            f"batch by {reb.batch_pad} rows to use "
                            f"all {n_devices} devices "
                            f"({reb.mesh.axis_sizes()}, est "
                            f"{reb_s * 1e3:.2f} ms/step) instead of "
                            f"idling {n_devices - n} rank(s) "
                            f"(est {idle_s * 1e3:.2f} ms/step)"
                        )
                        return reb
                    logger.info(
                        f"micro-batch rebalance priced out (pad "
                        f"{reb.batch_pad} rows, est "
                        f"{reb_s * 1e3:.2f} ms/step vs idle "
                        f"{idle_s * 1e3:.2f}); degrading instead"
                    )
                logger.info(
                    f"no valid mesh factorization uses all "
                    f"{n_devices} devices at batch="
                    f"{self.tcfg.batch_size}; degrading to "
                    f"{s.mesh.axis_sizes()} on {n} devices"
                )
            return s
        raise ValueError(
            f"no valid mesh factorization for any count <= {n_devices} "
            f"devices at batch={self.tcfg.batch_size}, "
            f"seq={self.tcfg.seq_len}: the resize target must let "
            f"dp*fsdp divide the batch or satisfy the model's "
            f"axis-divisibility rules"
        )

    def resize(
        self, n_devices: Optional[int] = None, devices=None,
        strategy: Optional[Strategy] = None,
    ) -> Dict[str, Any]:
        """Live reconfiguration to a new device world WITHOUT a restart.

        The fast path: (1) the prefetcher is closed FIRST — its
        buffered device copies pin old-mesh arrays and its producer
        thread could keep placing onto the dying mesh mid-reshard —
        and the live sampler is rewound by the dropped lookahead so no
        sample is skipped; (2) any in-flight chunked checkpoint stage
        is committed (its barrier) so nothing reads old buffers; (3)
        the accel artifacts are rebuilt for the new mesh (explicit
        strategy — no search) and the safe step comes out of the AOT
        compile cache, which a speculative pre-lower or an earlier
        visit to this mesh makes a HIT (no XLA compile in the downtime
        window); (4) live state is remapped shard-by-shard on device
        (``ckpt/reshard.py``) — only leaves with no surviving local
        source fall back to the shm/storage restore.

        Single-process scope: the sampler's replica split is
        per-process and unchanged here; multi-process resizes
        re-rendezvous through the agent and land in ``__init__``'s
        restore path instead. Returns a dict of timings/counters
        (``downtime_ms``, ``compile_cache_hit``, ``reshard_bytes_*``)."""
        import jax

        t0 = time.perf_counter()
        if devices is None:
            devices = (
                list(jax.devices())[:n_devices]
                if n_devices
                else list(jax.devices())
            )
        devices = list(devices)
        if self.accel.strategy.mesh.pp > 1:
            raise ValueError(
                "resize fast path requires a pp=1 current strategy "
                "(pipeline state has its own layout); restart instead"
            )
        idle_ranks = 0
        if strategy is None:
            strategy = self._strategy_for(len(devices))
            if strategy.mesh.num_devices < len(devices):
                # graceful degradation: the largest valid mesh won;
                # the surplus ranks sit idle this incarnation
                idle_ranks = len(devices) - strategy.mesh.num_devices
                devices = devices[: strategy.mesh.num_devices]
        if strategy.mesh.num_devices != len(devices):
            raise ValueError(
                f"strategy mesh needs {strategy.mesh.num_devices} "
                f"devices, resize got {len(devices)}"
            )
        if not aot_supported(strategy):
            raise ValueError(
                "resize fast path supports pp=1, non-offload "
                "strategies; restart for pipeline/offload changes"
            )
        # stat/gauge writes only after every validation that can still
        # abort this resize — a raise above must not leave dashboards
        # claiming idle ranks for a world that was never built
        if idle_ranks:
            logger.warning(
                f"resize: degrading to {strategy.mesh.num_devices} "
                f"of {strategy.mesh.num_devices + idle_ranks} devices "
                f"({strategy.mesh.axis_sizes()}), leaving "
                f"{idle_ranks} rank(s) idle"
            )
        if strategy.batch_pad:
            logger.info(
                f"resize: micro-batch rebalance active — batch padded "
                f"by {strategy.batch_pad} zero-weight rows so "
                f"{strategy.mesh.axis_sizes()} uses every rank "
                f"(resize_idle_ranks=0)"
            )
        self.pipeline_stats.resize_idle_ranks = idle_ranks
        self.pipeline_stats.resize_mb_pad = strategy.batch_pad
        self._registry.gauge(
            "dlrover_resize_idle_ranks",
            "devices left idle by resize degradation",
        ).set(float(idle_ranks))
        self._registry.gauge(
            "dlrover_resize_mb_pad",
            "zero-weight pad rows/step of the micro-batch rebalance",
        ).set(float(strategy.batch_pad))
        # a resize is a DELIBERATE stall: the hang watchdog must not
        # dump forensics of a cold compile that is working as designed
        # (cleared on success below; a raise lets the window lapse — a
        # resize that died mid-world-change masks real hangs for at
        # most this long)
        self._flight.suppress_watchdog(600.0)
        # stale scale predictions are worthless now — and the resize
        # owns the compile budget
        if self._spec_compiler is not None:
            self._spec_compiler.submit(())
        # the whole resize window is device-idle: refresh the arbiter's
        # out-of-compute mark so the co-located serving plane's idle-gap
        # gate opens NOW instead of waiting out the mark TTL
        transfer_sched.note_compute(False)
        # (1) prefetcher down BEFORE any reshard: see docstring
        with span("resize_drain"):
            buffered = (
                self._prefetcher.buffered_batches()
                if self._prefetcher is not None
                else 0
            )
            self._close_prefetcher()
            if buffered:
                self.sampler.load_state_dict(
                    self._rewound_sampler_state(
                        self.sampler.state_dict(), buffered
                    )
                )
            # (2) a half-staged checkpoint reads old-mesh buffers
            self._finish_stager()
        # (3) new-world artifacts; explicit strategy skips the search
        with span("resize_build"):
            accel = auto_accelerate(
                self._model_cfg,
                self._tx,
                batch=self.tcfg.batch_size,
                seq=self.tcfg.seq_len,
                devices=devices,
                strategy=strategy,
                donate=False,
                grad_accum=self.tcfg.grad_accum,
            )
        from dlrover_tpu.ckpt import reshard as reshard_mod
        from dlrover_tpu.models.train import state_spec

        from dlrover_tpu.parallel.grad_sync import strip_residual

        spec = state_spec(accel.cfg, accel.mesh, self._tx)
        # (4) on-device remap; host restore only for uncovered leaves.
        # The error-feedback residual is stripped first: reshard trees
        # must match the spec (which never carries it), its shapes are
        # tied to the OLD world's bucket plan anyway, and
        # _setup_grad_sync re-attaches a fresh one for the new plan
        # the with-block (not a manual handle) guarantees the span
        # closes on the raise paths below — a leaked open span would
        # poison hang attribution for the rest of the process
        with span("resize_reshard") as reshard_sp:
            try:
                new_state, report = reshard_mod.reshard_state(
                    strip_residual(self.state), spec,
                    stats=self.pipeline_stats,
                )
            except (OSError, RuntimeError) as e:
                # a failed on-device gather must not abort the resize
                # mid-world-change: degrade every leaf to the host
                # fallback below and restore the whole state from the
                # checkpoint instead. RuntimeError covers the real
                # failure mode (XLA surfaces interconnect/device errors
                # as XlaRuntimeError), OSError the injected
                # reshard.gather fault; ValueError (shape/struct
                # mismatch = model change) still raises
                logger.error(
                    f"resize: on-device reshard failed ({e!r}); "
                    f"falling back to a full checkpoint restore"
                )
                _leaves, _ = jax.tree_util.tree_flatten_with_path(spec)
                report = reshard_mod.ReshardReport(
                    fallback_paths=[
                        reshard_mod._keystr(kp) for kp, _ in _leaves
                    ]
                )
                new_state = spec
            reshard_sp.set(
                fallback_leaves=len(report.fallback_paths),
                device_bytes=report.device_bytes,
            )
            if report.fallback_paths:
                if self._ckptr is None:
                    raise RuntimeError(
                        f"resize: {len(report.fallback_paths)} leaves "
                        f"have no surviving on-device source and no "
                        f"ckpt_dir is configured for the host fallback "
                        f"(first: {report.fallback_paths[:3]})"
                    )
                step0, restored = self._load_checkpoint(
                    {"train": spec, "sampler": self.sampler.state_dict()}
                )
                if restored is None or step0 < 0:
                    raise RuntimeError(
                        "resize: host fallback restore found no usable "
                        "checkpoint"
                    )
                live_step = int(self.state.step)
                if step0 == live_step:
                    # same step: fill only the holes, keep the
                    # on-device arrays for everything that survived
                    new_state = reshard_mod.merge_fallback(
                        new_state, restored["train"],
                        report.fallback_paths,
                    )
                else:
                    # mixing leaves from different optimizer steps
                    # would be silently inconsistent state — roll the
                    # WHOLE state back to the checkpoint (every leaf
                    # from one step)
                    logger.warning(
                        f"resize: fallback checkpoint is step {step0} "
                        f"but live state is step {live_step}; "
                        f"restoring the full checkpoint instead of "
                        f"mixing steps ({live_step - step0} steps of "
                        f"progress replayed)"
                    )
                    new_state = restored["train"]
                    self.sampler.load_state_dict(restored["sampler"])
        # swap the world
        self.accel = accel
        self.cfg = accel.cfg
        self.mesh = accel.mesh
        self.state = new_state
        # the new world's twins; the old world's executable is dropped
        self._programs.rebuild(accel)
        self._eval.step_fn = None  # per-mesh memo re-resolves lazily
        self._built.clear()  # the new twins build at their first call
        # link model: re-probe ONLY when the device fingerprint changed
        # (docs/elastic-resize.md) — a resize back onto the same
        # hardware reuses the cached probe and costs nothing here
        self._setup_link_model()
        # buckets are re-planned for the new dp degree and a fresh
        # error-feedback residual attached (shapes changed with dp);
        # the timing probe is skipped — downtime window
        self._setup_grad_sync(measure=False)
        # the SDC lane axis is per-world: rebuild the detector and
        # probe for the new device total (history from the old world
        # describes different lanes)
        self._sdc.arm(self._grad_sync_plan, self.mesh)
        # spans straddling the rebuild belong to neither world's
        # budget: drop everything buffered so far, then re-price the
        # per-component budget for the new mesh (tests/test_audit.py
        # guards the no-double-count property)
        self._auditor.skip_to_now()
        self._setup_audit_budget()
        new_state = self.state
        # candidates already seen were filtered against the OLD world;
        # the next poll must re-evaluate them for this one
        self._last_candidates = None
        cache_hit = None
        programs = self._programs
        if programs.batch_avals is not None:
            with span("resize_compile") as compile_sp:
                key, xy = programs.lowering_for(
                    strategy, accel.mesh, new_state
                )
                if (
                    self._spec_compiler is not None
                    and self._spec_compiler.in_flight_key == key
                ):
                    # this exact executable is mid-compile on the
                    # background thread: waiting converts a duplicate
                    # multi-minute compile into a cache hit
                    self._spec_compiler.wait_idle(600.0)
                step_fn, state = accel.step_fn, new_state
                fn, cache_hit = programs.cache.get_or_compile(
                    key, lambda: step_fn.lower(state, *xy).compile()
                )
                compile_sp.set(cache_hit=bool(cache_hit))
                programs.install(fn)
        self._flight.clear_suppression()
        downtime_ms = (time.perf_counter() - t0) * 1e3
        self.pipeline_stats.resize_count += 1
        self.pipeline_stats.resize_downtime_ms = downtime_ms
        logger.info(
            f"resized to {strategy.describe()} on {len(devices)} "
            f"devices in {downtime_ms:.0f} ms (compile cache "
            f"{'hit' if cache_hit else 'miss' if cache_hit is not None else 'n/a'}, "
            f"{report.moved_leaves} leaves resharded on device, "
            f"{len(report.fallback_paths)} via host)"
        )
        return {
            "downtime_ms": downtime_ms,
            "compile_cache_hit": cache_hit,
            "reshard_bytes_device": report.device_bytes,
            "reshard_bytes_host": report.host_bytes,
            "fallback_paths": list(report.fallback_paths),
            "mesh": strategy.mesh.axis_sizes(),
        }

    # -- speculative compilation ---------------------------------------
    def _staging_active(self) -> bool:
        return (
            self._stager is not None
            or (
                self._ckptr is not None
                and self._ckptr.staging_in_flight()
            )
            or self._eval.staging_in_flight()
        )

    def update_scale_candidates(self, device_counts) -> int:
        """Pre-lower the train step for likely next world sizes on a
        background thread (the speculative leg of the resize fast
        path). Candidates that cannot form a valid mesh are skipped
        with a log — a bad prediction must never hurt the current
        world. Returns the number of candidates submitted."""
        if not self.tcfg.speculative_compile:
            return 0
        if self._programs.batch_avals is None or not aot_supported(
            self.accel.strategy
        ):
            return 0
        import jax

        all_devices = list(jax.devices())
        tasks, seen = [], set()
        for n in device_counts:
            n = int(n)
            if (
                n <= 0
                or n in seen
                or n == self.accel.strategy.mesh.num_devices
                or n > len(all_devices)
            ):
                continue
            seen.add(n)
            try:
                cand = self._strategy_for(n)
            except ValueError as e:
                logger.info(
                    f"speculative compile: skipping {n}-device "
                    f"candidate ({e})"
                )
                continue
            # a degraded candidate uses fewer devices than predicted —
            # lower for the mesh it will actually build
            task = self._speculative_task(
                cand, all_devices[: cand.mesh.num_devices]
            )
            if task is not None:
                tasks.append(task)
        if not tasks:
            return 0
        if self._spec_compiler is None:
            from dlrover_tpu.accel.compile_cache import (
                SpeculativeCompiler,
            )

            self._spec_compiler = SpeculativeCompiler(
                self._programs.cache,
                pause_fn=self._staging_active,
                budget_s=_SPEC_COMPILE_BUDGET_S,
            )
        self._spec_compiler.submit(tasks)
        logger.info(
            f"speculative compile: {len(tasks)} candidate meshes "
            f"queued ({sorted(seen)})"
        )
        return len(tasks)

    def _speculative_task(self, cand: Strategy, devices):
        """One pre-lower unit: key computed now (cheap eval_shape
        traces), the expensive lower+compile deferred to the
        background thread."""
        from dlrover_tpu.accel.dry_runner import _build
        from dlrover_tpu.models.train import state_spec
        from dlrover_tpu.accel.compile_cache import CompileTask
        from dlrover_tpu.parallel.mesh import build_mesh

        model_cfg, tx = self._model_cfg, self._tx
        try:
            mesh = build_mesh(cand.mesh, devices=devices)
        except ValueError as e:
            logger.info(f"speculative compile: {e}")
            return None
        # specs must match what the resize will lower against, so the
        # cfg/mesh derivation mirrors auto_accelerate's _build
        from dlrover_tpu.accel.opt_lib import apply_optimizations
        from dataclasses import replace as dc_replace

        cfg2, cand2 = apply_optimizations(model_cfg, cand, cand.opts)
        cfg2 = dc_replace(cfg2, dtype=cand2.dtype, remat=cand2.remat)
        spec = state_spec(cfg2, mesh, tx)
        from dlrover_tpu.parallel.grad_sync import (
            residual_spec,
            resolve_plan,
        )

        plan = resolve_plan(cfg2, cand2)
        if plan is not None and plan.compress == "int8":
            # a compressed run steps with the residual in its state
            # tree — the pre-lowered executable (and its cache key)
            # must see the same tree or the resize can never hit it
            spec = dc_replace(spec, grad_residual=residual_spec(plan, mesh))
        key, xy = self._programs.lowering_for(cand, mesh, spec)

        def build():
            _, mesh2, step_fn, _, _, _ = _build(
                cand, model_cfg, tx, devices, donate=False
            )
            return step_fn.lower(spec, *xy).compile()

        return CompileTask(
            label=f"mesh{cand.mesh.axis_sizes()}", key=key, build=build
        )

    def _poll_scale_candidates(self):
        """Pick up the master's predicted next worker counts from the
        paral-config file (the agent's ParalConfigTuner mirrors the
        master's ``candidate_worker_counts`` there) and queue
        speculative compiles for them."""
        if not self.tcfg.speculative_compile:
            return
        from dlrover_tpu.trainer.elastic.dataloader import (
            read_paral_config,
        )

        counts = read_paral_config().get("candidate_worker_counts") or []
        counts = [
            int(c) for c in counts if isinstance(c, (int, float)) and c > 0
        ]
        if not counts or counts == self._last_candidates:
            return
        if self._programs.batch_avals is None:
            # too early: the first step hasn't recorded the batch avals
            # the pre-lower needs — leave the candidates unconsumed so
            # the next poll picks them up
            return
        self._last_candidates = counts
        import jax

        from dlrover_tpu.common.constants import NodeEnv

        num_procs = max(
            1, int(os.getenv(NodeEnv.NUM_PROCESSES, "1") or "1")
        )
        # worker counts → device counts at this job's density
        per_worker = max(1, len(jax.devices()) // num_procs)
        self.update_scale_candidates([c * per_worker for c in counts])

    def train(self, num_steps: int) -> Any:
        """Run up to ``num_steps`` optimizer steps (across epochs)."""
        import jax

        t0 = time.time()
        start_step = self.global_step
        # hang attribution reads THIS thread's open spans (the prefetch
        # producer parks in a read by design and must not masquerade as
        # the stuck frame)
        self._train_tid = threading.get_ident()
        self._eval.begin_run()
        try:
            return self._train_loop(num_steps, t0, start_step)
        except BaseException as e:
            # crash flight recorder: the black box dumps BEFORE the
            # exception unwinds past the trainer (stacks, last-N spans,
            # metrics, recent events) — by the time a human reads the
            # worker log, the process is long gone
            if not isinstance(e, (KeyboardInterrupt, SystemExit)):
                # force past the rate limiter: the process is about to
                # die and the exception is evidence no earlier dump
                # (hang watchdog, degraded episode) captured
                self._flight.dump("crash", exc=e, force=True)
            raise
        finally:
            self._close_prefetcher()
            try:
                # a half-staged checkpoint must not die with the loop:
                # the barrier drains and publishes it
                self._finish_stager()
            except Exception as e:
                # never mask the loop's own exception with a commit
                # failure; the stage is already aborted (lock released)
                logger.error(f"final stage commit failed: {e!r}")
            logger.info(f"pipeline: {self.pipeline_stats.summary()}")

    def _observe_step_time(self, dt_s: float):
        self._step_time_hist.observe(dt_s)
        self._step_time_sum += dt_s
        self._step_time_n += 1

    def _report_metrics(self, step: int, scalars: Dict[str, float]):
        """Publish at log cadence: training scalars + the whole metrics
        registry through ONE file (the agent's TrainingMonitor forwards
        every float in it to the master's collector). PipelineStats
        folds into the registry here so its counters ride the same
        export path as everything else."""
        if self._step_time_n:
            scalars["step_time_ms"] = round(
                1e3 * self._step_time_sum / self._step_time_n, 3
            )
            self._step_time_sum = 0.0
            self._step_time_n = 0
        for k, v in scalars.items():
            self._registry.gauge(
                f"dlrover_train_{k}", "training scalar"
            ).set(v)
        fold_pipeline_stats(self.pipeline_stats, self._registry)
        # goodput accounting rides the same export: collect the window
        # since the last report and publish the dlrover_goodput_*
        # gauges (the aggregator re-assembles the fleet number from
        # these scalars)
        self._goodput.export(self._registry)
        # budget reconciliation rides the same cadence: audit every
        # step completed since the last report, publish the
        # dlrover_audit_* series (residual/drift/alarm per component)
        # and rate-limited-persist the drift snapshot beside the rail
        # cache so a warm restart starts repriced
        self._auditor.export(self._registry)
        if self._link_fp:
            self._auditor.persist(fingerprint=self._link_fp)
        self._poll_worker_commands()
        if self.tcfg.report_metrics:
            report_runtime_metrics(
                step, **{**scalars, **self._registry.scalars()}
            )
        return scalars

    def _poll_worker_commands(self):
        """Execute master->worker commands relayed by the agent
        (flight dumps, profiler captures). Log-cadence polling of one
        small JSON file; ids are master-monotonic so a command runs
        exactly once per process."""
        from dlrover_tpu.agent.monitor import read_worker_commands

        try:
            cmds = read_worker_commands()
        except Exception:
            return
        for c in cmds:
            try:
                cid = int(c.get("id", 0))
            except (TypeError, ValueError):
                continue
            if cid <= self._last_command_id:
                continue
            self._last_command_id = cid
            kind = c.get("kind", "")
            reason = str(c.get("reason", "") or "master_request")
            if kind == "evict":
                # the master-side notice channel (platform preemption
                # watchers, operators, the auto-scaler): arg carries
                # the grace window, 0 = the trainer's default
                self.request_eviction(
                    float(c.get("arg", 0) or 0) or None,
                    reason=f"master_{reason}",
                )
            elif kind == "flight_dump":
                logger.info(
                    f"master requested flight dump (#{cid}, {reason})"
                )
                self._flight.dump(f"request_{reason}")
            elif kind == "profile":
                steps = int(c.get("arg", 0) or 3)
                if self._profiler_capture.request(steps, reason=reason):
                    logger.info(
                        f"master requested profiler capture (#{cid}, "
                        f"{steps} steps, {reason})"
                    )
                else:
                    # refusal is the artifact-volume bound working
                    # (live capture / cooldown), but it must be
                    # visible — the master believes evidence is coming
                    logger.warning(
                        f"profiler capture request #{cid} ({reason}) "
                        f"refused: capture active or cooling down"
                    )
            else:
                logger.warning(
                    f"unknown worker command kind {kind!r} (#{cid})"
                )

    def _wait_for_step(self, done) -> None:
        """The loop's one wait per step, on the completion token of the
        step BEFORE the one just dispatched (``done``: an output of it
        that no later step is given to donate, its loss). A token not
        yet ready means the device still had work when the next step was
        queued behind it: ``steps_ahead`` counts those."""
        import jax

        if done is None:
            return
        if not done.is_ready():
            self.pipeline_stats.steps_ahead += 1
        jax.block_until_ready(done)

    def _log_step(self, due, t0, start_step):
        """The log-cadence report of one step, made once that step has
        been waited for: ``due`` is what the loop put aside at the step
        itself (its number, its reportable metrics, copies of the
        learning-rate scalars of its state, and the eval scalars as
        they stood)."""
        step, metrics, lr_parts, evals = due
        # materializing the loss is a host sync only when the report is
        # made at an exit of the loop; in the loop the step is done
        with span("host_sync"):
            loss = float(metrics["loss"])
            # of a MoE model, its drop rate and per-expert load
            fold_routing_report(
                metrics, self.pipeline_stats, self.cfg.held_experts
            )
            # of a looped model, its exits
            exits = fold_exit_report(metrics, self.pipeline_stats)
            # of a model trained by diffusion over blocks, its noise
            exits += fold_diffusion_report(metrics, self.pipeline_stats)
        with span("report"):
            scalars = {"loss": loss}
            lr = self._lr_value(lr_parts)
            if lr is not None:
                scalars["lr"] = lr
            scalars.update(evals)
            # the agent's TrainingMonitor forwards these to the
            # master's collector (TrainMetricsReport)
            self._report_metrics(step, scalars)
            rate = (step - start_step) / max(time.time() - t0, 1e-9)
            lr_s = f" lr={lr:.2e}" if lr is not None else ""
            logger.info(
                f"step {step}: loss={loss:.4f}{lr_s}{exits} "
                f"({rate:.2f} it/s)"
            )

    def _train_loop(self, num_steps: int, t0, start_step) -> Any:
        import jax
        import jax.numpy as jnp

        # One step in flight: each iteration dispatches step N+1 and
        # then waits for step N, so the rest of the iteration (staging,
        # hooks, report, save, the next batch and the next dispatch)
        # runs while the device computes N+1. `in_flight` is the
        # completion token of the step dispatched last, `report_due`
        # the log-cadence report of a step not yet waited for.
        in_flight = None
        report_due = None

        def held_lr():
            # copies on the device of the learning-rate scalars of
            # `self.state`: the next step may donate the state that
            # holds them before the report that wants them is made
            parts = self._lr_parts()
            return parts and [jnp.copy(p) for p in parts]

        # builds the copy's program now and not at the first report
        # step: nothing compiles once a run is warm
        held_lr()

        def report_if_due():
            nonlocal report_due
            if report_due is not None:
                due, report_due = report_due, None
                self._log_step(due, t0, start_step)

        def drain():
            # every exit of the loop: nothing stays in flight, and a
            # report still due is made
            jax.block_until_ready(self.state.params)
            report_if_due()

        while self.global_step < num_steps and not self.eviction_pending:
            self.dataloader.load_config()  # master-retuned batch size
            self._apply_lr_scale(self.dataloader.lr_scale)
            # master-predicted next world sizes → background pre-lower
            self._poll_scale_candidates()
            # epoch rollover and mid-epoch position both live in the
            # sampler (its iterator advances completed_num and bumps the
            # epoch on exhaustion) — the trainer never touches them, so a
            # num_steps stop mid-epoch checkpoints the exact position
            # (modulo the prefetch rewind in _ckpt_state)
            batches = self._epoch_batches(num_steps)
            # a device read, once an epoch; inside it the step's number
            # is the host's own count
            host_step = self.global_step
            while True:
                # step boundary = the preemption arrival point: every
                # step dispatched has its state in `self.state`, at most
                # one of them still runs on the device, nothing is
                # half-donated. node.preempt `kill` is the scripted hard
                # death the chaos harness replays; a pending eviction
                # notice (SIGTERM / env deadline / `evict` command)
                # enters the graceful drain instead
                faults.fire("node.preempt")
                if self.eviction_pending:
                    break
                # on-demand jax.profiler capture (no-op unless a master
                # `profile` command armed it)
                self._profiler_capture.on_step_begin()
                # the step span + its phase children are the trace's
                # spine: a dump shows where each step's wall time went
                # (docs/observability.md span taxonomy). An exception
                # escaping the body must CANCEL the span — a leaked
                # open frame would poison hang attribution for the
                # rest of the process (cancel after end is a no-op)
                # step_num is the host's own count, never a device
                # read: it names the step on the profiler's clock
                # (StepTraceAnnotation)
                step_sp = span("step", step_num=host_step + 1)
                step_t0 = time.perf_counter()
                try:
                    try:
                        with span("data_wait"):
                            x, y = next(batches)
                    except StopIteration:
                        step_sp.cancel()
                        break
                    with span("compute"):
                        # compute-window mark for the host-link
                        # arbiter: background transfers (spill drain,
                        # staging D2H) are scheduled INTO this window,
                        # off the inter-step host section
                        transfer_sched.note_compute(True)
                        try:
                            # holds the `dispatch` span
                            metrics = self._run_step(x, y)
                            step = host_step = host_step + 1
                            # the loop's one wait per step, for the
                            # step BEFORE the one just dispatched: the
                            # device goes from that step straight into
                            # this one, and what follows in the
                            # iteration runs under it. An error the
                            # device raised in that step surfaces here,
                            # one iteration late
                            with span("device_wait"):
                                self._wait_for_step(in_flight)
                            in_flight = metrics["loss"]
                        finally:
                            transfer_sched.note_compute(False)
                    # interleave checkpoint chunks while the step
                    # computes (the engine emits its own ckpt_stage
                    # span and the stage_* phases of each chunk)
                    if self._stager is not None:
                        with span("stage"):
                            self._advance_stager()
                    # what rides every step beside the program: the
                    # SDC fence and the caller's hook.
                    # `self.state` and `metrics` are THIS step's, still
                    # being computed: whoever reads them waits for them
                    with span("hooks"):
                        # the per-lane norm vector is detector input,
                        # not a reporting scalar — pop it before any
                        # consumer that reports scalars sees it (same
                        # contract as moe_expert_load)
                        dev_norms = metrics.pop("sdc_device_norms", None)
                        if self._sdc.detector is not None:
                            self._sdc.after_step(step, metrics, dev_norms)
                        if self._metrics_hook is not None:
                            self._metrics_hook(step, metrics)
                    # a report step before this one has been waited
                    # for by now: its loss costs no sync
                    report_if_due()
                    if step % self.tcfg.log_interval == 0:
                        # no device read here: this step's loss is
                        # reported once the step has been waited for,
                        # one iteration on (or at the loop's exit)
                        report_due = (
                            step,
                            dict(metrics),
                            held_lr(),
                            dict(self._eval.last),
                        )
                    if self._eval.due(step):
                        with span("eval"):
                            evals = self.evaluate()
                        logger.info(
                            f"step {step}: "
                            f"eval_loss={evals['eval_loss']:.4f} "
                            f"ppl={evals['eval_ppl']:.2f}"
                        )
                        if self._metrics_hook is not None:
                            self._metrics_hook(step, dict(evals))
                        if self._eval.after_eval(step, evals):
                            logger.info(
                                f"early stopping at step {step}: no "
                                f"eval improvement in "
                                f"{self.tcfg.early_stopping_patience} "
                                f"evals (best {self._eval.best_loss:.4f})"
                            )
                            step_sp.end()
                            drain()
                            return self.state
                    if self._sdc_halt:
                        # tier-3 conviction already rolled the state
                        # back — saving at THIS step would commit a
                        # checkpoint claiming progress the rollback
                        # discarded. End the step cleanly and halt
                        # (the quarantine-drain: the master excludes
                        # the convicted chip; the next incarnation
                        # resumes from the verified step)
                        step_sp.end()
                        break
                    with span("ckpt_save"):
                        self._maybe_save(step)
                    step_sp.end()
                    self._profiler_capture.on_step_end()
                    self._observe_step_time(
                        time.perf_counter() - step_t0
                    )
                    if (
                        self._replay_until_step is not None
                        and step >= self._replay_until_step
                    ):
                        # caught back up to the pre-restart frontier:
                        # wall time is productive again
                        self._goodput.replay_end()
                        self._replay_until_step = None
                except BaseException:
                    step_sp.cancel()
                    raise
                if step >= num_steps:
                    break
            if self._sdc_halt:
                break
            if self.eviction_pending:
                # the prefetcher stays up: the emergency checkpoint's
                # sampler snapshot rewinds by its buffered lookahead
                # (_ckpt_state), exactly like a normal save; the drain
                # closes it afterwards
                break
            self._close_prefetcher()  # fresh buffer per epoch
        drain()
        if self.eviction_pending:
            self._drain_for_eviction()
            jax.block_until_ready(self.state.params)
        return self.state

    def _apply_lr_scale(self, scale: float):
        """Linear-scaling rule: when the master retunes the batch size it
        also publishes optimizer.batch_size_factor. Optimizers from
        ``build_optimizer`` carry a dedicated ``retune_scale`` hyperparam
        that COMPOSES with the LR schedule (the schedule rewrites
        ``learning_rate`` every step, so multiplying that would be
        overwritten); plain ``optax.inject_hyperparams`` optimizers fall
        back to rescaling ``learning_rate`` in place."""
        if scale == getattr(self, "_applied_lr_scale", 1.0):
            return
        hp = getattr(self.state.opt_state, "hyperparams", None)
        # a SCHEDULE-driven learning_rate is recomputed from the step
        # count on every update, so multiplying it in place would be
        # silently discarded — only retune_scale can compose with it
        lr_is_scheduled = bool(
            getattr(self.state.opt_state, "hyperparams_states", {}).get(
                "learning_rate"
            )
        )
        can_apply = hp is not None and (
            "retune_scale" in hp
            or ("learning_rate" in hp and not lr_is_scheduled)
        )
        if not can_apply:
            if not getattr(self, "_warned_lr_scale", False):
                logger.warning(
                    f"master suggests lr scale {scale} but the optimizer "
                    "cannot accept it (no injected hyperparams, or a "
                    "schedule without a retune_scale knob); build tx "
                    "with build_optimizer to enable retuning"
                )
                self._warned_lr_scale = True
            return
        prev = getattr(self, "_applied_lr_scale", 1.0)
        if "retune_scale" in hp:
            hp["retune_scale"] = hp["retune_scale"] * (scale / prev)
        else:
            hp["learning_rate"] = hp["learning_rate"] * (scale / prev)
        self._applied_lr_scale = scale
        logger.info(f"learning rate rescaled x{scale} (linear scaling)")

    def close(self):
        if self._span_heartbeat is not None:
            self._span_heartbeat.stop()
            self._span_heartbeat = None
        # final drift snapshot, bypassing the rate limit — short jobs
        # still leave a calibration for the next run on this hardware
        if self._link_fp:
            self._auditor.persist(fingerprint=self._link_fp, force=True)
        self._flight.stop_watchdog()
        self._profiler_capture.abort()
        self._close_prefetcher()
        self._abort_stager()
        if self._spec_compiler is not None:
            self._spec_compiler.close()
            self._spec_compiler = None
        if self._ckptr is not None:
            self._ckptr.engine.close()
        self._eval.close()
