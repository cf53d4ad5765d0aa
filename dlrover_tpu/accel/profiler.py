"""Model profiler: per-module flops/params/memory + measured step cost.

Parity: ATorch ``AProfiler`` (atorch/atorch/utils/prof.py:38 — analytic
per-module flops formulas at :489-650 plus timed profiles feeding the
dry-runner) and the TF graph profile extractor.

``profile_model``: analytic per-block accounting from the config (no
device needed) — params, fwd/bwd FLOPs, activation bytes. Useful for
capacity planning and sanity-checking the compiler numbers. XLA's own
per-program accounting comes from ``dry_runner.compiled_cost``; measured
device time by part of the model comes from a profiler trace read by
``benchmark/scopes.py`` (docs/observability.md).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field, fields
from typing import Any, Dict, List, Optional

from dlrover_tpu.models.config import TransformerConfig, is_moe_layer

# bf16 peak TFLOP/s per chip (public specs); used for MFU
PEAK_TFLOPS = {
    "v2": 46.0,
    "v3": 123.0,
    "v4": 275.0,
    "v5 lite": 197.0,
    "v5e": 197.0,
    "v5p": 459.0,
    "v6 lite": 918.0,
    "v6e": 918.0,
}


def chip_peak_tflops(device) -> Optional[float]:
    """bf16 peak of ``device``. None off the TPU: a CPU run has no MFU.
    A TPU whose kind is not in the table is an error, not a silent
    ``mfu_pct=None``."""
    if device.platform != "tpu":
        return None
    kind = device.device_kind.lower()
    for key in sorted(PEAK_TFLOPS, key=len, reverse=True):
        if key in kind:
            return PEAK_TFLOPS[key]
    raise KeyError(
        f"no bf16 peak known for TPU device_kind {device.device_kind!r}; "
        f"add it to PEAK_TFLOPS"
    )


def _reported_to(places: int):
    """A float counter that ``as_dict`` rounds to ``places`` decimals."""
    return field(default=0.0, metadata={"places": places})


@dataclass
class PipelineStats:
    """Counters for the overlapped host↔device pipeline: the device
    prefetcher (data/prefetch.py), donation-aware stepping and chunked
    checkpoint staging (ckpt/engine.py) all write into one record so the
    train loop can report how much host work actually left the critical
    path. A "hit" is a ``next()`` that found a device-placed batch
    already waiting; a "miss" waited on the producer."""

    prefetch_hits: int = 0
    prefetch_misses: int = 0
    prefetch_reprimes: int = 0
    prefetch_wait_s: float = _reported_to(4)  # time the consumer blocked on misses
    stage_chunks: int = 0
    stage_bytes: int = 0
    stage_backlog_bytes: int = 0  # bytes still to stage (last observed)
    stage_block_s: float = _reported_to(4)  # critical-path seconds spent in advance()
    stage_commits: int = 0
    donated_steps: int = 0
    safe_steps: int = 0  # steps run without donation (staging in flight)
    # steps at whose ``device_wait`` the step before was still running:
    # the device had the next step queued and was never left without
    # work. The train loop keeps one step in flight; the steps not
    # counted here are the ones the host starved the device before
    steps_ahead: int = 0
    donated_bytes: int = 0
    # -- elastic-resize fast path (accel/compile_cache, ckpt/reshard) --
    compile_cache_hits: int = 0
    compile_cache_misses: int = 0
    reshard_bytes_device: int = 0  # state remapped without a host trip
    reshard_bytes_host: int = 0  # leaves that fell back to shm restore
    resize_count: int = 0
    resize_downtime_ms: float = _reported_to(2)  # last resize's wall downtime
    # ranks left idle by the last resize's graceful degradation (a
    # non-divisible device count picks the largest valid mesh <= n
    # instead of failing; also dlrover_resize_idle_ranks gauge)
    resize_idle_ranks: int = 0
    # padded rows per step of the micro-batch rebalance alternative
    # (ISSUE 13): instead of idling surplus ranks, the batch is padded
    # to divide over ALL ranks and the pads carry loss weight 0 — 0
    # when the current strategy is unpadded. resize_idle_ranks stays 0
    # on the rebalanced path (also dlrover_resize_mb_pad gauge).
    resize_mb_pad: int = 0
    # routing of the steps reported at the log cadence (trainer
    # _log_step, from metrics of a step already waited for): how many
    # reports, their summed moe_drop_rate, and their summed largest
    # expert's share of the assignments x num_experts (1.0 = even)
    moe_reports: int = 0
    moe_drop_rate_sum: float = _reported_to(6)
    moe_max_load_sum: float = _reported_to(6)
    # ... and their summed share of the assignments that fell on the
    # experts this chip holds (1.0 a report where it holds them all)
    moe_held_share_sum: float = _reported_to(6)
    # elements of the optimizer's int8 moments (ops/quantized_optim.py
    # ``Quantized8``, both moments) by where their blocks lie: in the
    # leaf's own tile order, which the update reads as a bitcast, or in
    # [nblocks, 128] rows, which cost two relayouts of the leaf a step.
    # Set once when the trainer has built its state; 0 / 0 for fp32
    opt_q8_tiles_elems: int = 0
    opt_q8_blocks_elems: int = 0
    # -- counted while programs were traced (common/trace_counts.py: a
    # counter's name is its field here; the trainer folds them when it
    # logs the programs it built) ---------------------------------------
    # fused short-sequence attention call sites (ops/flash_attention.py,
    # forward and backward each counted) this process has lowered, by
    # body: the triangle walk, which computes only the score tiles a
    # causal query can see, or the whole square; and the tiles the
    # triangle sites walk against the tiles of their squares. 0
    # everywhere for a model that never enters the fused family
    attn_tri_sites: int = 0
    attn_square_sites: int = 0
    attn_tiles_walked: int = 0
    attn_tiles_square: int = 0
    # ... and the streaming kernels (longer T or GQA; a forward is one
    # site, a backward one in one pass or two split), by grid: the
    # triangle path, which takes a grid step, a fetch and a mask only
    # where a causal query can see, or the whole rectangle; and the
    # blocks a head of the triangle sites walks against the blocks of
    # its rectangle
    attn_stream_tri_sites: int = 0
    attn_stream_rect_sites: int = 0
    attn_stream_blocks_walked: int = 0
    attn_stream_blocks_rect: int = 0
    # the blocks a head walks at the attention kernels that were given a
    # window (``flash_attention(..., window=W)``; counted as the streaming
    # family counts: a forward is one kernel, a backward one or two) in
    # the train step program this process traced last, and the blocks at
    # or under the diagonal at them: walked is the band's count where the
    # band ``i - wb <= j <= i`` is walked, and equals the other where the
    # window was only a mask; 0 / 0 for a model without a window
    attn_window_blocks_walked: int = 0
    attn_window_blocks_causal: int = 0
    # the score tiles in the edge blocks of the streaming attention
    # kernels' triangle path (the block on the diagonal, and under a
    # window the blocks its far edge crosses; a tile is a row strip's
    # height a side, ``ops/flash_attention._edge_strips``) in the train
    # step program this process traced last, summed over a head's walk and
    # the kernels as the window counts are, and those of them the kernels
    # multiply: the backward kernels walk an edge block in row strips and
    # leave out the tiles that lie wholly over the diagonal or past the
    # window, the forward kernel computes it whole. 0 / 0 where no call
    # takes that path
    attn_edge_tiles_multiplied: int = 0
    attn_edge_tiles: int = 0
    # attention kernel call sites inside a recomputed layer
    # (``models/transformer.recomputed``, ``cfg.remat``) in the train step
    # program this process traced last: the wrapper keeps what each one's
    # kernel read and returned (q, k, v, ``o``, the logsumexp), so its
    # forward kernel runs once a step and not again in the backward pass.
    # 0 without ``remat``, and for a layer whose attention is no kernel
    # call (the jnp path, a ring)
    attn_kept_sites: int = 0
    # layers that hold a share of the experts (``parallel/moe._moe_share``)
    # in the train step program this process traced last: the first round
    # of each, all its rounds unless the share is overloaded, is
    # differentiated in line and keeps what its backward pass reads (the
    # gathered rows, the grouped matmuls' results; under ``remat`` by
    # name), so its gather, grouped matmuls and activation run once a
    # step and not again in the backward pass. 0 for a model that holds
    # every expert, or none
    moe_share_kept_sites: int = 0
    # Gated DeltaNet mixers (ops/gated_delta.py) in the train step
    # program this process traced last, and the sequential chunk-state
    # steps one training step runs through them, forward and backward:
    # the step's serial depth in that layer kind; 0 / 0 for a model
    # without the kind. ``gdn_kernel_sites``: the mixers among them whose
    # chunk-local work (the [C, C] squares around the pass) was traced
    # into the ``gdn_chunk_*`` kernels (``ops/gated_delta_kernels.fits``);
    # ``gdn_pass_kernel_sites``: those whose serial pass itself was traced
    # into the ``delta_state_pass`` kernels, the state in VMEM across a
    # head's chunks (the same rule, counted where ``gdn_sites`` is);
    # ``gdn_chunk_steps`` counts the chunk states walked in order whoever
    # walks them
    gdn_sites: int = 0
    gdn_chunk_steps: int = 0
    gdn_kernel_sites: int = 0
    gdn_pass_kernel_sites: int = 0
    # the lanes of a key and a value head summed over the delta-rule sites
    # of the train step program this process traced last whose chunk-local
    # work is in the kernels: what a kernel's blocks hold (a head's width
    # in whole 128-lane tiles, ``ops/gated_delta_kernels.head_lanes``), and
    # what the model states (``gdn_key_dim + gdn_value_dim``). They differ
    # where a head is no whole tiles (96 + 192 stated, 128 + 256 held);
    # 0 / 0 without such a site
    gdn_head_lanes: int = 0
    gdn_head_lanes_used: int = 0
    # delta-rule mixers of the train step program this process traced last
    # whose write strength is scaled (``cfg.gdn_beta_scale`` != 1: beta up
    # to 2, the transition's eigenvalue along the key down to ``-alpha``);
    # 0 for a model that states none
    gdn_beta_scaled_sites: int = 0
    # entries of a ``layer_pattern`` in the train step program this process
    # traced last that read the residual stream with no norm before the
    # mixer and norm its output (``cfg.reordered_norm_kinds``); 0 for a
    # model whose layers are all pre-norm
    reordered_norm_sites: int = 0
    # Gated DeltaNet mixers traced as the primal of a recomputed layer
    # (``models/transformer.recomputed``, ``cfg.remat``) in the train step
    # program this process traced last: the wrapper keeps what the serial
    # pass read and returned, the rule's ``o`` and the ``[q | k | v]``
    # the convolution reads (``ops/gated_delta.KEPT``), so the
    # ``gdn_*_wy_fwd`` / ``gdn_*_read_fwd`` kernels, the pass's forward
    # loop and the projection run once a step and not again in the
    # backward pass, and ``gdn_chunk_steps`` holds no step of that trace.
    # 0 without ``remat``. So ``gdn_chunk_steps`` is the serial depth of
    # the DIFFERENTIATED train step, which is what is traced here: the
    # flag says "traced inside ``recomputed``", not "differentiated", and a
    # forward-only program of a ``remat`` configuration (an evaluation, a
    # pipeline stage's forward) would run the pass and count no step. That
    # the second pass is gone from the step that runs is read from the
    # device, not from this count (the benchmark's
    # ``gdn.fwd_kernel_runs_per_step``, ``step.mixer_scan_ms``)
    gdn_kept_sites: int = 0
    # convolution stretches before a scan (``ops/mamba2.conv_silu``: one
    # a Mamba-2, Gated DeltaNet or Mamba-1 mixer) in the train step program
    # this
    # process traced last, and those among them that were traced into the
    # ``conv_silu_*`` kernels (``ops/conv_kernels.fits``). Both counted at
    # one place, so a layer traced twice under ``jax.checkpoint`` counts
    # twice in both; 0 / 0 for a model without them
    conv_sites: int = 0
    conv_kernel_sites: int = 0
    # gated norms after a scan (``ops/mamba2.gated_norm``: one a Mamba-2
    # or Gated DeltaNet mixer) in the train step program this process
    # traced last, and those among them that were traced into the
    # ``gated_norm_*`` kernels (``ops/gated_norm_kernels.fits``). Counted
    # as the convolution's pair is; 0 / 0 for a model without them
    gate_sites: int = 0
    gate_kernel_sites: int = 0
    # Mamba-2 chunked scans (``ops/mamba2.mamba2_mixer``: one a mixer) in
    # the train step program this process traced last, and those among
    # them that were traced into the ``ssd_scan_*`` kernels
    # (``ops/ssd_kernels.fits``). Counted as the convolution's pair is;
    # 0 / 0 for a model without them
    ssd_sites: int = 0
    ssd_kernel_sites: int = 0
    # selective scans (``ops/selective_scan.selective_scan``: one a
    # Mamba-1 mixer) in the train step program this process traced last,
    # those among them that were traced into the ``sscan_*`` kernels
    # (``ops/selective_scan.fits``), and the steps one training step's
    # scans walk in order: T a forward pass, 2 T a backward pass (a
    # block's states made again, then walked from the end), so a layer
    # traced twice under ``jax.checkpoint`` counts its forward twice;
    # 0 / 0 / 0 for a model without the kind
    sscan_sites: int = 0
    sscan_kernel_sites: int = 0
    sscan_serial_steps: int = 0
    # differential attention (``models/transformer._diff_attention``) in
    # the train step program this process traced last: the head pairs
    # summed over its sites, and the attention calls' score heads over
    # two, summed likewise: equal where each of a pair's two score maps
    # is computed once (one call at the value pair's width), twice the
    # pairs where a pair's scores are computed once a value half
    attn_diff_pairs: int = 0
    attn_diff_score_calls: int = 0
    # layers of the train step program this process traced last that read
    # what another layer computed and not the residual stream alone: gated
    # memory units (a scan layer's output) and cross-attentions (one
    # layer's keys and values)
    xdec_memory_reads: int = 0
    xdec_kv_reads: int = 0
    # the width of a head's query and key summed over the attention sites
    # of the train step program this process traced last
    # (models/transformer.py): what the attention call was given, and
    # what the model states. They differ where the call pads (a latent
    # attention's 192 through kernels of whole lane tiles); 0 / 0 without
    # attention
    attn_score_lanes: int = 0
    attn_score_lanes_used: int = 0
    # latent-attention sites of the train step program this process
    # traced last whose query passes a latent of its own
    # (``cfg.q_latent_dim``: down-projection, norm, up-projection in place
    # of the whole ``wq``), and attention sites whose rotation reads a
    # scaled table (``cfg.rope_scaling``: YaRN's frequencies and not
    # ``theta^(-2j/D)``); 0 / 0 for a model with neither
    attn_q_latent_sites: int = 0
    rope_scaled_sites: int = 0
    # the query rows of a step that a position scale multiplies
    # (``cfg.attn_pos_scale_beta``, models/transformer._pos_scale), summed
    # over the attention sites of the train step program this process
    # traced last, and those of them at positions from
    # ``cfg.rope_original_len`` on, where the scale is not 1: from the
    # static row length, a row's positions from 0. 0 of N where the rows
    # are no longer than the unscaled table; 0 / 0 without the scale
    attn_pos_scaled_rows: int = 0
    attn_pos_rows: int = 0
    # a looped model (``cfg.ut_steps`` > 1, models/transformer.py: the
    # whole stack applied several times over the same weights) in the
    # train step program this process traced last: the passes one step
    # runs; the layer bodies one forward pass of the step runs, in
    # ONE-MIXER LAYERS (entries of the ``layer_pattern``, so a published
    # block of attention then feed-forward is two) summed over the passes;
    # and the exits through the head. The passes are a Python loop in
    # ``forward`` (``ut_passes``): every pass's layers are traced and
    # counted for themselves, so these and the attention kernels' site,
    # tile and kept counters above are of a whole step, L x ``ut_steps``
    # sites. ``ut_exit_fused_heads``: the exits whose gradients the
    # forward rule of ``exits_nll`` makes beside their losses (counted
    # where that rule is traced: every exit of a step that is
    # differentiated, none of a program that only evaluates). 0 / 0 / 0 /
    # 0 for a model that runs its layers once
    ut_steps: int = 0
    ut_layer_passes: int = 0
    ut_exit_heads: int = 0
    ut_exit_fused_heads: int = 0
    # ... and its exits in the steps reported at the log cadence (as the
    # routers' above): how many reports, their summed mean entropy of a
    # token's stopping distribution over the passes (nats; ln ut_steps =
    # even, 0 = the gate has collapsed onto one pass) and their summed
    # mean expected pass of stopping (from 1)
    ut_reports: int = 0
    ut_entropy_sum: float = _reported_to(6)
    ut_exit_step_sum: float = _reported_to(6)
    # a model trained by diffusion over blocks (``cfg.objective``
    # "block_diffusion", models/transformer.py: a row's noised copy fed
    # before the clean one) in the train step program this process traced
    # last: the attention sites under the block-diffusion rule; the blocks
    # a head's attention kernels walk there, forward and backward, and
    # the blocks of the whole ``2L x 2L`` grid at the same block size,
    # summed over the kernels as the window's pair is
    # (``ops/flash_attention._count_bd_site``: equal where the rule ran
    # as a mask over everything, the rectangular grid; 0 / 0 on the jnp
    # path, which has no blocks); the positions the stack sees a step and
    # the data tokens among them (``loss_fn``: twice the batch's tokens,
    # and the tokens; the head and the loss see the latter). 0s for a
    # model trained by next-token prediction
    attn_bd_sites: int = 0
    attn_bd_blocks_walked: int = 0
    attn_bd_blocks_square: int = 0
    diffusion_positions: int = 0
    diffusion_data_tokens: int = 0
    # ... and its noise in the steps reported at the log cadence (as the
    # exits' above): how many reports, their summed share of the data
    # tokens that were masked (E[t] = (1 + t_min) / 2 a report; 0 or 1
    # says the noise is dead) and their summed mean loss weight a data
    # token (``1 / t`` on a masked position, 0 on the rest: 1 in
    # expectation)
    diffusion_reports: int = 0
    diffusion_masked_sum: float = _reported_to(6)
    diffusion_weight_sum: float = _reported_to(6)
    # counted while the step was traced, as the kernels' sites above:
    # elements of the optimizer's int8 moments (both moments, as
    # ``opt_q8_tiles_elems`` above counts them) whose leaf's step the
    # train step that was built runs as the one-pass kernel
    # (ops/quantized_optim.py ``_q8_adam_step``: gradient, parameter,
    # codes and scales read once where they lie, parameter and moments
    # written in place; counted where the leaf's call is traced): what the
    # built program does, where ``opt_q8_tiles_elems`` says what the state
    # would allow. 0 off the TPU, on a mesh of several devices, with the
    # state offloaded, in a step that does not donate, and for a
    # transformation without the ``update_and_apply`` entry
    opt_q8_kernel_elems: int = 0
    # -- overlap-scheduled gradient sync (parallel/grad_sync.py) -------
    # which gradient-sync schedule the current mesh runs: "explicit"
    # (the bucketed scheduler engaged) or "gspmd" (fallback — was
    # silent-by-design before ISSUE 8; now visible in the log and in
    # the metrics registry via the numeric grad_sync_explicit twin).
    # "" until a trainer resolves the plan.
    grad_sync_path: str = ""
    # standalone wall time of one bucketed sync (its roofline: the
    # in-step cost is this minus whatever the scheduler overlaps)
    grad_sync_ms: float = _reported_to(3)
    # per-link split of the standalone sync (grad_sync.measure_sync_
    # legs_ms): slice-local ICI legs vs the cross-slice DCN all-reduce;
    # flat (single-slice) plans are all-ICI by construction
    grad_sync_ici_ms: float = _reported_to(3)
    grad_sync_dcn_ms: float = _reported_to(3)
    # fraction of sync wire time hidden behind backward compute; the
    # analytic model constant on backends where overlap cannot be
    # profiled (None until a grad-sync plan is active)
    comm_overlap_pct: Optional[float] = None
    # the A/B-measured twin of comm_overlap_pct (grad_sync.measured_
    # overlap_pct: step time with the sync vs without, normalized by
    # the standalone roofline); None where that A/B was not run, and
    # nothing in the tree runs it
    overlap_pct_measured: Optional[float] = None
    # wire bytes one sync moves vs what the uncompressed monolithic
    # sync would move (per optimizer step, per device ring traffic
    # aside — the ratio is the compression win)
    grad_bytes_wire: int = 0
    grad_bytes_raw: int = 0
    # -- the last restore's phases (ckpt/engine.py CheckpointEngine.load
    # times them where they happen; the trainer folds the record in) --
    restore_source: int = 0  # 0 none / 1 agent shm / 2 storage
    restore_bytes: int = 0
    # choosing the committed step: the storage tracker's newest step
    # that verifies (the repairing rank reads and checksums its files)
    restore_storage_verify_s: float = _reported_to(4)
    # the fleet's agreement on the storage step and on the shm step
    restore_agree_s: float = _reported_to(4)
    # blocking acquire of the shard lock; crc pass over the shm records
    restore_lock_wait_s: float = _reported_to(4)
    restore_shm_verify_s: float = _reported_to(4)
    restore_storage_read_s: float = _reported_to(4)  # 0 on the shm path
    # records -> device, to block_until_ready of the restored state
    restore_h2d_s: float = _reported_to(4)
    # -- the shard lock's side of the memory saves that fell due
    # (ckpt/engine.py ``_take_shard_lock``; the trainer folds the record
    # in after every save it asks for): saves skipped because the
    # agent's saver still held the lock, the seconds inside the
    # ``ckpt_begin_lock`` span of every due save, and the skips that
    # the lock's mirror answered with no request to the agent --
    save_skips: int = 0
    begin_lock_s: float = _reported_to(4)
    lock_local_answers: int = 0
    # -- this incarnation's way from the agent's Popen to its first
    # step (trainer/elastic/distributed.py ``init_elastic`` times the
    # first two where they happen; the trainer folds the record in and
    # adds the rest from the ``build:<what>`` rows at its first step) --
    # Popen -> init_elastic(): the interpreter's start and every import
    # up to there; 0 where no agent handed the instant over
    startup_import_s: float = _reported_to(4)
    # the ``backend_up`` span: device spec, compile cache,
    # jax.distributed.initialize, the backend's start and the chip
    startup_backend_s: float = _reported_to(4)
    # the first ``build:step_donating`` / ``step_safe`` row's seconds:
    # compile or cache load, plus one step
    startup_first_step_s: float = _reported_to(4)
    # XLA's compile + cache-retrieval seconds, and the persistent
    # cache's misses, over every build row up to and including that one
    startup_compile_s: float = _reported_to(4)
    startup_cache_misses: int = 0
    # -- the restart that made this incarnation, as the agent timed it
    # and handed it over (agent/training_agent.py ``_restart_workers``;
    # 0 on a first start): the monitor tick in which the death was
    # found, persist-before-restart, and stop + lock reset + rendezvous
    recover_detect_tick_s: float = _reported_to(4)
    recover_persist_s: float = _reported_to(4)
    recover_respawn_s: float = _reported_to(4)

    def _fold(self, record: Optional[Dict[str, float]], names):
        for key, value in (record or {}).items():
            if key in names:
                setattr(self, key, value)

    def set_restore(self, record: Optional[Dict[str, float]]):
        """Fold ``CheckpointEngine.last_restore`` in (None = no load)."""
        self._fold(record, RESTORE_FIELDS)

    def set_save_begin(self, record: Optional[Dict[str, float]]):
        """Fold ``CheckpointEngine.save_begin`` in."""
        self._fold(record, SAVE_BEGIN_FIELDS)

    def set_startup(self, record: Optional[Dict[str, float]]):
        """Fold ``distributed.startup_record()`` in."""
        self._fold(record, STARTUP_FIELDS)

    @property
    def prefetch_overlap_pct(self) -> Optional[float]:
        n = self.prefetch_hits + self.prefetch_misses
        if not n:
            return None
        return round(100.0 * self.prefetch_hits / n, 2)

    @property
    def compile_cache_hit_pct(self) -> Optional[float]:
        n = self.compile_cache_hits + self.compile_cache_misses
        if not n:
            return None
        return round(100.0 * self.compile_cache_hits / n, 2)

    @property
    def grad_bytes_wire_vs_raw(self) -> Optional[list]:
        if not self.grad_bytes_raw:
            return None
        return [self.grad_bytes_wire, self.grad_bytes_raw]

    def as_dict(self) -> Dict[str, Any]:
        """Every field under its own name (a float to the places
        written beside it), and the five derived keys."""
        d = {}
        for f in fields(self):
            value = getattr(self, f.name)
            places = f.metadata.get("places")
            d[f.name] = value if places is None else round(value, places)
        d["prefetch_overlap_pct"] = self.prefetch_overlap_pct
        d["compile_cache_hit_pct"] = self.compile_cache_hit_pct
        d["grad_bytes_wire_vs_raw"] = self.grad_bytes_wire_vs_raw
        d["reshard_bytes_device_vs_host"] = [
            self.reshard_bytes_device,
            self.reshard_bytes_host,
        ]
        # numeric twin for the metrics registry (fold_pipeline_stats
        # skips strings): 1 = explicit, 0 = gspmd fallback, None = no
        # trainer resolved a plan yet
        d["grad_sync_explicit"] = (
            None
            if not self.grad_sync_path
            else int(self.grad_sync_path == "explicit")
        )
        return d

    def summary(self) -> str:
        ov = self.prefetch_overlap_pct
        cc = self.compile_cache_hit_pct
        resize = (
            f", {self.resize_count} resizes (last "
            f"{self.resize_downtime_ms:.0f} ms, compile cache "
            f"{'-' if cc is None else cc}% hit, reshard "
            f"{self.reshard_bytes_device >> 20} MiB device / "
            f"{self.reshard_bytes_host >> 20} MiB host)"
            if self.resize_count
            else ""
        )
        legs = (
            f" [{self.grad_sync_ici_ms:.1f} ici / "
            f"{self.grad_sync_dcn_ms:.1f} dcn]"
            if self.grad_sync_dcn_ms
            else ""
        )
        measured = (
            f", {self.overlap_pct_measured}% measured"
            if self.overlap_pct_measured is not None
            else ""
        )
        path = f" [{self.grad_sync_path}]" if self.grad_sync_path else ""
        gsync = (
            f", grad sync{path} {self.grad_sync_ms:.1f} ms "
            f"standalone{legs} "
            f"({'-' if self.comm_overlap_pct is None else self.comm_overlap_pct}"
            f"% overlapped{measured}, {self.grad_bytes_wire >> 10} KiB "
            f"wire vs {self.grad_bytes_raw >> 10} KiB raw per sync)"
            if self.grad_bytes_raw
            else (f", grad sync{path}" if self.grad_sync_path else "")
        )
        restore = (
            f", restored {self.restore_bytes >> 20} MiB from "
            f"{('-', 'shm', 'storage')[self.restore_source]} (verify "
            f"{self.restore_storage_verify_s + self.restore_shm_verify_s:.2f}"
            f" s, read {self.restore_storage_read_s:.2f} s, to device "
            f"{self.restore_h2d_s:.2f} s)"
            if self.restore_source
            else ""
        )
        recovered = (
            f" after a restart (tick {self.recover_detect_tick_s:.2f} s, "
            f"persist {self.recover_persist_s:.2f} s, respawn "
            f"{self.recover_respawn_s:.2f} s)"
            if self.recover_detect_tick_s + self.recover_persist_s
            + self.recover_respawn_s
            else ""
        )
        startup = (
            f", up{recovered} in {self.startup_import_s:.2f} s of imports + "
            f"{self.startup_backend_s:.2f} s of backend, first step "
            f"{self.startup_first_step_s:.2f} s (compile or load "
            f"{self.startup_compile_s:.2f} s, "
            f"{self.startup_cache_misses} misses)"
            if self.startup_backend_s
            else ""
        )
        skips = (
            f", {self.save_skips} saves skipped on a busy shard lock "
            f"({self.lock_local_answers} answered by its mirror, "
            f"{self.begin_lock_s * 1e3:.1f} ms asking over all due saves)"
            if self.save_skips
            else ""
        )
        return (
            f"prefetch {self.prefetch_hits}h/{self.prefetch_misses}m"
            f" ({'-' if ov is None else ov}% overlap), "
            f"staged {self.stage_bytes >> 20} MiB in {self.stage_chunks} "
            f"chunks ({self.stage_block_s * 1e3:.1f} ms on critical "
            f"path, {self.stage_commits} commits{skips}), donated "
            f"{self.donated_bytes >> 20} MiB over {self.donated_steps} "
            f"steps ({self.safe_steps} safe, {self.steps_ahead} dispatched "
            f"ahead of the device){resize}{gsync}{restore}{startup}"
        )


RESTORE_FIELDS = tuple(
    f.name for f in fields(PipelineStats) if f.name.startswith("restore_")
)
SAVE_BEGIN_FIELDS = ("save_skips", "begin_lock_s", "lock_local_answers")
STARTUP_FIELDS = tuple(
    f.name for f in fields(PipelineStats)
    if f.name.startswith(("startup_", "recover_"))
)


# -- the profiler's clock and XLA's compile work ---------------------------


def install_profiler_mirror(tracer=None):
    """Give the span tracer its twin on the profiler's clock
    (``SpanTracer.set_mirror``): every span becomes a
    ``jax.profiler.TraceAnnotation`` of the same name, a ``step`` span
    that carries a host-side ``step_num`` a ``StepTraceAnnotation``
    ("train"). Called once by the process that holds the chip; whoever
    starts ``jax.profiler`` later (``ProfilerCapture``, a benchmark)
    then finds the host's spans on the train thread's line of
    ``/host:CPU``, on the clock of ``XLA Ops``. With no session live
    an annotation is one atomic load."""
    from jax.profiler import StepTraceAnnotation, TraceAnnotation

    from dlrover_tpu.obs.trace import get_tracer

    def annotation(name, attrs):
        if name == "step" and attrs and "step_num" in attrs:
            return StepTraceAnnotation("train", step_num=attrs["step_num"])
        return TraceAnnotation(name)

    (tracer if tracer is not None else get_tracer()).set_mirror(annotation)


class CompileMeter:
    """Process-wide totals of XLA's compile work, from ``jax.monitoring``
    (seconds in the backend's compile and in retrieval from the
    persistent cache, cache hits and misses), and the list of
    ``build:<what>`` spans that bracket the trainer's build sites with
    the change of those totals across each: which programs an
    incarnation compiled, and which it found in the cache."""

    _DURATIONS = {
        "/jax/core/compile/backend_compile_duration": "compile_s",
        "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
    }
    _EVENTS = {
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_misses",
    }

    def __init__(self):
        self.totals: Dict[str, float] = {
            "compile_s": 0.0, "retrieval_s": 0.0, "compiles": 0,
            "cache_hits": 0, "cache_misses": 0,
        }
        self.builds: List[Dict[str, Any]] = []
        self._installed = False

    def install(self):
        if self._installed:
            return
        self._installed = True
        import jax.monitoring

        def on_duration(event, secs, **_):
            key = self._DURATIONS.get(event)
            if key is not None:
                self.totals[key] += secs
                if key == "compile_s":
                    self.totals["compiles"] += 1

        def on_event(event, **_):
            key = self._EVENTS.get(event)
            if key is not None:
                self.totals[key] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def build(self, what: str) -> "_BuildSpan":
        """``with meter.build("init"):`` — a ``build:init`` span whose
        attributes are the change of the totals across it."""
        self.install()
        return _BuildSpan(self, what)


class _BuildSpan:
    __slots__ = ("_meter", "_what", "_before", "_t0", "_span")

    def __init__(self, meter: CompileMeter, what: str):
        self._meter = meter
        self._what = what

    def __enter__(self):
        from dlrover_tpu.obs.trace import span

        self._before = dict(self._meter.totals)
        self._span = span(f"build:{self._what}")
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        row = {
            k: round(v - self._before[k], 4)
            for k, v in self._meter.totals.items()
        }
        row["seconds"] = round(time.perf_counter() - self._t0, 4)
        self._span.set(**row)
        self._span.end()
        self._meter.builds.append({"what": self._what, **row})
        return False


def describe_builds(rows: List[Dict[str, Any]]) -> str:
    """One clause per ``build:<what>`` span: its wall seconds, the
    programs that went through XLA's compile-or-load and their seconds
    (a cache hit is counted there too, with its retrieval), and the
    persistent cache's hits and misses."""
    return "; ".join(
        f"{b['what']} {b['seconds']:.2f}s ("
        f"{b['compiles']:.0f} programs {b['compile_s']:.2f}s, "
        f"{b['cache_hits']:.0f} hits {b['retrieval_s']:.2f}s, "
        f"{b['cache_misses']:.0f} misses)"
        for b in rows
    )


_compile_meter = CompileMeter()


def compile_meter() -> CompileMeter:
    return _compile_meter


@dataclass
class ModuleProfile:
    name: str
    params: int
    fwd_flops: float  # per step at the given batch/seq
    activation_bytes: int


@dataclass
class ModelProfile:
    batch: int
    seq: int
    modules: List[ModuleProfile] = field(default_factory=list)

    @property
    def total_params(self) -> int:
        return sum(m.params for m in self.modules)

    @property
    def fwd_flops(self) -> float:
        return sum(m.fwd_flops for m in self.modules)

    @property
    def step_flops(self) -> float:
        """fwd + bwd ≈ 3x fwd (the standard 6ND/2ND split)."""
        return 3.0 * self.fwd_flops

    @property
    def activation_bytes(self) -> int:
        return sum(m.activation_bytes for m in self.modules)

    def report(self) -> str:
        lines = [
            f"{'module':<18}{'params':>12}{'fwd GFLOPs':>14}{'act MB':>10}"
        ]
        for m in self.modules:
            lines.append(
                f"{m.name:<18}{m.params:>12,}"
                f"{m.fwd_flops / 1e9:>14.2f}"
                f"{m.activation_bytes / 1e6:>10.1f}"
            )
        lines.append(
            f"{'TOTAL':<18}{self.total_params:>12,}"
            f"{self.fwd_flops / 1e9:>14.2f}"
            f"{self.activation_bytes / 1e6:>10.1f}"
        )
        lines.append(
            f"step (fwd+bwd) ≈ {self.step_flops / 1e12:.3f} TFLOPs @ "
            f"batch={self.batch} seq={self.seq}"
        )
        return "\n".join(lines)


def _gdn_profile(cfg: TransformerConfig, i: int, tok: int,
                 act_bytes: int) -> ModuleProfile:
    """A Gated DeltaNet mixer (``ops/gated_delta.py``): parameters, forward
    operations of ``tok`` tokens and what it keeps, at a key head of
    ``gdn_key_dim`` and a value head of ``gdn_value_dim`` (they need not
    be equal). The chunked rule, a value head and chunk of ``C`` steps:
    ``K K^T``, ``W`` and the masked ``Q K^T`` (``2 C^2 d_k`` each), ``U``
    and the read-out (``2 C^2 d_v`` each), the triangle's inverse
    (``2 (log2 C - 1)`` products of ``2 C^3``) and the three products
    with the carried state (``2 C d_k d_v`` each)."""
    d, Hv, Hk = cfg.model_dim, cfg.gdn_value_heads, cfg.gdn_key_heads
    dk, dv, C = cfg.gdn_key_dim, cfg.gdn_value_dim, cfg.gdn_chunk
    key_w, val_w = Hk * dk, Hv * dv
    gate_w = Hv if cfg.gdn_gate == "head_sigmoid" else val_w
    small = (
        d * (Hv + key_w) + key_w + Hv if cfg.gdn_decay == "channel"
        else d * 2 * Hv + 2 * Hv
    )
    matrices = d * (2 * key_w + val_w + gate_w) + val_w * d
    conv = cfg.gdn_conv * (2 * key_w + val_w)
    params = matrices + small + conv + dv
    levels = max(C.bit_length() - 2, 0)
    rule = Hv * (
        2.0 * C * (3 * dk + 2 * dv) + 4.0 * C * C * levels + 6.0 * dk * dv
    )
    flops = tok * (2.0 * (matrices + small + conv) + rule)
    kept = tok * (2 * key_w + 2 * val_w + d) * act_bytes
    return ModuleProfile(f"block{i}.gdn", params, flops, kept)


def profile_model(
    cfg: TransformerConfig, batch: int, seq: int, act_bytes: int = 2
) -> ModelProfile:
    """Analytic per-module accounting (parity: prof.py:489-650 flops
    formulas, transformer-specialized)."""
    d, f, v = cfg.model_dim, cfg.ffn_dim, cfg.vocab_size
    h, kvh, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    T, B = seq, batch
    # a looped model (``cfg.ut_steps``) runs every block and the head
    # once a pass over the same weights: a token's operations and
    # activations are ``ut_steps`` tokens' of a plain model, the
    # parameters are held once, the embedding is read once
    tok = B * T * cfg.ut_steps
    # trained by diffusion over blocks (``cfg.objective``) a row of ``seq``
    # data tokens passes the stack twice, noised and clean: 2 T positions
    # through every block, T through the head, and a head's attention
    # over T^2 + T * block visible pairs a row in place of the
    # triangle's T^2 / 2
    head_tok = tok
    pairs = B * cfg.ut_steps * T * T / 2
    if cfg.objective:
        tok *= 2
        pairs = B * T * (T + cfg.diffusion_block)
    prof = ModelProfile(batch=batch, seq=seq)

    emb_params = v * d + (0 if cfg.rope else cfg.max_seq_len * d)
    prof.modules.append(
        ModuleProfile("embed", emb_params, 0.0, B * T * d * act_bytes)
    )

    for i in range(cfg.num_layers):
        if cfg.layer_pattern[i:i + 1] == "G":
            # a one-mixer entry: the delta rule at its own head widths
            prof.modules.append(_gdn_profile(cfg, i, tok, act_bytes))
            continue
        qkv_params = d * (h + 2 * kvh) * hd + h * hd * d
        score_width = value_width = hd
        if cfg.attn_kind == "latent":
            # keys and values through their latent; the query projected
            # whole, or through a latent of its own (two projections in
            # place of the one)
            score_width, value_width = cfg.qk_head_dim, cfg.v_head_dim
            rq, rkv = cfg.q_latent_dim, cfg.kv_latent_dim
            qkv_params = (
                (d * rq + rq * h * score_width) if rq
                else d * h * score_width
            ) + d * (rkv + cfg.qk_rope_dim) + rkv * h * (
                cfg.qk_nope_dim + value_width
            ) + h * value_width * d
        attn_flops = 2.0 * tok * qkv_params  # projections, output proj
        # qk^T and softmax*v over the same visible pairs
        attn_flops += 2.0 * pairs * h * (score_width + value_width)
        attn_act = tok * (h + 2 * kvh) * hd * act_bytes + tok * d * act_bytes
        prof.modules.append(
            ModuleProfile(
                f"block{i}.attn", qkv_params, attn_flops, attn_act
            )
        )
        if is_moe_layer(cfg, i):
            mlp_params = cfg.num_experts * 2 * d * f + d * cfg.num_experts
            mlp_flops = 2.0 * tok * 2 * d * f  # top-1: same flops as dense
        elif cfg.swiglu:
            mlp_params = 3 * d * f
            mlp_flops = 2.0 * tok * 3 * d * f
        else:
            mlp_params = 2 * d * f + f + d
            mlp_flops = 2.0 * tok * 2 * d * f
        prof.modules.append(
            ModuleProfile(
                f"block{i}.mlp", mlp_params, mlp_flops,
                tok * f * act_bytes,
            )
        )

    head_params = 0 if cfg.tie_embeddings else d * v
    prof.modules.append(
        ModuleProfile(
            "lm_head", head_params, 2.0 * head_tok * d * v,
            head_tok * v * 4,  # logits are fp32
        )
    )
    return prof

