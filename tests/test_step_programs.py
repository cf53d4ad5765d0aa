"""``StepPrograms`` alone (which program runs a step, on CPU with a toy
model), and ``PipelineStats.as_dict()``'s keys as the benchmark reads
them."""

import dataclasses

import jax
import numpy as np
import optax
import pytest

from dlrover_tpu.accel.accelerate import auto_accelerate
from dlrover_tpu.accel.profiler import PipelineStats
from dlrover_tpu.accel.strategy import Strategy
from dlrover_tpu.models.config import tiny
from dlrover_tpu.models.train import shard_batch
from dlrover_tpu.parallel.mesh import MeshConfig
from dlrover_tpu.trainer.elastic.step_programs import (
    StepPrograms,
    step_cache_key,
)

BATCH, SEQ = 4, 16


@pytest.fixture(scope="module")
def accel():
    return auto_accelerate(
        tiny(num_layers=1), optax.adamw(1e-3), batch=BATCH, seq=SEQ,
        devices=jax.devices()[:1], strategy=Strategy(mesh=MeshConfig()),
        donate=False,
    )


def _batch(accel, rows=BATCH):
    x = np.arange(rows * SEQ, dtype=np.int32).reshape(rows, SEQ) % 64
    b = shard_batch({"x": x, "y": x}, accel.mesh)
    return b["x"], b["y"]


def _fresh(accel, **kw):
    stats = PipelineStats()
    return StepPrograms(accel, stats, **kw), stats


def test_staging_off_runs_the_donating_function(accel):
    programs, stats = _fresh(accel)
    state = accel.init_fn(jax.random.PRNGKey(0))
    x, y = _batch(accel)
    assert programs.donates(staging=False)
    fn = programs.step_for(state, x, y, donate=True)
    assert fn is programs.donating_step is accel.donating_step_fn
    assert (stats.donated_steps, stats.safe_steps) == (1, 0)
    nbytes = sum(
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(state)
    )
    assert stats.donated_bytes == nbytes + x.nbytes + y.nbytes
    # a donation-only run primes no executable
    assert programs.aot_exec is None
    state, metrics = fn(state, x, y)
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.parametrize(
    "rows,through", [(BATCH, "aot"), (BATCH // 2, "jit")],
    ids=["shapes_match", "short_batch"],
)
def test_staging_on_runs_the_safe_twin(accel, rows, through):
    programs, stats = _fresh(accel)
    state = accel.init_fn(jax.random.PRNGKey(0))
    x, y = _batch(accel)
    assert not programs.donates(staging=True)
    # the first safe step primes the executable for its shapes
    assert programs.step_for(state, x, y, donate=False) is programs.aot_exec
    assert programs.aot_exec is not None
    assert stats.compile_cache_misses == 1
    x2, y2 = _batch(accel, rows)
    fn = programs.step_for(state, x2, y2, donate=False)
    assert fn is (programs.aot_exec if through == "aot" else accel.step_fn)
    assert (stats.donated_steps, stats.safe_steps) == (0, 2)
    new_state, metrics = fn(state, x2, y2)
    assert np.isfinite(float(metrics["loss"]))
    # not donated: the state it was given is still there
    assert int(state.step) == 0 and int(new_state.step) == 1


def test_donation_unaware_never_donates(accel):
    programs, _ = _fresh(accel, donation_aware=False)
    assert programs.donating_step is None
    assert not programs.donates(staging=False)


def test_key_of_arrays_is_key_of_their_shapes(accel):
    programs, _ = _fresh(accel)
    state = accel.init_fn(jax.random.PRNGKey(0))
    x, y = _batch(accel)
    programs.step_for(state, x, y, donate=True)  # records the batch
    spec = jax.eval_shape(lambda s: s, state)
    spec = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=a.sharding
        ),
        state, spec,
    )
    key, xy = programs.lowering_for(accel.strategy, accel.mesh, spec)
    assert [(a.shape, a.dtype) for a in xy] == [
        (x.shape, x.dtype), (y.shape, y.dtype)
    ]
    assert key == step_cache_key(
        accel.strategy, accel.mesh, state, (x, y)
    )


def test_rebuild_drops_the_executable(accel):
    programs, stats = _fresh(accel)
    state = accel.init_fn(jax.random.PRNGKey(0))
    x, y = _batch(accel)
    first = programs.step_for(state, x, y, donate=False)
    programs.rebuild(accel)
    assert programs.aot_exec is None
    assert programs.batch_avals is not None  # the batch is the job's
    # the next safe step primes again: out of the cache this time
    assert programs.step_for(state, x, y, donate=False) is first
    assert (stats.compile_cache_misses, stats.compile_cache_hits) == (1, 1)
    # an executable handed over serves the recorded shapes
    programs.rebuild(accel)
    programs.install(first)
    assert programs.step_for(state, x, y, donate=False) is first
    assert stats.compile_cache_hits == 1


# the parent's keys (PR 28), less the one counter PR 29 deleted with its
# code, the fused attention kernels' four counts (PR 36) and the held
# experts' share of the routing (PR 37), the streaming attention kernels'
# four (PR 38), an incarnation's way up and the restart behind it (PR 40),
# the shard lock's side of the due saves (PR 42), the Gated DeltaNet
# mixers' two (PR 43), their sites in the kernels (PR 44), the
# convolutions' two (PR 47), the attention sites whose outputs a
# recomputed layer keeps (PR 51) and the share layers whose first round
# keeps what its backward pass reads (PR 52), the selective scans' three,
# the differential pairs' two and the cross-decoder's two reads (PR 53),
# the edge blocks' two tile counts (PR 54), the delta-rule mixers whose
# pass a recomputed layer keeps (PR 56), a looped model's three counts and
# what is folded of its exits (PR 57), the gated norms after a scan and
# those in the kernels (PR 63), the Mamba-2 chunked scans and those in the
# kernels (PR 66), the block-diffusion walk's three, the doubled row's two
# and what is folded of its noise (PR 67); how the counted ones are
# folded: ``test_trace_counts.py``
AS_DICT_KEYS = [
    "attn_bd_blocks_square", "attn_bd_blocks_walked", "attn_bd_sites",
    "attn_diff_pairs", "attn_diff_score_calls",
    "attn_edge_tiles", "attn_edge_tiles_multiplied",
    "attn_kept_sites",
    "attn_pos_rows", "attn_pos_scaled_rows", "attn_q_latent_sites",
    "attn_score_lanes", "attn_score_lanes_used", "attn_square_sites",
    "attn_stream_blocks_rect",
    "attn_stream_blocks_walked", "attn_stream_rect_sites",
    "attn_stream_tri_sites", "attn_tiles_square", "attn_tiles_walked",
    "attn_tri_sites", "attn_window_blocks_causal",
    "attn_window_blocks_walked", "begin_lock_s",
    "comm_overlap_pct",
    "compile_cache_hit_pct", "compile_cache_hits",
    "compile_cache_misses", "conv_kernel_sites", "conv_sites",
    "diffusion_data_tokens", "diffusion_masked_sum", "diffusion_positions",
    "diffusion_reports", "diffusion_weight_sum",
    "donated_bytes", "donated_steps", "gate_kernel_sites", "gate_sites",
    "gdn_beta_scaled_sites", "gdn_chunk_steps", "gdn_head_lanes",
    "gdn_head_lanes_used", "gdn_kept_sites", "gdn_kernel_sites",
    "gdn_pass_kernel_sites", "gdn_sites",
    "grad_bytes_raw", "grad_bytes_wire",
    "grad_bytes_wire_vs_raw",
    "grad_sync_dcn_ms", "grad_sync_explicit", "grad_sync_ici_ms",
    "grad_sync_ms", "grad_sync_path", "lock_local_answers",
    "moe_drop_rate_sum",
    "moe_held_share_sum", "moe_max_load_sum", "moe_reports",
    "moe_share_kept_sites", "opt_q8_blocks_elems",
    "opt_q8_kernel_elems", "opt_q8_tiles_elems", "overlap_pct_measured",
    "prefetch_hits",
    "prefetch_misses", "prefetch_overlap_pct", "prefetch_reprimes",
    "prefetch_wait_s", "recover_detect_tick_s", "recover_persist_s",
    "recover_respawn_s", "reordered_norm_sites", "reshard_bytes_device",
    "reshard_bytes_device_vs_host", "reshard_bytes_host", "resize_count",
    "resize_downtime_ms", "resize_idle_ranks", "resize_mb_pad",
    "restore_agree_s", "restore_bytes", "restore_h2d_s",
    "restore_lock_wait_s", "restore_shm_verify_s", "restore_source",
    "restore_storage_read_s", "restore_storage_verify_s",
    "rope_scaled_sites", "safe_steps",
    "save_skips", "sscan_kernel_sites", "sscan_serial_steps", "sscan_sites",
    "ssd_kernel_sites", "ssd_sites",
    "stage_backlog_bytes", "stage_block_s", "stage_bytes", "stage_chunks",
    "stage_commits", "startup_backend_s", "startup_cache_misses",
    "startup_compile_s", "startup_first_step_s", "startup_import_s",
    "steps_ahead", "ut_entropy_sum", "ut_exit_fused_heads", "ut_exit_heads",
    "ut_exit_step_sum", "ut_layer_passes", "ut_reports", "ut_steps",
    "xdec_kv_reads", "xdec_memory_reads",
]
# a float is reported to the places it had when each key was written out
ROUNDED = {
    "prefetch_wait_s": 4, "stage_block_s": 4, "resize_downtime_ms": 2,
    "moe_drop_rate_sum": 6, "moe_held_share_sum": 6,
    "moe_max_load_sum": 6, "grad_sync_ms": 3,
    "grad_sync_ici_ms": 3, "grad_sync_dcn_ms": 3,
    "restore_storage_verify_s": 4, "restore_agree_s": 4,
    "restore_lock_wait_s": 4, "restore_shm_verify_s": 4,
    "restore_storage_read_s": 4, "restore_h2d_s": 4,
    "startup_import_s": 4, "startup_backend_s": 4,
    "startup_first_step_s": 4, "startup_compile_s": 4,
    "recover_detect_tick_s": 4, "recover_persist_s": 4,
    "recover_respawn_s": 4, "begin_lock_s": 4,
    "ut_entropy_sum": 6, "ut_exit_step_sum": 6,
    "diffusion_masked_sum": 6, "diffusion_weight_sum": 6,
}


@pytest.mark.parametrize("key", AS_DICT_KEYS)
def test_as_dict_has_the_parents_keys(key):
    stats = PipelineStats()
    d = stats.as_dict()
    assert sorted(d) == AS_DICT_KEYS
    names = [f.name for f in dataclasses.fields(stats)]
    assert set(names) <= set(d)
    if key in names:
        field_type = type(getattr(stats, key))
        value = {int: 7, float: 1.23456789, str: "explicit"}.get(
            field_type, 12.5
        )
        setattr(stats, key, value)
        want = round(value, ROUNDED[key]) if key in ROUNDED else value
        assert stats.as_dict()[key] == want
    else:
        # the five derived keys
        stats.prefetch_hits, stats.prefetch_misses = 3, 1
        stats.compile_cache_hits, stats.compile_cache_misses = 1, 3
        stats.grad_bytes_wire, stats.grad_bytes_raw = 5, 10
        stats.reshard_bytes_device, stats.reshard_bytes_host = 8, 2
        stats.grad_sync_path = "gspmd"
        assert stats.as_dict()[key] == {
            "prefetch_overlap_pct": 75.0,
            "compile_cache_hit_pct": 25.0,
            "grad_bytes_wire_vs_raw": [5, 10],
            "reshard_bytes_device_vs_host": [8, 2],
            "grad_sync_explicit": 0,
        }[key]


