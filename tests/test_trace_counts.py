"""``common/trace_counts``: the registry the modules count into while a
program is traced, the trainer's one fold of it into ``PipelineStats``
(``ElasticTrainer._first_build`` takes the snapshot, ``_fold_trace_counts``
folds), and the two rules that keep the loop from knowing the kernels: every
name counted is a stats field, and ``trainer.py`` imports no module that
counts."""

import ast
import dataclasses
import functools
import importlib
import os
import sys
import threading
import types
from collections import Counter

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.accel.profiler import PipelineStats
from dlrover_tpu.common import trace_counts
from dlrover_tpu.models.config import TransformerConfig
from dlrover_tpu.models.train import TrainState, build_train_step
from dlrover_tpu.models.transformer import init_params
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.trainer.elastic.trainer import ElasticTrainer, build_optimizer
from trace_counted import (
    CONV, DIFF, EDGE, FUSED, GATE, GDN, GDN_KEPT, KEPT, LANES, PASS, SCALED,
    SHARE, SSCAN, SSD, STREAM, UT, WINDOW, XDEC, added,
)

# `dlrover_tpu.ops.flash_attention` the attribute is the function
fa = importlib.import_module("dlrover_tpu.ops.flash_attention")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIELDS = {f.name for f in dataclasses.fields(PipelineStats)}


@pytest.fixture
def fresh(monkeypatch):
    """The registry as a process finds it: nothing counted."""
    monkeypatch.setattr(trace_counts, "_counts", Counter())


# -- the registry -------------------------------------------------------------


def test_a_name_never_counted_reads_zero(fresh):
    assert trace_counts.snapshot() == {}
    assert trace_counts.snapshot()["gdn_sites"] == 0
    assert trace_counts.since(trace_counts.snapshot())["gdn_sites"] == 0


def test_counts_add_up_and_a_snapshot_is_a_copy(fresh):
    trace_counts.count("gdn_sites")
    before = trace_counts.snapshot()
    trace_counts.count("gdn_sites")
    trace_counts.count("gdn_chunk_steps", 8)
    trace_counts.count("conv_kernel_sites", True)  # a rule's answer
    trace_counts.count("conv_kernel_sites", False)
    assert before == {"gdn_sites": 1}
    assert trace_counts.snapshot() == {
        "gdn_sites": 2, "gdn_chunk_steps": 8, "conv_kernel_sites": 1,
    }


def test_since_names_everything_seen_and_what_each_added(fresh):
    trace_counts.count("conv_sites", 2)
    before = trace_counts.snapshot()
    trace_counts.count("gdn_sites", 3)
    # a name seen and not added to is there, as 0: a step that traced
    # none of it resets the field
    assert trace_counts.since(before) == {"conv_sites": 0, "gdn_sites": 3}
    assert dict(trace_counts.since(trace_counts.snapshot())) == {
        "conv_sites": 0, "gdn_sites": 0,
    }


def test_counting_from_many_threads_loses_nothing(fresh):
    """A speculative compile traces on a thread of its own beside the
    loop's: more threads than cores, switching every few bytecodes."""
    threads, each = 4 * (os.cpu_count() or 2), 500
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        workers = [
            threading.Thread(target=lambda: [
                trace_counts.count("gdn_chunk_steps", 2) for _ in range(each)
            ])
            for _ in range(threads)
        ]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60)
    finally:
        sys.setswitchinterval(was)
    assert not any(w.is_alive() for w in workers)
    assert trace_counts.snapshot()["gdn_chunk_steps"] == 2 * each * threads


def test_the_running_totals_are_stats_fields():
    assert set(trace_counts.RUNNING_TOTALS) == set(FUSED + STREAM) <= FIELDS
    assert set(
        GDN + CONV + GATE + LANES + WINDOW + EDGE + KEPT + SHARE + SSCAN + DIFF
        + XDEC + GDN_KEPT + SSD
    ) <= FIELDS - set(trace_counts.RUNNING_TOTALS)


# -- the trainer's fold -------------------------------------------------------


def _trainer():
    return types.SimpleNamespace(
        pipeline_stats=PipelineStats(), _counts_before_step=None,
        _built=set(), _builds=types.SimpleNamespace(build=lambda what: what),
    )


def _site(names, n, kernels=1):
    """Such a site of the attention kernels, counted as they count it."""
    return functools.partial(fa._count_site, names, n, kernels)


# a case is a run of moves on one trainer: {name: n} is counted (a trace),
# as is a site of the attention kernels,
# a string is ``_first_build`` of that program, a pair is the fold: the
# line's clause and the fields it leaves
FOLDS = {
    # the fused attention kernels' counts are the process's running
    # total; the line says what was lowered since the line before
    "fused_running_total": [
        ("", {}),  # nothing lowered: a model outside the family
        # a step program of twelve layers at T = 1024, fwd and bwd
        *[_site(FUSED, 4)] * 24,
        (
            "; traced: attn_tri_sites +24, attn_tiles_walked +240, "
            "attn_tiles_square +384",
            dict(zip(FUSED, (24, 0, 240, 384))),
        ),
        ("", {}),  # nothing new since that line
        # its twin, and one ring hop (traced offsets: the square body)
        *[_site(FUSED, 4)] * 24,
        _site(FUSED, 0),
        (
            "; traced: attn_tri_sites +24, attn_square_sites +1, "
            "attn_tiles_walked +240, attn_tiles_square +384",
            dict(zip(FUSED, (48, 1, 480, 768))),
        ),
    ],
    # two layers at T = 4096 in blocks of 1024, a forward and a one-pass
    # backward each: ten of sixteen blocks a kernel
    "stream_one_pass": [
        *[_site(STREAM, 4)] * 4,
        (
            "; traced: attn_stream_tri_sites +4, "
            "attn_stream_blocks_walked +40, attn_stream_blocks_rect +64",
            dict(zip(STREAM + FUSED, (4, 0, 40, 64, 0, 0, 0, 0))),
        ),
        ("", {}),
        # a fused site lowered later is said before the streaming one
        _site(FUSED, 4), _site(STREAM, 2),
        (
            "; traced: attn_tri_sites +1, attn_tiles_walked +10, "
            "attn_tiles_square +16, attn_stream_tri_sites +1, "
            "attn_stream_blocks_walked +3, attn_stream_blocks_rect +4",
            {"attn_tri_sites": 1, "attn_stream_tri_sites": 5},
        ),
    ],
    # one layer at T = 8192, its backward split in two kernels
    "stream_split": [
        _site(STREAM, 8), _site(STREAM, 8, kernels=2),
        (
            "; traced: attn_stream_tri_sites +3, "
            "attn_stream_blocks_walked +108, attn_stream_blocks_rect +192",
            dict(zip(STREAM, (3, 0, 108, 192))),
        ),
    ],
    # a ring hop: traced offsets, the rectangular grid, three kernels
    "stream_rectangle": [
        _site(STREAM, 0), _site(STREAM, 0, kernels=2),
        (
            "; traced: attn_stream_rect_sites +3",
            dict(zip(STREAM, (0, 3, 0, 0))),
        ),
    ],
    # every other count is of the train step built since: the worker's
    # reference check (a forward pass before any step) is not in it, a
    # twin that came whole out of a cache of executables traced nothing
    # and moves nothing, and a step is said once
    "delta_rule_plain": [
        dict(zip(GDN, (2, 8, 0))),
        ("", {"gdn_sites": 0}),  # no step built
        "eval",
        ("", {"gdn_sites": 0}),  # and an evaluation is no step
        "step_donating",
        dict(zip(GDN, (2, 16, 0))),
        (
            "; traced: gdn_sites =2, gdn_chunk_steps =16",
            dict(zip(GDN, (2, 16, 0))),
        ),
        ("", {}),  # said once
        "step_safe",
        ("", dict(zip(GDN, (2, 16, 0)))),
    ],
    # a step whose mixers have heads of whole lane tiles
    "delta_rule_in_the_kernels": [
        "step_donating",
        dict(zip(GDN + PASS, (1, 8, 1, 1))),
        (
            "; traced: gdn_sites =1, gdn_chunk_steps =8, "
            "gdn_kernel_sites =1, gdn_pass_kernel_sites =1",
            dict(zip(GDN + PASS, (1, 8, 1, 1))),
        ),
    ],
    # a layer traced twice under ``jax.checkpoint`` counts twice in both,
    # and without recomputation every mixer is one site
    "convolution_under_checkpoint": [
        dict(zip(CONV, (2, 2))),  # the reference check
        "step_donating",
        dict(zip(CONV, (4, 4))),
        ("; traced: conv_sites =4, conv_kernel_sites =4",
         dict(zip(CONV, (4, 4)))),
        "step_donating",
        dict(zip(CONV, (2, 2))),
        ("; traced: conv_sites =2, conv_kernel_sites =2",
         dict(zip(CONV, (2, 2)))),
    ],
    # a latent attention's 24-wide scores called 128 wide beside three
    # KDA mixers, then the same under ``remat``: the fields hold the step
    # traced last
    "score_lanes_beside_the_delta_rule": [
        dict(zip(GDN + LANES, (3, 12, 0, 128, 24))),
        "step_donating",
        dict(zip(GDN + LANES, (3, 24, 0, 128, 24))),
        (
            "; traced: gdn_sites =3, gdn_chunk_steps =24, "
            "attn_score_lanes =128, attn_score_lanes_used =24",
            dict(zip(GDN + LANES, (3, 24, 0, 128, 24))),
        ),
        "step_donating",
        dict(zip(GDN + LANES, (6, 36, 0, 128, 24))),
        (
            "; traced: gdn_sites =6, gdn_chunk_steps =36, "
            "attn_score_lanes =128, attn_score_lanes_used =24",
            dict(zip(GDN + LANES, (6, 36, 0, 128, 24))),
        ),
    ],
    # a resize onto a mesh the kernels refuse: the step built there is
    # traced anew, and what it did not count reads 0, not the old step's
    "a_rebuilt_step_resets_what_it_did_not_count": [
        "step_donating",
        dict(zip(CONV, (2, 2))),
        ("; traced: conv_sites =2, conv_kernel_sites =2",
         dict(zip(CONV, (2, 2)))),
        "step_donating",
        {"conv_sites": 2},
        ("; traced: conv_sites =2", dict(zip(CONV, (2, 0)))),
    ],
    # a recomputed model of five attention layers, then the same without
    # ``remat``: every site is asked, so the field falls back to 0
    "attention_outputs_a_recomputed_layer_keeps": [
        "step_donating",
        dict(zip(LANES + KEPT, (640, 640, 5))),
        (
            "; traced: attn_score_lanes =640, attn_score_lanes_used =640, "
            "attn_kept_sites =5",
            dict(zip(LANES + KEPT, (640, 640, 5))),
        ),
        "step_donating",
        dict(zip(LANES + KEPT, (640, 640, 0))),
        (
            "; traced: attn_score_lanes =640, attn_score_lanes_used =640",
            dict(zip(LANES + KEPT, (640, 640, 0))),
        ),
    ],
    # six KDA mixers in the kernels at T = 8192, recomputed: each is traced
    # as the primal and through the rule, the primal's forward pass is one
    # the backward pass does not run and holds no step; then the same
    # without ``remat``
    "delta_rule_mixers_a_recomputed_layer_keeps": [
        "step_donating",
        dict(zip(GDN + GDN_KEPT, (12, 1536, 12, 6))),
        (
            "; traced: gdn_sites =12, gdn_chunk_steps =1536, "
            "gdn_kernel_sites =12, gdn_kept_sites =6",
            dict(zip(GDN + GDN_KEPT, (12, 1536, 12, 6))),
        ),
        "step_donating",
        dict(zip(GDN, (6, 1536, 6))),
        (
            "; traced: gdn_sites =6, gdn_chunk_steps =1536, "
            "gdn_kernel_sites =6",
            dict(zip(GDN + GDN_KEPT, (6, 1536, 6, 0))),
        ),
    ],
    # four layers that hold a share of the experts beside five attention
    # layers, recomputed: the reference check's forward pass counted
    # before the step's build is not in it, and a model that holds every
    # expert built later resets the field
    "share_layers_whose_first_round_is_kept": [
        dict(zip(SHARE, (4,))),
        "step_donating",
        dict(zip(LANES + KEPT + SHARE, (640, 640, 5, 4))),
        (
            # in the order the names were first counted in
            "; traced: moe_share_kept_sites =4, attn_score_lanes =640, "
            "attn_score_lanes_used =640, attn_kept_sites =5",
            dict(zip(LANES + KEPT + SHARE, (640, 640, 5, 4))),
        ),
        "step_donating",
        dict(zip(LANES, (640, 640))),
        (
            "; traced: attn_score_lanes =640, attn_score_lanes_used =640",
            dict(zip(LANES + KEPT + SHARE, (640, 640, 0, 0))),
        ),
    ],
    # two Mamba-1 scans in the kernels at T = 64 (a forward of 64 steps
    # and a backward of 128 each), three differential layers of four
    # pairs, a memory unit and a cross-attention; then the same under
    # ``remat``, whose scans walk their forward twice
    "scans_pairs_and_the_layers_that_read_another": [
        dict(zip(SSCAN + DIFF + XDEC, (2, 2, 128, 12, 12, 1, 1))),  # check
        "step_donating",
        dict(zip(SSCAN + DIFF + XDEC, (2, 2, 384, 12, 12, 1, 1))),
        (
            "; traced: sscan_sites =2, sscan_kernel_sites =2, "
            "sscan_serial_steps =384, attn_diff_pairs =12, "
            "attn_diff_score_calls =12, xdec_memory_reads =1, "
            "xdec_kv_reads =1",
            dict(zip(SSCAN + DIFF + XDEC, (2, 2, 384, 12, 12, 1, 1))),
        ),
        "step_donating",
        dict(zip(SSCAN + DIFF, (2, 0, 512, 12, 24))),
        (
            "; traced: sscan_sites =2, sscan_serial_steps =512, "
            "attn_diff_pairs =12, attn_diff_score_calls =24",
            dict(zip(SSCAN + DIFF + XDEC, (2, 0, 512, 12, 24, 0, 0))),
        ),
    ],
    # both scopes on one line: the kernels' totals first
    "both_scopes_on_one_line": [
        "step_safe",
        _site(STREAM, 8),
        {"attn_score_lanes": 128, "attn_score_lanes_used": 128},
        (
            "; traced: attn_stream_tri_sites +1, "
            "attn_stream_blocks_walked +36, attn_stream_blocks_rect +64, "
            "attn_score_lanes =128, attn_score_lanes_used =128",
            {"attn_stream_tri_sites": 1, "attn_score_lanes": 128},
        ),
    ],
}


@pytest.mark.parametrize("case", sorted(FOLDS))
def test_the_trainer_folds_the_registry_into_its_stats_and_its_line(
    case, fresh
):
    trainer = _trainer()
    stats = trainer.pipeline_stats
    for move in FOLDS[case]:
        if callable(move):
            move()
        elif isinstance(move, dict):
            for name, n in move.items():
                trace_counts.count(name, n)
        elif isinstance(move, str):
            assert ElasticTrainer._first_build(trainer, move) == move
            assert (trainer._counts_before_step is None) == (
                not move.startswith("step_")
            )
            trainer._built.clear()  # the next build of it is a first too
        else:
            said, fields = move
            assert ElasticTrainer._fold_trace_counts(trainer) == said
            assert {k: getattr(stats, k) for k in fields} == fields
            assert trainer._counts_before_step is None
    assert set(trace_counts.snapshot()) <= set(stats.as_dict())


# -- every name counted is a field, and the loop knows no kernel ------------

_SMALL = dict(
    vocab_size=64, model_dim=32, num_heads=2, mlp_dim=32, max_seq_len=64,
    dtype="float32", param_dtype="float32",
)
_MIXERS = dict(
    ssm_heads=4, ssm_head_dim=16, ssm_state=16, ssm_groups=2, ssm_chunk=16,
    gdn_key_heads=2, gdn_value_heads=2, gdn_key_dim=16, gdn_value_dim=16,
    gdn_chunk=16, positions="none", rmsnorm=True, tie_embeddings=False,
    dense_mlp_dim=32,
)
_SHARE = dict(
    num_layers=4, layer_pattern="*E*E", num_experts=8, moe_top_k=2,
    experts_held=2, router="sigmoid", shared_expert_dim=16,
    positions="none", rmsnorm=True, tie_embeddings=False,
)
_PHI = dict(
    num_layers=12, layer_pattern="S-W-S-*-U-C-", first_layer=14,
    attn_window=16, attn_kind="diff", attn_bias=True, num_kv_heads=2,
    attn_head_dim=8, positions="none", swiglu=True, dense_mlp_dim=32,
    sscan_state=8, sscan_dt_rank=2, sscan_chunk=16,
    **dict(_SMALL, num_heads=4),
)
# one toy a family of the benchmark's configurations, and the families of
# names a traced train step of it counts under
TOYS = {
    "dense": (TransformerConfig(num_layers=2, **_SMALL), (FUSED, LANES)),
    # the block a loop calls is traced once under ``jax.checkpoint``: one
    # site whose outputs the wrapper keeps
    "dense_remat": (
        TransformerConfig(num_layers=2, remat=True, **_SMALL),
        (FUSED, LANES, KEPT),
    ),
    "grouped_queries": (
        TransformerConfig(num_layers=1, num_kv_heads=1, **_SMALL),
        (STREAM, EDGE, LANES),
    ),
    "mamba2_and_delta_rule": (
        TransformerConfig(
            num_layers=3, layer_pattern="MG*", **_SMALL, **_MIXERS
        ),
        (GDN, CONV, GATE, SSD, FUSED, LANES),
    ),
    "vector_decay_and_latent_attention": (
        TransformerConfig(
            num_layers=2, layer_pattern="G*", gdn_decay="channel",
            gdn_decay_bound=-5.0,
            gdn_gate="head_sigmoid", attn_kind="latent", kv_latent_dim=16,
            qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, rope=True,
            **_SMALL, **dict(_MIXERS, positions=""),
        ),
        (GDN, CONV, GATE, FUSED, LANES),
    ),
    # the same recomputed: a mixer the wrapper keeps the pass's arrays of
    "vector_decay_and_latent_attention_remat": (
        TransformerConfig(
            num_layers=2, layer_pattern="G*", gdn_decay="channel",
            gdn_decay_bound=-5.0, remat=True,
            gdn_gate="head_sigmoid", attn_kind="latent", kv_latent_dim=16,
            qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, rope=True,
            **_SMALL, **dict(_MIXERS, positions=""),
        ),
        (GDN, CONV, GATE, FUSED, LANES, KEPT, GDN_KEPT),
    ),
    # two of eight experts held in each of two layers, then the same
    # recomputed: a share layer is one site either way
    "a_share_of_the_experts": (
        TransformerConfig(**_SHARE, **_SMALL), (FUSED, LANES, SHARE),
    ),
    "a_share_of_the_experts_remat": (
        TransformerConfig(remat=True, **_SHARE, **_SMALL),
        (FUSED, LANES, KEPT, SHARE),
    ),
    # window and global attention layers in one model, heads grouped: the
    # streaming kernels, the window layers' on the band
    "window_and_global_attention": (
        TransformerConfig(
            num_layers=3, layer_pattern="W*W", attn_window=16,
            num_kv_heads=1, positions="window", rmsnorm=True,
            mixer_out_norm=True, embed_scale=True, **_SMALL,
        ),
        (STREAM, WINDOW, EDGE, LANES),
    ),
    # phi4flash's six kinds in one model, and the same recomputed: two
    # scans (the plain statement: 96 channels are no lane tile), window,
    # full and cross differential attention, a memory unit
    "scans_and_differential_attention": (
        TransformerConfig(sscan_inner=96, **_PHI),
        (SSCAN, CONV, DIFF, XDEC, STREAM, WINDOW, EDGE, LANES),
    ),
    "scans_and_differential_attention_remat": (
        TransformerConfig(sscan_inner=128, remat=True, **_PHI),
        (SSCAN, CONV, DIFF, XDEC, STREAM, WINDOW, EDGE, LANES, KEPT),
    ),
    # a looped model, recomputed: two sandwich-normed blocks run three
    # times over the same weights, an exit after every pass
    "layers_run_several_times_remat": (
        TransformerConfig(
            num_layers=4, layer_pattern="*-*-", mixer_out_norm=True,
            rmsnorm=True, rope=True, swiglu=True, dense_mlp_dim=32,
            tie_embeddings=False, ut_steps=3, ut_entropy_weight=0.05,
            remat=True, **_SMALL,
        ),
        (FUSED, LANES, KEPT, UT),
    ),
    # latent attention whose query passes a latent, rotated by a YaRN
    # table on interleaved pairs, the query scaled past 16 positions,
    # before a share of the experts; recomputed
    "a_latent_query_and_a_scaled_table_remat": (
        TransformerConfig(
            attn_kind="latent", q_latent_dim=16, kv_latent_dim=16,
            qk_nope_dim=8, qk_rope_dim=8, v_head_dim=16, rope=True,
            rope_scaling="yarn", rope_factor=8.0, rope_original_len=16,
            rope_mscale_all_dim=1.0, rope_pairs="interleaved",
            attn_pos_scale_beta=0.1, remat=True,
            **dict(_SHARE, router="softmax", positions=""), **_SMALL,
        ),
        (FUSED, LANES, KEPT, SHARE, SCALED),
    ),
}


@pytest.mark.parametrize("toy", sorted(TOYS))
def test_every_name_a_traced_step_counts_is_a_stats_field(toy, monkeypatch):
    """Whatever a module counts lands in ``PipelineStats`` by its name
    alone: a name that is no field would fail the trainer's ``setattr``
    silently into an attribute nothing reads."""
    # the attention kernels' own path, interpreted: where their sites count
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fa, "_interpret_default", lambda: True)
    cfg, families = TOYS[toy]
    tx = build_optimizer("adamw", lr=1e-3)
    mesh = build_mesh(MeshConfig(), jax.devices()[:1])
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    state = jax.eval_shape(lambda p: TrainState(
        step=jnp.zeros((), jnp.int32), params=p, opt_state=tx.init(p),
    ), params)
    x = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    trainer = _trainer()
    before = trace_counts.snapshot()
    ElasticTrainer._first_build(trainer, "step_donating")
    build_train_step(cfg, mesh, tx, donate=False).lower(state, x, x)
    step = trace_counts.since(before)
    assert set(trace_counts.snapshot()) <= FIELDS
    assert {name for name, n in step.items() if n} <= set(sum(families, ()))
    for family in families:
        assert any(step[name] for name in family), family
    said = ElasticTrainer._fold_trace_counts(trainer)
    stats = trainer.pipeline_stats
    for name, n in step.items():
        if name not in trace_counts.RUNNING_TOTALS:
            assert getattr(stats, name) == n, name
            assert (f"{name} ={n}" in said) == bool(n)


KERNEL_MODULES = {
    "dlrover_tpu.ops.flash_attention", "dlrover_tpu.ops.gated_delta",
    "dlrover_tpu.ops.gated_delta_kernels", "dlrover_tpu.ops.mamba2",
    "dlrover_tpu.ops.conv_kernels", "dlrover_tpu.ops.selective_scan",
    "dlrover_tpu.ops.ssd_kernels",
    "dlrover_tpu.models.transformer",
}


def test_the_loop_imports_no_module_that_counts():
    """``trainer/elastic/trainer.py`` names no kernel module and nothing
    of ``models.transformer``, at the top or inside a function: a new
    count is one ``count(...)`` and one field, never an edit there."""
    path = os.path.join(
        ROOT, "dlrover_tpu", "trainer", "elastic", "trainer.py"
    )
    with open(path) as f:
        tree = ast.parse(f.read(), filename=path)
    named = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            named |= {alias.name for alias in node.names}
        elif isinstance(node, ast.ImportFrom) and node.module:
            named.add(node.module)
            named |= {f"{node.module}.{a.name}" for a in node.names}
    assert "dlrover_tpu.common.trace_counts" in named  # the walk sees them
    assert not named & KERNEL_MODULES


# -- the delta rule's serial pass as kernels (ISSUE 65) ----------------------

PASS_METRIC = "gdn.pass_kernel_sites_share"


def _reader(metric):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location("reader_under_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _delta_rule_grad(kind):
    """``gated_delta_chunked`` under ``grad`` at shapes the kernels take,
    traced and not run, as a jaxpr: two key heads of 128 / 128 (each of
    two value heads where the decay is a scalar), four chunks of 16."""
    from dlrover_tpu.ops import gated_delta

    B, T, Hk, d, C = 1, 64, 2, 128, 16
    Hv = Hk if kind == "channel" else 2 * Hk
    f32 = jnp.float32
    args = [
        jax.ShapeDtypeStruct(shape, f32) for shape in (
            (B, T, Hk, d), (B, T, Hk, d), (B, T, Hv, d), (B, T, Hv),
            (B, T, Hv, d) if kind == "channel" else (B, T, Hv),
        )
    ]
    return jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(gated_delta.gated_delta_chunked(*a, C)),
        argnums=range(5),
    ))(*args).jaxpr


@pytest.mark.parametrize("kind", ["head", "channel"])
def test_no_reader_of_the_chunk_kernels_takes_a_kernel_of_the_pass(kind):
    """``kernel.gdn_roofline`` sums every kernel whose name holds its
    ``NAME`` against work that counts the four chunk kernels alone, and
    ``gdn.fwd_kernel_runs_per_step`` counts names that hold its ``NAME``
    and ``FORWARD``: the pass's two kernels are neither's."""
    from test_recomputed_layer import _kernels_in

    names = _kernels_in(_delta_rule_grad(kind))
    of_the_pass = sorted(n for n in names if "gdn_" not in n)
    assert of_the_pass == ["delta_state_pass", "delta_state_pass_rev"]
    assert len(names) == 6  # the kind's four around them, once each
    assert set(names.values()) == {1}
    roofline = _reader("kernel.gdn_roofline")
    runs = _reader("gdn.fwd_kernel_runs_per_step")
    for name in of_the_pass:
        assert roofline.NAME not in name.lower()
        assert not (
            runs.NAME in name.lower() and runs.FORWARD in name.lower()
        )


@pytest.mark.parametrize("kind", ["head", "channel"])
def test_the_chunk_steps_count_the_same_whoever_walks_them(kind, monkeypatch):
    """A site of the kernels counts its pass in the kernels too, and the
    chunk states walked in order are the plain way's number."""
    from dlrover_tpu.ops import gated_delta_kernels

    before = trace_counts.snapshot()
    _delta_rule_grad(kind)
    assert added(before, GDN + PASS) == (1, 8, 1, 1)
    monkeypatch.setattr(gated_delta_kernels, "fits", lambda *a, **k: False)
    before = trace_counts.snapshot()
    _delta_rule_grad(kind)
    assert added(before, GDN + PASS) == (1, 8, 0, 0)


@pytest.mark.parametrize("model,pipeline,reads", [
    ({"layer_pattern": "GGG*"}, None, None),
    # the parent of PR 65: sites, and no such counter
    ({"layer_pattern": "GGG*"}, {"gdn_sites": 6, "gdn_kernel_sites": 6}, None),
    ({"layer_pattern": "GGG*"}, {"gdn_pass_kernel_sites": 0}, None),
    ({"layer_pattern": "GGG*"},
     {"gdn_sites": 6, "gdn_pass_kernel_sites": 6}, 100.0),
    ({"layer_pattern": "GGG*"},
     {"gdn_sites": 12, "gdn_pass_kernel_sites": 3}, 25.0),
    ({"layer_pattern": "M*"},
     {"gdn_sites": 6, "gdn_pass_kernel_sites": 6}, None),
], ids=["no_stats", "no_counter", "no_site", "all", "some", "no_such_layer"])
def test_the_pass_share_reader_reads_the_two_counts(model, pipeline, reads):
    run = types.SimpleNamespace(
        config={"model": model}, window={"pipeline": pipeline}
    )
    assert _reader(PASS_METRIC).read(run) == reads


def test_the_pass_share_is_listed_in_the_cells_its_rule_takes():
    """The metric's ``workloads``, found by its name and not by its place
    in ``per_layer``, are the cells its ``CELLS`` rule takes: the three
    whose configuration names a Gated DeltaNet layer."""
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == PASS_METRIC]
    reader = _reader(PASS_METRIC)
    assert (entry["unit"], entry["layer"], entry["moves"]) == (
        reader.UNIT, reader.LAYER, reader.MOVES
    )
    taken = [w["name"] for w in bench["workloads"] if reader.CELLS(w)]
    assert entry["workloads"] == taken == [
        "qwen3-next-80b-a3b-d4.steady", "ling-3.0-flash-d7.steady",
        "olmo-hybrid-7b-d4.steady",
    ]


# -- the Mamba-2 chunked scan as kernels (ISSUE 66) ---------------------------

SSD_METRIC = "ssd.kernel_sites_share"
# every reader that picks kernels out of a trace by a part of their names
NAMED = [
    "kernel.attn_roofline", "kernel.attn_window_roofline",
    "kernel.gdn_roofline", "kernel.sscan_roofline",
    "kernel.moe_gmm_roofline", "attn.fwd_kernel_runs_per_step",
    "gdn.fwd_kernel_runs_per_step", "moe.gmm_runs_per_step",
]


def _ssd_grad():
    """``ssd_kernels.ssd`` under ``grad`` at the smallest shapes its rule
    takes, traced and not run, as a jaxpr."""
    from dlrover_tpu.ops import ssd_kernels

    B, T, H, P, G, N = 1, 256, 2, 64, 1, 128
    f32 = jnp.float32
    args = [
        jax.ShapeDtypeStruct(shape, f32) for shape in (
            (B, T, H, P), (B, T, H), (H,), (B, T, G, N), (B, T, G, N), (H,),
        )
    ]
    return jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(ssd_kernels.ssd(*a, 128)), argnums=range(6),
    ))(*args).jaxpr


@pytest.mark.parametrize("metric", NAMED)
def test_no_reader_of_another_kernel_takes_a_kernel_of_the_scan(metric):
    """The scan's two kernels, once each under ``grad``, hold no part of a
    name by which a reader sums or counts another family's kernels."""
    from test_recomputed_layer import _kernels_in

    names = _kernels_in(_ssd_grad())
    assert names == {"ssd_scan_fwd": 1, "ssd_scan_bwd": 1}
    reader = _reader(metric)
    parts = [
        getattr(reader, attr).lower().lstrip("%")
        for attr in ("NAME", "PREFIX") if hasattr(reader, attr)
    ]
    assert parts, metric
    for name in names:
        assert not any(part in name.lower() for part in parts)


def test_a_scan_is_a_site_once_on_either_way(monkeypatch):
    from dlrover_tpu.ops import mamba2, ssd_kernels

    fits = TransformerConfig(
        num_layers=1, layer_pattern="M", **dict(
            _MIXERS, ssm_head_dim=64, ssm_state=128, ssm_chunk=128,
        ), **_SMALL,
    )
    toy = TransformerConfig(
        num_layers=1, layer_pattern="M", **_SMALL, **_MIXERS
    )
    for cfg, T, want in ((fits, 256, (1, 1)), (toy, 64, (1, 0))):
        p = init_params(jax.random.PRNGKey(0), cfg)["layers"][0]["ssm"]
        u = jax.ShapeDtypeStruct((1, T, cfg.model_dim), jnp.float32)
        before = trace_counts.snapshot()
        jax.make_jaxpr(lambda u: mamba2.mamba2_mixer(u, p, cfg, 1e-5))(u)
        assert added(before, SSD) == want
    monkeypatch.setattr(ssd_kernels, "fits", lambda *a: False)
    before = trace_counts.snapshot()
    p = init_params(jax.random.PRNGKey(0), fits)["layers"][0]["ssm"]
    u = jax.ShapeDtypeStruct((1, 256, fits.model_dim), jnp.float32)
    jax.make_jaxpr(lambda u: mamba2.mamba2_mixer(u, p, fits, 1e-5))(u)
    assert added(before, SSD) == (1, 0)


@pytest.mark.parametrize("model,pipeline,reads", [
    ({"layer_pattern": "MEM*"}, None, None),
    # the parent of PR 66: no such counter
    ({"layer_pattern": "MEM*"}, {"conv_sites": 4, "gate_sites": 4}, None),
    ({"layer_pattern": "MEM*"}, {"ssd_sites": 0, "ssd_kernel_sites": 0}, None),
    ({"layer_pattern": "MEM*"}, {"ssd_sites": 4}, None),
    ({"layer_pattern": "MEM*"}, {"ssd_sites": 4, "ssd_kernel_sites": 4}, 100.0),
    ({"layer_pattern": "MEM*"}, {"ssd_sites": 4, "ssd_kernel_sites": 1}, 25.0),
    ({"layer_pattern": "GGG*"}, {"ssd_sites": 4, "ssd_kernel_sites": 4}, None),
], ids=["no_stats", "no_counter", "no_site", "half_a_counter", "all", "some",
        "no_such_layer"])
def test_the_scan_share_reader_reads_the_two_counts(model, pipeline, reads):
    run = types.SimpleNamespace(
        config={"model": model}, window={"pipeline": pipeline}
    )
    assert _reader(SSD_METRIC).read(run) == reads


def test_the_scan_share_reads_100_on_a_toy_that_fits():
    """The two counts of a traced step whose one Mamba-2 layer fits the
    kernels, through the trainer's fold and the reader."""
    cfg = TransformerConfig(
        num_layers=1, layer_pattern="M", **dict(
            _MIXERS, ssm_head_dim=64, ssm_state=128, ssm_chunk=128,
        ), **dict(_SMALL, max_seq_len=256),
    )
    tx = build_optimizer("adamw", lr=1e-3)
    mesh = build_mesh(MeshConfig(), jax.devices()[:1])
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    state = jax.eval_shape(lambda p: TrainState(
        step=jnp.zeros((), jnp.int32), params=p, opt_state=tx.init(p),
    ), params)
    x = jax.ShapeDtypeStruct((1, 256), jnp.int32)
    trainer = _trainer()
    ElasticTrainer._first_build(trainer, "step_donating")
    build_train_step(cfg, mesh, tx, donate=False).lower(state, x, x)
    ElasticTrainer._fold_trace_counts(trainer)
    stats = trainer.pipeline_stats
    assert (stats.ssd_sites, stats.ssd_kernel_sites) == (1, 1)
    run = types.SimpleNamespace(
        config={"model": {"layer_pattern": cfg.layer_pattern}},
        window={"pipeline": stats.as_dict()},
    )
    assert _reader(SSD_METRIC).read(run) == 100.0


def test_the_scan_share_is_listed_in_the_cells_its_rule_takes():
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == SSD_METRIC]
    reader = _reader(SSD_METRIC)
    assert (entry["unit"], entry["layer"], entry["moves"]) == (
        reader.UNIT, reader.LAYER, reader.MOVES
    )
    taken = [w["name"] for w in bench["workloads"] if reader.CELLS(w)]
    assert entry["workloads"] == taken == [
        "nemotron3-nano-30b-a3b-d9.steady"
    ]
