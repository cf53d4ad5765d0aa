"""The ``phi4flash`` family (Phi-4-mini-flash-reasoning's) at a small size
on the CPU: the program held to ``benchmark/references/phi4flash.py`` (loss
and every gradient leaf); the window exact at 512 keys in rows of 700 and
1500; the gradients into the two shared values equal to the sum over their
readers; ``remat``; a published layer's matmuls in float8 failing the
stated tolerance; and what refuses the new kinds. (The scan itself:
``test_selective_scan.py``.)"""

import importlib.util
import json
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.common import trace_counts
from dlrover_tpu.models import transformer
from dlrover_tpu.models.config import LAYER_KINDS, TransformerConfig
from dlrover_tpu.models.train import build_train_step
from dlrover_tpu.models.transformer import (
    _diff_attention,
    _diff_head_order,
    diff_lambda_init,
    forward,
    init_kv_cache,
    init_params,
    logical_axes,
    loss_fn,
)
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.pipeline import _check_pipeline_cfg
from dlrover_tpu.trainer.elastic.trainer import build_optimizer

RTOL = 2e-5
GRAD_RTOL = 2e-4  # a gradient sums more terms in another order
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PATTERN = "S-W-S-*-U-C-"  # the cut's: published layers 14-19
WINDOW_KEYS = 12  # under T = 64, and no multiple of any block
REF_KW = dict(window=WINDOW_KEYS)


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmark", "references", "phi4flash.py")
    spec = importlib.util.spec_from_file_location("phi4flash_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg(**over):
    cfg = TransformerConfig(
        vocab_size=256, num_layers=12, layer_pattern=PATTERN, first_layer=14,
        attn_window=WINDOW_KEYS, positions="none", attn_kind="diff",
        attn_bias=True, model_dim=48, num_heads=8, num_kv_heads=4,
        attn_head_dim=8, mlp_dim=40, dense_mlp_dim=40, max_seq_len=64,
        rmsnorm=False, norm_eps=1e-5, swiglu=True, tie_embeddings=True,
        sscan_inner=96, sscan_state=8, sscan_dt_rank=3, sscan_conv=4,
        sscan_chunk=24, dtype="float32", param_dtype="float32",
    )
    return replace(cfg, **over)


def _weights(cfg, seed=1):
    """Seeded weights with every norm, bias, ``lambda`` and skip off its
    initial value."""
    params = init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 512))

    def jitter(leaf):
        if leaf.size > 4096:
            return leaf
        return leaf + 0.1 * jax.random.normal(next(keys), leaf.shape)

    params = jax.tree_util.tree_map(jitter, params)
    params["embed"]["tokens"] = 0.3 * params["embed"]["tokens"]
    return params


def _batch(cfg, seed=0, rows=2, seq=64):
    rng = np.random.default_rng(seed)
    data = rng.integers(0, cfg.vocab_size, (rows, seq + 1)).astype(np.int32)
    return data[:, :-1], data[:, 1:]


def _rel(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _grads(fn, params):
    return jax.jit(jax.value_and_grad(fn))(params)


# a key's bias moves every score of a query alike: the softmax drops it,
# and its gradient is rounding
NO_GRADIENT = ("['bk']",)


def _hold_leaves(g_got, g_want, rtol=GRAD_RTOL):
    got = jax.tree_util.tree_leaves_with_path(g_got)
    want = jax.tree_util.tree_leaves(g_want)
    assert len(got) == len(want)
    for (path, a), b in zip(got, want):
        name = jax.tree_util.keystr(path)
        if name.endswith(NO_GRADIENT):
            assert float(jnp.max(jnp.abs(a))) < 1e-6, name
            continue
        assert float(jnp.max(jnp.abs(b))) > 0, name
        assert _rel(a, b) <= rtol, name
    return len(got)


# -- the whole model against the reference --------------------------------


def test_loss_and_every_gradient_leaf_match_the_reference(ref):
    cfg = _cfg()
    params = _weights(cfg)
    x, y = _batch(cfg)
    got, g_got = _grads(lambda p: loss_fn(p, x, y, cfg, None), params)
    want, g_want = _grads(lambda p: ref.loss(p, x, y, **REF_KW), params)
    assert abs(float(got) - float(want)) <= RTOL * abs(float(want))
    # the table and the final norm's two; every layer's norm of two; two
    # scans of 10, two attentions of 13, a memory unit of 2, a cross-
    # attention of 9, six feed-forwards of 3
    assert _hold_leaves(g_got, g_want) == (
        3 + 12 * 2 + 2 * 10 + 2 * 13 + 2 + 9 + 6 * 3
    )


@pytest.mark.parametrize("pattern,first", [
    ("S-W-S-W-S-*-U-C-U-C-", 12),  # two readers of each shared value
    ("S-*-U-", 16), ("*-C-", 17), ("W-*-", 15),
])
def test_other_cuts_match_the_reference(ref, pattern, first):
    cfg = _cfg(layer_pattern=pattern, num_layers=len(pattern),
               first_layer=first,
               attn_window=WINDOW_KEYS if "W" in pattern else 0)
    params = _weights(cfg)
    x, y = _batch(cfg)
    kw = dict(REF_KW, first_layer=first)
    got, g_got = _grads(lambda p: loss_fn(p, x, y, cfg, None), params)
    want, g_want = _grads(lambda p: ref.loss(p, x, y, **kw), params)
    assert abs(float(got) - float(want)) <= RTOL * abs(float(want))
    _hold_leaves(g_got, g_want)


def test_the_tree_and_its_axes():
    cfg = _cfg()
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    axes = logical_axes(cfg)
    for kind, layer, names in zip(PATTERN, shapes["layers"], axes["layers"]):
        mixer = LAYER_KINDS[kind]
        assert set(layer) == set(names) == {"norm", mixer}
        assert set(layer[mixer]) == set(names[mixer])
        assert set(layer["norm"]) == {"scale", "bias"}  # a LayerNorm
    full, cross = shapes["layers"][6]["attn"], shapes["layers"][10]["xattn"]
    # a cross-attention projects queries only
    assert set(full) - set(cross) == {"wk", "wv", "bk", "bv"}
    assert full["subln"].shape == (16,) and full["lambda_q1"].shape == (8,)
    assert shapes["layers"][8]["gmu"]["w_in"].shape == (48, 96)
    assert "lm_head" not in shapes and "positions" not in shapes["embed"]


def test_lambda_init_is_the_published_layers():
    assert diff_lambda_init(0) == pytest.approx(0.2)
    assert diff_lambda_init(17) == pytest.approx(0.8 - 0.6 * np.exp(-5.1))
    # the pairs in the order a grouped-query call wants them: 8 query
    # heads on 4 key heads, two query pairs a key pair
    assert list(_diff_head_order(4, 2)) == [0, 2, 1, 3, 4, 6, 5, 7]
    assert list(_diff_head_order(2, 2)) == [0, 1, 2, 3]


@pytest.mark.parametrize("switch", [
    {"first_layer": 0},  # lambda_init of other layers
    {"attn_window": 20},
    {"layer_pattern": "S-*-S-*-U-C-", "attn_window": 0},  # no window
    {"layer_pattern": "S-W-S-*-S-C-"},  # a scan where the memory unit is
    {"layer_pattern": "S-W-S-*-U-*-"},  # keys of its own
    {"norm_eps": 1e-3},
], ids=lambda s: "-".join(f"{k}={v}" for k, v in s.items()))
def test_each_switch_is_worth_more_than_ten_tolerances(ref, switch):
    cfg = _cfg()
    x, y = _batch(cfg)
    want = float(jax.jit(
        lambda p: ref.loss(p, x, y, **REF_KW)
    )(_weights(cfg)))
    off = replace(cfg, **switch)
    got = float(jax.jit(lambda p: loss_fn(p, x, y, off, None))(_weights(off)))
    assert abs(got - want) > 10 * RTOL * abs(want), (got, want)


def test_remat_gives_the_same_loss_and_gradients():
    cfg = _cfg()
    params = _weights(cfg)
    x, y = _batch(cfg)
    before = trace_counts.snapshot()
    a, ga = _grads(lambda p: loss_fn(p, x, y, cfg, None), params)
    plain = trace_counts.since(before)
    b, gb = _grads(
        lambda p: loss_fn(p, x, y, replace(cfg, remat=True), None), params
    )
    assert abs(float(a) - float(b)) <= RTOL * abs(float(a))
    for u, v in zip(
        jax.tree_util.tree_leaves(ga), jax.tree_util.tree_leaves(gb)
    ):
        assert _rel(v, u) <= GRAD_RTOL
    # what one trace of the model counts: the two scans, the three
    # differential layers' 4 pairs each once, one reader of each kind
    assert {n: plain[n] for n in (
        "sscan_sites", "sscan_kernel_sites", "attn_diff_pairs",
        "attn_diff_score_calls", "xdec_memory_reads", "xdec_kv_reads",
        "conv_sites",
    )} == {
        "sscan_sites": 2, "sscan_kernel_sites": 0, "attn_diff_pairs": 12,
        "attn_diff_score_calls": 12, "xdec_memory_reads": 1,
        "xdec_kv_reads": 1, "conv_sites": 2,
    }


# -- the window, exactly ---------------------------------------------------


@pytest.mark.parametrize("T", [700, 1500])
def test_the_window_is_512_keys_exactly(ref, T):
    """One window layer at the published window in rows longer than it:
    the program's differential attention against the reference's full
    masked scores, and a key 512 back is not seen while one 511 back is."""
    cfg = _cfg(layer_pattern="W", num_layers=1, attn_window=512,
               first_layer=15, max_seq_len=T)
    layer = _weights(cfg)["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(3), (1, T, 48))
    run = jax.jit(lambda x: _diff_attention(
        x, layer, cfg, None, "W", 15
    )[0])
    got = run(x)

    def plain(x):
        u = ref._layer_norm(x, layer["norm"], 1e-5)
        a = layer["attn"]
        k, v = ref._keys_values(u, a)
        return x + ref._diff_attention(u, a, k, v, 15, 1e-5, 512)

    with jax.default_matmul_precision("highest"):
        want = jax.jit(plain)(x[0])
    assert _rel(got[0], want) <= 2e-5
    # move token 100: queries up to 611 see it, query 612 does not
    bump = jax.random.normal(jax.random.PRNGKey(4), (48,))
    moved = run(x.at[0, 100].add(bump))
    changed = np.any(np.abs(np.asarray(moved - got))[0] > 1e-6, axis=-1)
    assert changed[100:612].all() and not changed[612:].any()
    assert not changed[:100].any()


# -- what one layer reads of another -----------------------------------------


def test_shared_values_take_the_sum_of_their_readers_gradients(ref):
    """Two memory units and two cross-attentions read one scan output and
    one layer's keys and values: the gradient that reaches each shared
    value is the sum over its readers, one reader at a time."""
    pattern = "S-*-U-C-U-C-"
    cfg = _cfg(layer_pattern=pattern, num_layers=12, first_layer=16,
               attn_window=0)
    params = _weights(cfg)
    x, y = _batch(cfg)
    readers = {"U": [4, 8], "C": [6, 10]}
    taps = {"S": jnp.zeros((2, 64, 96)), "*k": jnp.zeros((2, 4, 64, 8)),
            "*v": jnp.zeros((2, 4, 64, 16))}

    def loss_with(taps_of):
        """The loss with a zero added to what reader ``i`` reads: its
        gradient is what that reader sends back."""
        real_gmu, real_diff = transformer._gmu_block, (
            transformer._diff_attention
        )
        seen = {"U": 0, "C": 0}

        def gmu(x, layer, cfg, memory):
            tap = taps_of["U"][seen["U"]]
            seen["U"] += 1
            return real_gmu(x, layer, cfg, memory + tap["S"])

        def diff(x, layer, cfg, mesh, kind, published, shared=None):
            if kind == "C":
                tap = taps_of["C"][seen["C"]]
                seen["C"] += 1
                shared = (shared[0] + tap["*k"], shared[1] + tap["*v"])
            return real_diff(x, layer, cfg, mesh, kind, published, shared)

        transformer._gmu_block, transformer._diff_attention = gmu, diff
        try:
            return loss_fn(params, x, y, cfg, None)
        finally:
            transformer._gmu_block = real_gmu
            transformer._diff_attention = real_diff

    def all_readers(shared):
        return loss_with({"U": [shared] * 2, "C": [shared] * 2})

    def one_reader(kind, i):
        def fn(tap):
            taps_of = {"U": [taps] * 2, "C": [taps] * 2}
            taps_of[kind] = [
                tap if j == i else taps for j in range(2)
            ]
            return loss_with(taps_of)
        return fn

    whole = jax.grad(all_readers)(taps)
    parts = {
        kind: [jax.grad(one_reader(kind, i))(taps) for i in range(2)]
        for kind in readers
    }
    for name, kind in (("S", "U"), ("*k", "C"), ("*v", "C")):
        each = [part[name] for part in parts[kind]]
        assert all(float(jnp.max(jnp.abs(e))) > 0 for e in each), name
        assert _rel(each[0] + each[1], whole[name]) <= GRAD_RTOL, name
        # and neither reader alone
        assert _rel(each[0], whole[name]) > 1e-2, name
    # the source layers' own leaves against the reference, leaf by leaf
    _, g_got = _grads(lambda p: loss_fn(p, x, y, cfg, None), params)
    _, g_want = _grads(
        lambda p: ref.loss(p, x, y, first_layer=16, **REF_KW), params
    )
    for name in ("w_xproj", "w_dt", "A_log", "dt_bias", "D"):
        a, b = (g["layers"][0]["sscan"][name] for g in (g_got, g_want))
        assert _rel(a, b) <= GRAD_RTOL, name
    for name in ("wk", "wv", "bv"):
        a, b = (g["layers"][2]["attn"][name] for g in (g_got, g_want))
        assert _rel(a, b) <= GRAD_RTOL, name


# -- the tolerance refuses a lower precision ----------------------------------


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("dtype,refused", [
    (jnp.float8_e4m3fn, True), (jnp.bfloat16, False),
])
def test_float8_matmuls_fail_the_stated_tolerance(ref, dtype, refused, seed):
    """The control that set the configuration's ``reference_check.
    tolerance`` (``benchmark/tests/reference_on_chip.py``), at the toy's
    size: the reference with both operands of every matmul rounded to
    float8, the nearest precision below the stated one, is refused;
    rounded to bfloat16, the stated precision, it passes. The toy's limit
    lies between its own two readings as the configuration's does
    (``test_the_stated_tolerance_lies_between_its_readings``): over 2 x 64
    tokens bfloat16 reads 2e-4 to 1.1e-3 and float8 3e-3 to 1.5e-2."""
    tolerance = 2e-3
    cfg = _cfg()
    params = _weights(cfg, seed)
    x, y = _batch(cfg, seed)

    def to(a):
        return a.astype(dtype).astype(jnp.float32)

    def rounded(p):
        keep = ref.matmul, ref.einsum
        ref.matmul = lambda a, b: keep[0](to(a), to(b))
        ref.einsum = lambda s, a, b: keep[1](s, to(a), to(b))
        try:
            return ref.loss(p, x, y, **REF_KW)
        finally:
            ref.matmul, ref.einsum = keep

    want = float(jax.jit(lambda p: loss_fn(p, x, y, cfg, None))(params))
    got = float(jax.jit(rounded)(params))
    assert (abs(got - want) > tolerance) is refused, (got, want)


def test_the_stated_tolerance_lies_between_its_readings():
    """``reference_check`` of the configuration file records what set its
    tolerance on the chip: the program's distances from the reference and
    the float8 control's. The tolerance lies over every reading of the
    program, with room, and under the control's median."""
    with open(os.path.join(
        ROOT, "benchmark", "configs", "phi4-mini-flash-d6.json"
    )) as f:
        check = json.load(f)["reference_check"]
    program, control = check["program_abs_diff"], check["float8_abs_diff"]
    assert len(program) >= 8 and len(control) >= 3
    assert 2 * max(program) <= check["tolerance"]
    assert 3 * check["tolerance"] <= float(np.median(control))


# -- what refuses the new kinds, and nonsense ---------------------------------


def test_sequence_parallelism_refuses_a_recurrence_and_the_pairs():
    mesh = build_mesh(MeshConfig(sp=2), jax.devices()[:2])
    scan = _cfg(layer_pattern="S-", num_layers=2, attn_window=0,
                attn_kind="", attn_bias=False)
    with pytest.raises(NotImplementedError, match="recurrence"):
        build_train_step(scan, mesh, build_optimizer("adamw", lr=1e-3))
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), scan))
    x = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    with pytest.raises(NotImplementedError, match="recurrence"):
        jax.eval_shape(lambda p, x: forward(p, x, scan, mesh), params, x)
    pairs = _cfg(layer_pattern="*-C-", num_layers=4, attn_window=0)
    with pytest.raises(NotImplementedError, match="differential"):
        build_train_step(pairs, mesh, build_optimizer("adamw", lr=1e-3))
    with pytest.raises(NotImplementedError, match="window"):
        build_train_step(_cfg(), mesh, build_optimizer("adamw", lr=1e-3))


def test_the_pipeline_refuses_a_layer_that_reads_another():
    with pytest.raises(ValueError, match="read another layer"):
        _check_pipeline_cfg(_cfg(), 2)
    with pytest.raises(ValueError, match="read another layer"):
        _check_pipeline_cfg(
            _cfg(layer_pattern="*-C-", num_layers=4, attn_window=0), 2
        )


@pytest.mark.parametrize("pattern", ["S-", "S-U-", "*-C-"])
def test_cached_decoding_refuses_the_new_kinds(pattern):
    cfg = _cfg(layer_pattern=pattern, num_layers=len(pattern), attn_window=0)
    with pytest.raises(NotImplementedError, match="layer_pattern"):
        init_kv_cache(cfg, 1, 64)


@pytest.mark.parametrize("nonsense", [
    dict(layer_pattern="U-S-W-S-*-C-"),  # a memory unit before any scan
    dict(layer_pattern="S-W-S-C-U-*-"),  # a cross-attention before any "*"
    dict(layer_pattern="W-C-W-C-W-C-"),  # a window layer hands nothing on
    dict(sscan_inner=0),
    dict(sscan_dt_rank=0),
    dict(attn_kind=""),  # a "C" layer is differential
    dict(num_heads=7, num_kv_heads=7),
    dict(num_heads=12, num_kv_heads=8),  # 6 query pairs on 4 key pairs
    dict(attn_gate="sigmoid"),
    dict(qk_norm=True),
    dict(positions="", rope=True),
    dict(attn_kind="diff", layer_pattern="", num_layers=2, attn_window=0),
])
def test_construction_refuses(nonsense):
    with pytest.raises(ValueError):
        _cfg(**nonsense)


def test_attn_bias_is_the_differential_kinds():
    with pytest.raises(ValueError, match="attn_bias"):
        TransformerConfig(attn_bias=True)
