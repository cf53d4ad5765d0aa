"""A Kimi-Delta-Attention / latent-attention / grouped-expert hybrid with a
leading dense layer against its plain reference (ISSUE 45).

A tiny ``ling3`` (pattern ``G-GE*EGE``, width 48; Kimi Delta Attention of 4
heads of 8 / 12 in chunks of 16, its decay a vector over the key's channels
bounded at -5, one sigmoid gate a head; latent attention of 4 heads, a
latent of 20, scores 16 + 8 wide and values 12 wide, a-head q / k norms,
rotary positions on the 8 rope dims; a dense SwiGLU layer of 40; 16 SwiGLU
experts of 24 in 4 groups of which a token keeps 2, 3 a token by sigmoid
score + bias, times 2.5, beside an ungated SwiGLU shared one of 40; plain
norms, 64 tokens a row) in float32 on the CPU, seeded weights: the
program's ``loss_fn`` and every gradient leaf against
``benchmark/references/ling3.py`` (loaded by path), the chunked vector-decay
rule against the recurrence one step at a time, its kernels (``gdn_channel_*``,
ISSUE 46) under ``interpret=True`` against the plain statement and the
recurrence at heads of 128, the latent attention
against the written-out full matrix, group-limited routing against a
brute-force mask, a chip's share of the experts adding up to the whole
layer, and the counts of a built step.

The tolerance is 2e-5 relative (2e-4 for a gradient leaf), as
``test_qwen3_next.py``'s and for its reason.
"""

import functools
import hashlib
import importlib.util
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.common import trace_counts
from dlrover_tpu.models.config import TransformerConfig, tiny
from dlrover_tpu.models.train import TrainState, build_train_step
from dlrover_tpu.models.transformer import (
    _latent_attention,
    init_kv_cache,
    init_params,
    logical_axes,
    loss_fn,
)
from dlrover_tpu.ops import gated_delta
from dlrover_tpu.ops import gated_delta_kernels as kernels
from dlrover_tpu.ops.gated_delta import (
    gated_delta_chunked,
    gated_delta_mixer,
    head_gated_rmsnorm,
    l2norm,
    unit_lower_inverse,
    unit_lower_inverse_blocked,
)
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.moe import (
    init_moe_params,
    keep_best_groups,
    moe_layer_local,
    route,
)
from dlrover_tpu.trainer.elastic.trainer import build_optimizer
from lowering_fingerprint import inner_numbers_off
from pass_parity import check_pass
from trace_counted import GDN, GDN_KEPT, LANES, added

RTOL = 2e-5
GRAD_RTOL = 2e-4  # a gradient sums more terms in another order
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOP_K = 3
GROUPS = (4, 2)
REF_KW = dict(
    top_k=TOP_K, n_group=GROUPS[0], topk_group=GROUPS[1], balance_weight=1e-2
)


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmark", "references", "ling3.py")
    spec = importlib.util.spec_from_file_location("ling3_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref_loss(ref):
    """The reference's loss on the tiny model's weights and batch."""
    cfg = _cfg()
    x, y = _batch(cfg)
    return float(
        jax.jit(lambda p: ref.loss(p, x, y, **REF_KW))(_weights(cfg))
    )


def _cfg(**over):
    cfg = TransformerConfig(
        vocab_size=256, num_layers=8, layer_pattern="G-GE*EGE", model_dim=48,
        num_heads=4, mlp_dim=24, dense_mlp_dim=40, max_seq_len=64, rope=True,
        rope_theta=6e6, rmsnorm=True, norm_eps=1e-6, swiglu=True,
        tie_embeddings=False, qk_norm=True, qk_norm_span="head",
        attn_kind="latent", kv_latent_dim=20, qk_nope_dim=16, qk_rope_dim=8,
        v_head_dim=12, num_experts=16, moe_top_k=TOP_K, norm_topk_prob=True,
        router="sigmoid", routed_scale=2.5, router_groups=GROUPS[0],
        router_groups_kept=GROUPS[1], router_balance_weight=1e-2,
        router_z_weight=0.0, shared_expert_dim=40, gdn_value_heads=4,
        gdn_key_heads=4, gdn_key_dim=8, gdn_value_dim=12, gdn_chunk=16,
        gdn_decay="channel", gdn_decay_bound=-5.0, gdn_gate="head_sigmoid",
        dtype="float32", param_dtype="float32",
    )
    return replace(cfg, **over)


def _weights(cfg, seed=1):
    """Seeded weights with every norm weight, step bias, decay scale and
    selection bias off its initial value, and a token table small enough
    that the norms' eps counts. (Seed 0 draws a first layer in which one
    head's 8-wide key is all but zero at some token: the unit-length
    norm's cotangent is then 1 / |k| times float32's rounding, and
    program and reference each stand 8e-4 from the same gradients in
    float64.)"""
    params = init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 256))

    def jitter(path, leaf):
        name = getattr(path[-1], "key", None) or getattr(
            path[-1], "name", None
        )
        if name in ("scale", "norm", "dt_bias", "A_log", "bias"):
            return leaf + 0.1 * jax.random.normal(next(keys), leaf.shape)
        return leaf

    params = jax.tree_util.tree_map_with_path(jitter, params)
    params["embed"]["tokens"] = 0.1 * params["embed"]["tokens"]
    return params


def _batch(cfg, seed=0, rows=2):
    rng = np.random.default_rng(seed)
    data = rng.integers(0, cfg.vocab_size, (rows, 65)).astype(np.int32)
    return data[:, :-1], data[:, 1:]


def _rel(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# -- the whole model against the reference --------------------------------


@pytest.mark.parametrize("held", [(0, 0), (4, 8)])
def test_loss_and_every_gradient_leaf_match_the_reference(ref, held):
    count, offset = held
    cfg = _cfg(experts_held=count, experts_offset=offset)
    params = _weights(cfg)
    x, y = _batch(cfg)
    got, g_got = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, x, y, cfg, None)
    ))(params)
    want, g_want = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, x, y, experts_offset=offset, **REF_KW)
    ))(params)
    assert abs(float(got) - float(want)) <= RTOL * abs(float(want))
    got_leaves = jax.tree_util.tree_leaves_with_path(g_got)
    want_leaves = jax.tree_util.tree_leaves(g_want)
    # tables and final norm; 3 KDA layers of 9 + norm; the latent
    # attention of 5 + 2 head norms + norm; the dense layer of 3 + norm;
    # 3 expert blocks of gate, 3 routed, bias, 3 shared + norm
    assert len(got_leaves) == len(want_leaves) == 3 + 3 * 10 + 8 + 4 + 3 * 9
    for (path, a), b in zip(got_leaves, want_leaves):
        name = jax.tree_util.keystr(path)
        if name.endswith(".bias"):  # steers the choice, takes no gradient
            assert not np.any(np.asarray(a)) and not np.any(np.asarray(b))
            continue
        assert float(jnp.max(jnp.abs(b))) > 0, name
        assert _rel(a, b) <= GRAD_RTOL, name


@pytest.mark.parametrize(
    "switch",
    [
        {"gdn_decay_bound": -4.0},
        {"gdn_gate": "silu"},
        {"gdn_decay": "head"},
        {"qk_norm": False},
        {"rope_theta": 1e4},
        {"positions": "none"},
        {"kv_latent_dim": 24},
        {"router_groups_kept": 4},
        {"router_groups": 2, "router_groups_kept": 1},
        {"routed_scale": 1.0},
        {"shared_expert_dim": 0},
        {"norm_topk_prob": False},
        {"router_balance_weight": 0.0},
        {"dense_mlp_dim": 24},
        {"norm_eps": 1e-5},
    ],
    ids=lambda s: next(iter(s)),
)
def test_each_switch_is_worth_more_than_ten_tolerances(ref_loss, switch):
    cfg = _cfg()
    params = _weights(cfg)
    x, y = _batch(cfg)
    off = replace(cfg, **switch)
    p = params
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), off))
    if jax.tree_util.tree_map(lambda a: a.shape, shapes) != (
        jax.tree_util.tree_map(lambda a: a.shape, params)
    ):
        # a tree the other kind can run: same draws where both have them
        p = _weights(off)
    got = float(jax.jit(lambda p: loss_fn(p, x, y, off, None))(p))
    assert abs(got - ref_loss) > 10 * RTOL * abs(ref_loss), (got, ref_loss)


def test_remat_gives_the_same_loss_and_gradients():
    cfg = _cfg()
    params = _weights(cfg)
    x, y = _batch(cfg)
    run = lambda c: jax.jit(jax.value_and_grad(  # noqa: E731
        lambda p: loss_fn(p, x, y, c, None)
    ))(params)
    (a, ga), (b, gb) = run(cfg), run(replace(cfg, remat=True))
    assert abs(float(a) - float(b)) <= RTOL * abs(float(a))
    for u, v in zip(
        jax.tree_util.tree_leaves(ga), jax.tree_util.tree_leaves(gb)
    ):
        if np.any(np.asarray(u)):
            assert _rel(v, u) <= GRAD_RTOL


# -- the delta rule with a decay a key channel ------------------------------


def delta_rule_sequential(q, k, v, beta, g):
    """The recurrence one step at a time, a head's state [d_k, d_v]:
    q, k, g [B, T, H, d_k], v [B, T, H, d_v], beta [B, T, H]."""
    B, T, H, dk = q.shape

    def step(S, x):
        q_t, k_t, v_t, b_t, g_t = x
        S = jnp.exp(g_t)[..., None] * S
        read = jnp.einsum("bhdv,bhd->bhv", S, k_t)
        S = S + k_t[..., None] * (b_t[..., None] * (v_t - read))[..., None, :]
        return S, jnp.einsum("bhdv,bhd->bhv", S, q_t)

    xs = [jnp.moveaxis(a, 1, 0) for a in (q, k, v, beta, g)]
    _, o = jax.lax.scan(step, jnp.zeros((B, H, dk, v.shape[-1])), xs)
    return jnp.moveaxis(o, 0, 1)


# (beta from, to, g from, to): ``fast`` sits at the bound through every
# whole chunk, where a sub-block's own square divides by 15 steps' decay
REGIMES = {
    "mid": (0.2, 0.8, -1.0, -0.01),
    "fast_full_strength": (0.95, 1.0, -5.0, -4.9),
    "slow_faint": (0.0, 0.05, -1e-3, 0.0),
    "mixed": (0.0, 1.0, -5.0, 0.0),
}


def _rule_inputs(regime="mid", seed=0, B=2, T=128, H=2, dk=8, dv=12):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    lo_b, hi_b, lo_g, hi_g = REGIMES[regime]
    return (
        l2norm(jax.random.normal(ks[0], (B, T, H, dk))) * dk**-0.5,
        l2norm(jax.random.normal(ks[1], (B, T, H, dk))),
        jax.random.normal(ks[2], (B, T, H, dv)),
        jax.random.uniform(ks[3], (B, T, H), minval=lo_b, maxval=hi_b),
        jax.random.uniform(ks[4], (B, T, H, dk), minval=lo_g, maxval=hi_g),
    )


@pytest.mark.parametrize("regime", sorted(REGIMES))
@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_chunked_vector_decay_rule_is_the_recurrence(chunk, regime):
    """Forward and every cotangent, over several chunks (T = 128), with
    sub-blocks (chunk 64: four of 16) and without (8, 16)."""
    args = _rule_inputs(regime)
    with jax.default_matmul_precision("highest"):
        want = delta_rule_sequential(*args)
        got = gated_delta_chunked(*args, chunk)
        assert got.shape == want.shape and np.all(np.isfinite(got))
        assert _rel(got, want) <= RTOL
        w = jax.random.normal(jax.random.PRNGKey(9), want.shape)
        g_want = jax.grad(
            lambda *a: jnp.sum(delta_rule_sequential(*a) * w),
            argnums=(0, 1, 2, 3, 4),
        )(*args)
        g_got = jax.grad(
            lambda *a: jnp.sum(gated_delta_chunked(*a, chunk) * w),
            argnums=(0, 1, 2, 3, 4),
        )(*args)
    for a, b in zip(g_got, g_want):
        assert np.all(np.isfinite(a))
        assert _rel(a, b) <= GRAD_RTOL


@pytest.mark.parametrize("C", [1, 2, 5, 16, 48, 64])
def test_the_inverse_by_halves_is_the_inverse(C):
    A = jnp.tril(
        0.3 * jax.random.normal(jax.random.PRNGKey(C), (3, C, C)), -1
    )
    want = np.linalg.inv(np.eye(C) - np.asarray(A, np.float64))
    got = unit_lower_inverse_blocked(A)
    assert got.shape == A.shape
    assert np.max(np.abs(np.asarray(got) - want)) <= 1e-5 * np.max(
        np.abs(want)
    )
    if C > 1:
        w = jax.random.normal(jax.random.PRNGKey(1), A.shape)
        g_got = jax.grad(
            lambda A: jnp.sum(unit_lower_inverse_blocked(A) * w)
        )(A)
        g_want = jax.grad(
            lambda A: jnp.sum(jnp.linalg.inv(jnp.eye(C) - A) * w)
        )(A)
        assert _rel(jnp.tril(g_got, -1), jnp.tril(g_want, -1)) <= GRAD_RTOL


@pytest.mark.parametrize("c", [0.5, 0.99])
def test_the_inverse_by_halves_holds_where_the_keys_are_parallel(c):
    """``A = -c`` everywhere below the diagonal: a chunk of identical keys
    written at strength ``c`` with no decay. The inverse is of order 1;
    the product form's powers reach 1e17 and cancel to nothing float32
    can hold, which is what broke the first slowly decaying cell."""
    A = -c * jnp.tril(jnp.ones((64, 64)), -1)
    want = np.linalg.inv(np.eye(64) - np.asarray(A, np.float64))
    assert np.max(np.abs(want)) <= 1.0
    got = np.asarray(unit_lower_inverse_blocked(A))
    assert np.max(np.abs(got - want)) <= 1e-5
    product = np.asarray(unit_lower_inverse(A))
    assert np.max(np.abs(product - want)) > 10.0


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_nearly_parallel_keys_that_hardly_decay_stay_the_recurrence(dtype):
    """Keys of cosine 0.99, written at full strength, decaying by 0.1 % a
    step, over 32 chunks: the chunked rule stays the recurrence (of order
    0.1) where the product-form inverse gave 1e8 in the first chunk and
    NaN by the third."""
    B, T, H, dk, dv = 1, 2048, 2, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    base = l2norm(jax.random.normal(ks[0], (1, 1, H, dk)))
    noise = jax.random.normal(ks[1], (B, T, H, dk)) / np.sqrt(dk)
    k = l2norm(0.99 * base + np.sqrt(1 - 0.99**2) * noise)
    q = l2norm(jax.random.normal(ks[2], (B, T, H, dk))) * dk**-0.5
    v = jax.random.normal(ks[3], (B, T, H, dv))
    beta = jnp.full((B, T, H), 0.99)
    g = jnp.full((B, T, H, dk), -1e-3)
    want = delta_rule_sequential(q, k, v, beta, g)
    act = jnp.dtype(dtype)
    got = gated_delta_chunked(
        q.astype(act), k.astype(act), v.astype(act), beta, g, 64
    ).astype(jnp.float32)
    assert np.all(np.isfinite(got))
    assert _rel(got, want) <= (2e-4 if dtype == "float32" else 5e-2)


def test_a_vector_of_one_decay_is_the_scalar_rule():
    """With every channel of a head decaying alike, the vector rule is
    Gated DeltaNet's, whichever way each is chunked."""
    q, k, v, beta, g = _rule_inputs("mid")
    scalar = g[..., 0]
    with jax.default_matmul_precision("highest"):
        want = gated_delta_chunked(q, k, v, beta, scalar, 16)
        got = gated_delta_chunked(
            q, k, v, beta, jnp.broadcast_to(scalar[..., None], g.shape), 16
        )
    assert _rel(got, want) <= RTOL


# sha256 of what ``_head_rule_lowering`` gave at the commit before ISSUE 45
HEAD_RULE_LOWERING = (
    "fcfb2ce9ed7f814a58a6c22dc0cf52e20ed9f99ecd2e330d4a9a0a96c093fc16"
)


def _head_rule_lowering() -> str:
    """What the scalar-decay rule's plain statement, forward and every
    cotangent, lowers to (2 key and 4 value heads of 8 / 12, 64 steps in
    chunks of 16: no site of the kernels)."""
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    fn = jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(jnp.sin(gated_delta_chunked(*a, 16))),
        argnums=(0, 1, 2, 3, 4),
    ))
    return fn.lower(
        shape(2, 64, 2, 8), shape(2, 64, 2, 8), shape(2, 64, 4, 12),
        shape(2, 64, 4), shape(2, 64, 4),
    ).as_text()


def test_the_scalar_rule_is_bit_equal_to_what_it_gave_before(monkeypatch):
    """The pass and its reversal are one code for both kinds of decay:
    for the ``head`` kind they lower to the program they lowered to before
    the ``channel`` kind came, operation for operation. The names ISSUE 56
    gave the pass's residuals lower to nothing, but each is an equation
    the lowering emits as a function of its own before it inlines it, and
    that moves the numbers that tell an inner function's copies apart
    (``@triu_68`` -> ``@triu_73``): with the names off the text is the
    recorded one byte for byte, with them on the same but those numbers."""
    named = _head_rule_lowering()
    monkeypatch.setattr(gated_delta, "checkpoint_name", lambda x, name: x)
    plain = _head_rule_lowering()
    assert hashlib.sha256(plain.encode()).hexdigest() == HEAD_RULE_LOWERING
    assert inner_numbers_off(named) == inner_numbers_off(plain)
    assert not any(name in named for name in gated_delta.KEPT)


def test_the_vector_rule_refuses_what_it_cannot_chunk():
    q, k, v, beta, g = _rule_inputs("mid", T=48)
    with pytest.raises(ValueError, match="whole chunks"):
        gated_delta_chunked(q, k, v, beta, g, 32)
    with pytest.raises(ValueError, match="as many key heads"):
        gated_delta_chunked(
            q, k, jnp.tile(v, (1, 1, 2, 1)), jnp.tile(beta, (1, 1, 2)),
            jnp.tile(g, (1, 1, 2, 1)), 16,
        )


@pytest.mark.parametrize("d_k,d_v,chunk,T,dtype,kernel", [
    (128, 128, 64, 8192, "bfloat16", True),  # the cell
    (128, 128, 64, 1024, "float32", True),
    (128, 128, 64, 192, "float32", True),  # chunks that do not pair up
    (64, 128, 64, 8192, "bfloat16", False),  # a key head of half a tile
    (128, 192, 64, 8192, "bfloat16", False),
    (128, 128, 8, 64, "bfloat16", False),  # half a bfloat16 sublane tile
    (128, 128, 20, 20, "float32", False),
    (128, 128, 64, 8192 + 32, "bfloat16", False),  # a ragged sequence
])
def test_the_kernels_take_a_vector_decay_where_the_shapes_allow(
    d_k, d_v, chunk, T, dtype, kernel
):
    """``fits`` reads the shapes alone, and a site with a vector decay goes
    the way it says: traced at the shapes, the site is counted as the
    kernels' or not."""
    # the vector kind's kernels read lane blocks of a head: whole tiles
    assert kernels.fits(d_k, d_v, chunk, T, dtype, channel=True) is kernel
    before = trace_counts.snapshot()
    shape = lambda *s: jax.ShapeDtypeStruct(s, dtype)  # noqa: E731
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    args = (
        shape(1, T, 2, d_k), shape(1, T, 2, d_k), shape(1, T, 2, d_v),
        f32(1, T, 2), f32(1, T, 2, d_k),
    )
    if T % chunk:  # neither way takes it
        with pytest.raises(ValueError, match="whole chunks"):
            jax.eval_shape(lambda *a: gated_delta_chunked(*a, chunk), *args)
        return
    out = jax.eval_shape(lambda *a: gated_delta_chunked(*a, chunk), *args)
    assert out.shape == (1, T, 2, d_v) and out.dtype == dtype
    assert added(before, GDN) == (1, T // chunk, int(kernel))


def test_a_vector_decay_site_of_kernel_shapes_is_the_kernels_and_says_so():
    """Heads of whole lane tiles: the site is counted as the kernels' with
    a vector decay as with a scalar one, and lowers to the kind's own."""
    before = trace_counts.snapshot()
    q, k, v, beta, g = _rule_inputs("mid", B=1, T=64, dk=128, dv=128)
    text = jax.jit(lambda *a: gated_delta._delta_rule(*a, 16, None)).lower(
        q, k, v, beta, g
    ).as_text()
    assert added(before, GDN) == (1, 4, 1)
    assert "f32[4,1,2,16,16]" not in text  # no chunk's square around them
    jax.jit(lambda *a: gated_delta._delta_rule(*a, 16, None)).lower(
        q, k, v, beta, g[..., 0]
    )
    assert added(before, GDN) == (2, 8, 2)


# the vector-decay kernels (``gdn_channel_*``) under ``interpret=True``:
# heads of 128, chunks of 64, two heads, four chunks
KERNEL_TOL = {"float32": (1e-5, GRAD_RTOL), "bfloat16": (2e-2, 2e-2)}


def _kernel_inputs(regime, dtype="float32", **shape):
    shape = dict(dict(B=1, T=256, H=2, dk=128, dv=128), **shape)
    q, k, v, beta, g = _rule_inputs(regime, **shape)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), beta, g


def _value_and_cotangents(rule, args, seed=9):
    o = jax.jit(rule)(*args)
    w = jax.random.normal(jax.random.PRNGKey(seed), o.shape)
    grads = jax.jit(jax.grad(
        lambda *a: jnp.sum(rule(*a).astype(jnp.float32) * w),
        argnums=range(5),
    ))(*args)
    return [np.asarray(x, np.float32) for x in (o, *grads)]


@pytest.mark.parametrize("regime", sorted(REGIMES))
@pytest.mark.parametrize("dtype", sorted(KERNEL_TOL))
def test_vector_decay_kernels_are_the_plain_statement(dtype, regime):
    """``o`` and every cotangent (``dq, dk, dv, dbeta, dg``) of the rule
    through the kernels against ``_chunked_channel``, in the regimes the
    plain statement is held to the recurrence in."""
    tol, grad_tol = KERNEL_TOL[dtype]
    args = _kernel_inputs(regime, dtype)
    got = _value_and_cotangents(lambda *a: gated_delta_chunked(*a, 64), args)
    want = _value_and_cotangents(
        lambda *a: gated_delta._chunked_channel(*a, 64), args
    )
    for name, a, b, t in zip(
        ["o", "dq", "dk", "dv", "dbeta", "dg"], got, want,
        [tol] + [grad_tol] * 5,
    ):
        assert a.shape == b.shape and np.all(np.isfinite(a)), name
        assert np.max(np.abs(a - b)) <= t * np.max(np.abs(b)), name


@pytest.mark.parametrize("n,blocks", [(6, 3), (5, 5)])
@pytest.mark.parametrize("dtype", sorted(KERNEL_TOL))
def test_the_pass_kernels_take_a_vector_decay(dtype, n, blocks):
    """The serial pass with the state in VMEM (ISSUE 65) where ``a`` is a
    row over the key's channels and the keys come decayed (``delta`` None):
    four heads of 128 / 128, the state carried across 3 and 5 runs of
    chunks, forward and reversed, against the plain scan."""
    check_pass(n, 1, 4, 1, 16, 128, 128, True, dtype, blocks)


@pytest.mark.parametrize("regime", sorted(REGIMES))
def test_vector_decay_rule_through_the_kernels_is_the_recurrence(regime):
    """Forward and in every gradient against one step at a time; the counts
    say which way the site went (a site, its steps, in the kernels; the
    backward pass its steps again)."""
    before = trace_counts.snapshot()
    args = _kernel_inputs(regime)
    want = jax.jit(delta_rule_sequential)(*args)
    got = jax.jit(lambda *a: gated_delta_chunked(*a, 64))(*args)
    assert added(before, GDN) == (1, 4, 1)
    assert got.shape == want.shape == (1, 256, 2, 128)
    assert _rel(got, want) <= RTOL

    def grads(fn):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=range(5)
        ))(*args)

    for a, b in zip(
        grads(lambda *a: gated_delta_chunked(*a, 64)),
        grads(delta_rule_sequential),
    ):
        assert _rel(a, b) <= GRAD_RTOL
    assert added(before, GDN) == (2, 12, 2)


def test_under_recomputation_every_counted_site_is_counted_in_the_kernels():
    """A layer under ``jax.checkpoint`` is traced as the primal once and
    through the ``custom_vjp`` rules once more: the pass counts a site each
    time, and so does the kernels' forward, or the share of sites in the
    kernels would read 50 where every site is theirs (the Ling cell's
    first traced run did)."""
    before = trace_counts.snapshot()
    args = _kernel_inputs("mid")
    layer = jax.checkpoint(lambda *a: gated_delta_chunked(*a, 64))
    jax.jit(jax.grad(
        lambda *a: jnp.sum(layer(*a) ** 2), argnums=range(5)
    )).lower(*args)
    sites, steps, in_kernels = added(before, GDN)
    assert (sites, in_kernels) == (2, 2)
    assert steps == 3 * 4  # forward, the forward again, backward


@pytest.mark.parametrize("T,chunk", [(192, 64), (64, 8), (80, 40)])
def test_the_kernels_square_whatever_chunks_there_are(T, chunk):
    """Three chunks (a square holds one, not two), chunks of 8 (no
    sub-blocks, no level of halves) and of 40 (not a power of two: the
    halves' masks go by position) are the recurrence too."""
    args = _kernel_inputs("mid", T=T)
    want = jax.jit(delta_rule_sequential)(*args)
    got = jax.jit(lambda *a: gated_delta_chunked(*a, chunk))(*args)
    assert _rel(got, want) <= RTOL


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_kernels_inverse_is_the_one_by_halves(dtype):
    """``test_nearly_parallel_keys_that_hardly_decay_stay_the_recurrence``'s
    case at heads of 128, through the kernels: with the product form in
    their place the first chunk reads 1e8 and the third NaN."""
    before = trace_counts.snapshot()
    B, T, H, d = 1, 2048, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    base = l2norm(jax.random.normal(ks[0], (1, 1, H, d)))
    noise = jax.random.normal(ks[1], (B, T, H, d)) / np.sqrt(d)
    k = l2norm(0.99 * base + np.sqrt(1 - 0.99**2) * noise)
    q = l2norm(jax.random.normal(ks[2], (B, T, H, d))) * d**-0.5
    v = jax.random.normal(ks[3], (B, T, H, d))
    beta = jnp.full((B, T, H), 0.99)
    g = jnp.full((B, T, H, d), -1e-3)
    want = jax.jit(delta_rule_sequential)(q, k, v, beta, g)
    got = jax.jit(lambda *a: gated_delta_chunked(*a, 64))(
        q.astype(dtype), k.astype(dtype), v.astype(dtype), beta, g
    ).astype(jnp.float32)
    assert added(before, GDN) == (1, 32, 1)
    assert np.all(np.isfinite(got))
    assert _rel(got, want) <= (2e-4 if dtype == "float32" else 5e-2)


def test_the_decay_stays_inside_its_bound_and_the_gate_is_a_head():
    """However large the projection, a step's log-decay lies in (bound,
    0): what the chunked rule's one division relies on. And the gate is
    ``w * RMSNorm(o) * sigmoid(z)``, one ``z`` a head."""
    cfg = _cfg()
    p = _weights(cfg)["layers"][0]["gdn"]
    assert p["w_z"].shape == (48, 4) and p["w_b"].shape == (48, 4)
    assert p["w_f"].shape == (48, 32) and p["dt_bias"].shape == (32,)
    assert p["A_log"].shape == (4,) and "w_ba" not in p
    u = 50.0 * jax.random.normal(jax.random.PRNGKey(3), (1, 64, 48))
    f = (u @ p["w_f"] + p["dt_bias"]).reshape(1, 64, 4, 8)
    g = cfg.gdn_decay_bound * jax.nn.sigmoid(
        jnp.exp(p["A_log"])[:, None] * f
    )
    assert float(g.min()) >= -5.0 and float(g.max()) <= 0.0
    assert float(g.min()) < -4.99 and float(g.max()) > -0.01
    assert np.all(np.isfinite(gated_delta_mixer(u, p, cfg, 1e-6)))
    o = jax.random.normal(jax.random.PRNGKey(4), (2, 5, 4 * 12))
    z = jax.random.normal(jax.random.PRNGKey(5), (2, 5, 4))
    w = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(6), (12,))
    heads = o.reshape(2, 5, 4, 12)
    want = heads / jnp.sqrt(
        jnp.mean(heads * heads, -1, keepdims=True) + 1e-6
    ) * w * jax.nn.sigmoid(z)[..., None]
    got = head_gated_rmsnorm(o, z, w, 1e-6)
    assert _rel(got, want.reshape(o.shape)) <= RTOL


# -- latent attention --------------------------------------------------------


def _latent_layer(cfg, seed=0):
    params = _weights(cfg, seed)
    return next(layer for layer in params["layers"] if "attn" in layer)


def _rotate(x, theta):
    """x [B, H, T, D]: pairs (i, i + D/2) by t * theta^(-2i/D)."""
    T, half = x.shape[2], x.shape[-1] // 2
    ang = jnp.arange(T)[:, None] * theta ** (-jnp.arange(half) / half)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
         x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1,
    )


def _latent_written_out(x, layer, cfg, qk_norm=True):
    """The layer as the source's equations state it: the full [T, T]
    score matrix over an unpadded 24-wide q and k, values 12 wide."""
    a = layer["attn"]
    rms = lambda t, w: t / jnp.sqrt(  # noqa: E731
        jnp.mean(t * t, -1, keepdims=True) + 1e-6
    ) * w
    h = rms(x, layer["norm"]["scale"])
    q = jnp.einsum("btd,dhk->bhtk", h, a["wq"])
    down = h @ a["w_kva"]
    c = rms(down[..., :20], a["kv_norm"]["scale"])
    kv = jnp.einsum("btc,chk->bhtk", c, a["w_kvb"])
    k_rope = jnp.repeat(down[:, None, :, 20:], 4, axis=1)
    k = jnp.concatenate([kv[..., :16], k_rope], -1)
    if qk_norm:
        q = rms(q, layer["q_norm"]["scale"])
        k = rms(k, layer["k_norm"]["scale"])
    q = jnp.concatenate([q[..., :16], _rotate(q[..., 16:], 6e6)], -1)
    k = jnp.concatenate([k[..., :16], _rotate(k[..., 16:], 6e6)], -1)
    s = jnp.einsum("bhqk,bhtk->bhqt", q, k) / jnp.sqrt(24.0)
    T = x.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqt,bhtk->bhqk", jax.nn.softmax(s, -1), kv[..., 16:])
    return x + jnp.einsum("bhtk,hkd->btd", o, a["wo"])


@pytest.mark.parametrize("qk_norm", [True, False])
def test_latent_attention_is_the_written_out_full_matrix(qk_norm):
    """The program pads q and k from 24 and v from 12 to one lane tile
    around its attention call; the written-out form pads nothing."""
    cfg = _cfg(qk_norm=qk_norm)
    layer = _latent_layer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 48))
    positions = jnp.broadcast_to(jnp.arange(64), (2, 64))
    with jax.default_matmul_precision("highest"):
        got = _latent_attention(x, layer, cfg, None, positions, "norm")
        want = _latent_written_out(x, layer, cfg, qk_norm)
    assert _rel(got - x, want - x) <= RTOL


def test_the_one_rotated_key_is_shared_and_only_the_rope_dims_turn():
    """Without the q / k norm a head's key is [its own 16 | the one
    rotated 8]: moving the rotated key's column block of ``w_kva`` moves
    every head's scores, and moving the positions moves nothing that an
    all-zero ``k_rope`` and ``q_rope`` would not."""
    cfg = _cfg(qk_norm=False)
    layer = _latent_layer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 64, 48))
    positions = jnp.broadcast_to(jnp.arange(64), (1, 64))
    run = functools.partial(
        _latent_attention, cfg=cfg, mesh=None, norm="norm"
    )
    base = run(x, layer, positions=positions)
    # one token's attention depends on where it stands ...
    shifted = run(x, layer, positions=positions * 3)
    assert _rel(shifted - x, base - x) > 1e-3
    # ... through the rope dims alone: with them zeroed it does not
    a = layer["attn"]
    flat = dict(
        layer, attn=dict(
            a, wq=a["wq"].at[..., 16:].set(0.0),
            w_kva=a["w_kva"].at[:, 20:].set(0.0),
        ),
    )
    assert np.array_equal(
        run(x, flat, positions=positions),
        run(x, flat, positions=positions * 3),
    )
    # the shared key has no head axis in the tree
    assert a["w_kva"].shape == (48, 20 + 8)
    assert a["w_kvb"].shape == (20, 4, 16 + 12)
    assert a["wq"].shape == (48, 4, 16 + 8) and a["wo"].shape == (4, 12, 48)
    normed = _latent_layer(_cfg())
    assert normed["q_norm"]["scale"].shape == (24,)
    assert normed["k_norm"]["scale"].shape == (24,)


def test_cached_decoding_refuses_a_latent():
    with pytest.raises(NotImplementedError, match="latent"):
        init_kv_cache(
            tiny(attn_kind="latent", kv_latent_dim=8, qk_nope_dim=8,
                 qk_rope_dim=4, v_head_dim=8, num_kv_heads=None), 1, 8,
        )


# -- group-limited routing ----------------------------------------------------


def _brute_force_choice(choose, groups, kept, k):
    """numpy, one token at a time: the k best entries inside the kept
    groups, a group scoring the sum of its two best."""
    T, E = choose.shape
    size = E // groups
    out = np.zeros((T, k), np.int64)
    for t in range(T):
        score = [
            np.sort(choose[t, g * size:(g + 1) * size])[-2:].sum()
            for g in range(groups)
        ]
        keep = np.argsort(score)[::-1][:kept]
        allowed = [e for e in range(E) if e // size in keep]
        out[t] = sorted(allowed, key=lambda e: -choose[t, e])[:k]
    return out


@pytest.mark.parametrize("kind", ["sigmoid", "softmax"])
def test_group_limited_routing_is_the_brute_force_mask(kind):
    logits = 2.0 * jax.random.normal(jax.random.PRNGKey(0), (96, 16))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (16,))
    kw = dict(kind=kind, scale=2.5)
    if kind == "sigmoid":
        kw["bias"] = bias
        choose = np.asarray(jax.nn.sigmoid(logits) + bias)
    else:
        choose = np.asarray(jax.nn.softmax(logits, -1))
    idx, gates, aux = route(logits, TOP_K, True, groups=GROUPS, **kw)
    want = _brute_force_choice(choose, *GROUPS, TOP_K)
    assert np.array_equal(np.sort(idx, -1), np.sort(want, -1))
    # the gate values are the chosen scores over their sum, times 2.5,
    # without the bias
    scores = np.asarray(
        jax.nn.sigmoid(logits) if kind == "sigmoid"
        else jax.nn.softmax(logits, -1)
    )
    vals = np.take_along_axis(scores, np.asarray(idx), -1)
    assert _rel(gates, 2.5 * vals / vals.sum(-1, keepdims=True)) <= RTOL
    assert int(aux["counts"].sum()) == 96 * TOP_K
    # the limit binds: the unlimited choice is another for some token
    free, _, _ = route(logits, TOP_K, True, **kw)
    assert not np.array_equal(np.sort(free, -1), np.sort(idx, -1))


def test_an_expert_outside_the_kept_groups_is_never_chosen():
    """Expert 0 scores highest for every token, alone in a group whose
    second best is the lowest anywhere: its group loses, and it with it."""
    logits = jax.random.normal(jax.random.PRNGKey(0), (64, 16))
    logits = logits.at[:, 0].set(30.0).at[:, 1:4].set(-30.0)
    logits = logits.at[:, 4:].add(8.0)
    idx, _, _ = route(
        logits, TOP_K, True, kind="sigmoid", groups=(4, 2)
    )
    assert not np.any(np.asarray(idx) < 4)
    free, _, _ = route(logits, TOP_K, True, kind="sigmoid")
    assert np.all(np.any(np.asarray(free) == 0, -1))
    masked = keep_best_groups(jax.nn.sigmoid(logits), 4, 2)
    assert np.all(np.isneginf(np.asarray(masked)[:, :4]))
    assert np.all(np.sum(np.isfinite(np.asarray(masked)), -1) == 8)


@pytest.mark.parametrize("groups", [(1, 1), (4, 4)])
def test_one_group_or_every_group_kept_is_the_unlimited_route(groups):
    logits = jax.random.normal(jax.random.PRNGKey(0), (64, 16))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (16,))
    kw = dict(kind="sigmoid", bias=bias, scale=2.5)
    want = route(logits, TOP_K, True, **kw)
    got = route(logits, TOP_K, True, groups=groups, **kw)
    for a, b in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    ):
        assert np.array_equal(a, b)


# -- a chip's share ----------------------------------------------------------

E, HELD = 32, 4
SHARE_GROUPS = (8, 4)


def _expert_block(held=0, seed=0):
    block = init_moe_params(
        jax.random.PRNGKey(seed), E, 32, 24, gated=True, held=held,
        selection_bias=True, shared_dim=40,
    )
    return block._replace(
        bias=0.2 * jax.random.normal(jax.random.PRNGKey(seed + 1), (E,))
    )


@functools.partial(jax.jit, static_argnames="held")
def _run(params, x, held=None):
    return moe_layer_local(
        params, x, axis_name=None, top_k=4, normalize=True, router="sigmoid",
        routed_scale=2.5, held=held, groups=SHARE_GROUPS,
    )


def test_the_shares_add_up_to_the_whole_block(ref):
    """Over all 8 offsets, the held experts' parts plus the shared expert
    counted once are the uncut block, the program's and the reference's,
    the group-limited choice made over all 32 columns every time."""
    whole = _expert_block()
    assert whole.shared_gate.shape == (32, 40)
    assert whole.shared_out_gate is None and whole.bias.shape == (E,)
    x = jax.random.normal(jax.random.PRNGKey(5), (96, 32))
    want, aux = _run(whole, x)
    plain = jax.jit(
        lambda x, p, offset: ref._experts(x, p, 4, *SHARE_GROUPS, 2.5, offset),
        static_argnums=2,
    )
    assert _rel(want, plain(x, whole, 0)[0]) <= RTOL
    shared_only = dict(shared_up=None, shared_down=None, shared_gate=None)
    total = jnp.zeros_like(want)
    for offset in range(0, E, HELD):
        cut = {
            name: getattr(whole, name)[offset:offset + HELD]
            for name in ("w_up", "w_down", "w_gate")
        }
        part, part_aux = _run(
            whole._replace(**cut, **shared_only), x, held=(offset, HELD)
        )
        # the router saw all 32, whatever is held
        assert np.array_equal(part_aux["load"], aux["load"])
        assert _rel(
            _run(whole._replace(**cut), x, held=(offset, HELD))[0],
            plain(x, whole._replace(**cut), offset)[0],
        ) <= RTOL
        total = total + part
    shared = (
        jax.nn.silu(x @ whole.shared_gate) * (x @ whole.shared_up)
    ) @ whole.shared_down
    assert _rel(total + shared, want) <= RTOL


def test_share_gradients_match_the_reference(ref):
    share = _expert_block(held=HELD)
    x = jax.random.normal(jax.random.PRNGKey(7), (64, 32))

    def probe(fn):
        return lambda p, x: jnp.sum(jnp.sin(fn(p, x)))

    got = jax.grad(probe(lambda p, x: _run(p, x, held=(4, HELD))[0]), (0, 1))(
        share, x
    )
    want = jax.grad(probe(
        lambda p, x: ref._experts(x, p, 4, *SHARE_GROUPS, 2.5, 4)[0]
    ), (0, 1))(share, x)
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(got),
        jax.tree_util.tree_leaves(want),
    ):
        if jax.tree_util.keystr(path).endswith(".bias"):
            assert not np.any(np.asarray(a)) and not np.any(np.asarray(b))
            continue
        assert np.any(np.asarray(b))
        assert _rel(a, b) <= GRAD_RTOL


# -- the tree and the configuration ------------------------------------------


def test_the_tree_has_one_mixer_a_layer_and_axes_to_match():
    cfg = _cfg(experts_held=4, experts_offset=8)
    params = init_params(jax.random.PRNGKey(0), cfg)
    kinds = [
        next(k for k in ("gdn", "mlp", "attn", "moe") if k in layer)
        for layer in params["layers"]
    ]
    assert kinds == ["gdn", "mlp", "gdn", "moe", "attn", "moe", "gdn", "moe"]
    for kind, layer in zip(kinds, params["layers"]):
        assert set(layer) - {"q_norm", "k_norm"} == {"norm", kind}
    dense = params["layers"][1]["mlp"]
    assert {k: v.shape for k, v in dense.items()} == {
        "w_gate": (48, 40), "w_up": (48, 40), "w_down": (40, 48),
    }
    moe = params["layers"][3]["moe"]
    assert moe.gate.shape == (48, 16) and moe.w_up.shape == (4, 48, 24)
    assert moe.bias.shape == (16,) and moe.shared_out_gate is None
    # the decay starts inside its bound, spread over time scales
    gdn = params["layers"][0]["gdn"]
    start = -5.0 * jax.nn.sigmoid(gdn["dt_bias"])
    lo, hi = gated_delta.DT_SHARE
    assert float(start.min()) >= -5.0 * hi - 1e-6
    assert float(start.max()) <= -5.0 * lo + 1e-6
    axes = logical_axes(cfg)
    is_axes = lambda x: isinstance(x, tuple) and all(  # noqa: E731
        a is None or isinstance(a, str) for a in x
    )
    shapes = jax.tree_util.tree_map(lambda a: a.ndim, params)
    ranks = jax.tree_util.tree_map(len, axes, is_leaf=is_axes)
    assert shapes == ranks


@pytest.mark.parametrize(
    "bad,match",
    [
        ({"gdn_decay": "row"}, "unknown gdn_decay"),
        ({"gdn_gate": "tanh"}, "unknown gdn_gate"),
        ({"attn_kind": "sparse"}, "unknown attn_kind"),
        ({"gdn_decay_bound": -6.0}, "gdn_decay_bound"),
        ({"gdn_decay_bound": 0.0}, "gdn_decay_bound"),
        ({"gdn_key_heads": 2}, "as many key heads"),
        ({"kv_latent_dim": 0}, "latent attention needs"),
        ({"qk_rope_dim": 7}, "latent attention needs"),
        ({"num_kv_heads": 2}, "latent attention needs"),
        ({"attn_gate": "sigmoid"}, "latent attention needs"),
        ({"qk_norm_span": "token"}, "latent attention needs"),
        ({"router_groups": 3}, "router_groups"),
        ({"router_groups_kept": 5}, "router_groups"),
        ({"router_groups": 16, "router_groups_kept": 16}, "router_groups"),
        ({"router_groups_kept": 1, "moe_top_k": 5}, "router_groups"),
        ({"layer_pattern": "G-GE*EGX"}, "kinds are"),
    ],
    ids=lambda v: str(v)[:40],
)
def test_a_configuration_that_cannot_be_is_refused(bad, match):
    with pytest.raises(ValueError, match=match):
        _cfg(**bad)


# -- the counts --------------------------------------------------------------


def test_the_counts_are_sites_chunk_steps_and_score_lanes():
    """Three KDA mixers over 64 tokens in chunks of 16 and one latent
    attention whose 24-wide scores are called 128 wide: a traced train
    step is 3 sites, 3 x 4 steps forward and as many backward, none in
    the kernels, and 24 of 128 score lanes (how the trainer folds what a
    step's build traced: ``test_trace_counts.py``)."""
    before = trace_counts.snapshot()
    cfg = _cfg()
    tx = build_optimizer("adamw", lr=1e-3)
    mesh = build_mesh(MeshConfig(), jax.devices()[:1])
    params = _weights(cfg)
    x, y = _batch(cfg)
    # the worker's reference check: a forward pass before any step
    jax.jit(lambda p: loss_fn(p, x, y, cfg, None)).lower(params)
    assert added(before, GDN) == (3, 12, 0)
    assert added(before, LANES) == (128, 24)
    state = TrainState(
        step=jnp.zeros((), jnp.int32), params=params,
        opt_state=tx.init(params),
    )
    before = trace_counts.snapshot()
    build_train_step(cfg, mesh, tx, donate=False).lower(state, x, y)
    assert added(before, GDN) == (3, 24, 0)
    assert added(before, LANES) == (128, 24)
    # under ``remat`` every layer is traced on its own (a wrapper a layer:
    # ``jax.checkpoint`` would hand layers two and three the trace of the
    # first) and a mixer's forward pass is traced twice; it runs once, the
    # recomputed layer keeping what the pass read and returned, so the
    # primal's trace counts its site and no step: the step's serial depth
    # is 3 x 2 x 4 as without ``remat``, and the latent site still one
    before = trace_counts.snapshot()
    build_train_step(
        replace(cfg, remat=True), mesh, tx, donate=False
    ).lower(state, x, y)
    assert added(before, GDN) == (6, 24, 0)
    assert added(before, GDN_KEPT) == (3,)
    assert added(before, LANES) == (128, 24)
    # an attention that states one width for all three is called with it
    dense = tiny()
    p = init_params(jax.random.PRNGKey(0), dense)
    xs = jnp.zeros((1, 16), jnp.int32)
    before = trace_counts.snapshot()
    jax.jit(lambda p: loss_fn(p, xs, xs, dense, None)).lower(p)
    assert added(before, GDN) == (0, 0, 0)
    assert added(before, LANES) == (16, 16)
def test_one_train_step_moves_every_leaf_and_reports_the_routing():
    cfg = _cfg(experts_held=4, experts_offset=4, router_bias_rate=1e-3)
    tx = build_optimizer("adamw", lr=1e-2)
    mesh = build_mesh(MeshConfig(), jax.devices()[:1])
    params = _weights(cfg)
    x, y = _batch(cfg)
    state = TrainState(
        step=jnp.zeros((), jnp.int32), params=params,
        opt_state=tx.init(params),
    )
    new, metrics = build_train_step(cfg, mesh, tx, donate=False)(state, x, y)
    assert np.isfinite(float(metrics["loss"]))
    assert metrics["moe_expert_load"].shape == (16,)
    assert abs(float(metrics["moe_expert_load"].sum()) - 1.0) < 1e-5
    assert float(metrics["moe_drop_rate"]) == 0.0
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(new.params),
        jax.tree_util.tree_leaves(params),
    ):
        assert np.all(np.isfinite(a)), jax.tree_util.keystr(path)
        assert not np.array_equal(a, b), jax.tree_util.keystr(path)
    # the selection bias moved by the rule's step and nothing else
    for after, before in zip(new.params["layers"], params["layers"]):
        if "moe" in after:
            step = np.abs(np.asarray(after["moe"].bias - before["moe"].bias))
            assert np.all((np.abs(step - 1e-3) < 1e-6) | (step < 1e-9))
