"""A Gated DeltaNet / gated attention / sparse-expert hybrid against its
plain reference (ISSUE 43).

A tiny ``qwen3_next`` (pattern ``GEGE*E``, width 48, 4 query and 2 key/value
heads of 16 with an output gate, a-head q / k norms and rotary positions on
8 of a head's 16 dims, Gated DeltaNet of 4 value and 2 key heads of 12 / 8
in chunks of 16, 8 SwiGLU experts of 24 beside a gated SwiGLU shared one of
40, 3 a token by renormalised softmax, zero-centred norms, 64 tokens a row)
in float32 on the CPU, seeded weights: the program's ``loss_fn`` and every
gradient leaf against ``benchmark/references/qwen3_next.py`` (loaded by
path), the chunked delta rule against the recurrence one step at a time,
each new kind of the attention layer against what it replaces, a chip's
share of the experts adding up to the whole layer, and the counts of a built
step.

The tolerance is 2e-5 relative (2e-4 for a gradient leaf): program and
reference both compute in float32 and differ in the order of their sums
(the delta rule in chunks through a triangle's inverse against one step at
a time, sorted grouped matmuls against every expert on every token, flash
attention's jnp path against a plain softmax).
"""

import functools
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
    _attention_block,
    _qk_norm,
    _rope,
    init_kv_cache,
    init_params,
    logical_axes,
    loss_fn,
)
from dlrover_tpu.ops import gated_delta
from dlrover_tpu.ops import gated_delta_kernels as kernels
from dlrover_tpu.ops.gated_delta import (
    gated_delta_chunked,
    l2norm,
    unit_lower_inverse,
)
from dlrover_tpu.ops.mamba2 import gated_group_rmsnorm
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.moe import init_moe_params, moe_layer_local, route
from dlrover_tpu.trainer.elastic.trainer import build_optimizer
from pass_parity import check_pass, pass_inputs
from trace_counted import GDN, PASS, added

RTOL = 2e-5
GRAD_RTOL = 2e-4  # a gradient sums more terms in another order
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOP_K = 3
REF_KW = dict(top_k=TOP_K, rotary_dims=8, key_heads=2, balance_weight=1e-2)


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmark", "references", "qwen3_next.py")
    spec = importlib.util.spec_from_file_location("qwen3_next_ref", path)
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
        vocab_size=256, num_layers=6, layer_pattern="GEGE*E", model_dim=48,
        num_heads=4, num_kv_heads=2, attn_head_dim=16, mlp_dim=24,
        max_seq_len=64, rope=True, rope_theta=1e7, rope_dim=8, rmsnorm=True,
        norm_eps=1e-6, norm_weight="one_plus", swiglu=True,
        tie_embeddings=False, qk_norm=True, qk_norm_span="head",
        attn_gate="sigmoid", num_experts=8, moe_top_k=TOP_K,
        norm_topk_prob=True, router="softmax", router_balance_weight=1e-2,
        router_z_weight=0.0, shared_expert_dim=40,
        shared_expert_gate="sigmoid", gdn_value_heads=4, gdn_key_heads=2,
        gdn_key_dim=8, gdn_value_dim=12, gdn_chunk=16, dtype="float32",
        param_dtype="float32",
    )
    return replace(cfg, **over)


def _weights(cfg, seed=0):
    """Seeded weights with every norm weight and step bias off its initial
    value, and a token table small enough that the norms' eps counts."""
    params = init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 256))

    def jitter(path, leaf):
        name = getattr(path[-1], "key", None) or getattr(
            path[-1], "name", None
        )
        if name in ("scale", "norm", "dt_bias"):
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


@pytest.mark.parametrize("held", [(0, 0), (2, 4)])
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
    # tables and final norm; 2 DeltaNet layers of 8 + norm; 1 attention
    # of 4 + 2 head norms + norm; 3 expert blocks of gate, 3 routed, 4
    # shared + norm
    assert len(got_leaves) == len(want_leaves) == 3 + 2 * 9 + 7 + 3 * 9
    for (path, a), b in zip(got_leaves, want_leaves):
        name = jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(b))) > 0, name
        assert _rel(a, b) <= GRAD_RTOL, name


@pytest.mark.parametrize(
    "switch",
    [
        {"norm_weight": ""},
        {"qk_norm": False},
        {"attn_gate": ""},
        {"rope_dim": 0},
        {"rope_theta": 1e4},
        {"shared_expert_gate": ""},
        {"shared_expert_dim": 0},
        {"norm_topk_prob": False},
        {"router": "sigmoid"},
        {"router_balance_weight": 0.0},
        {"norm_eps": 1e-5},
        {"gdn_key_heads": 4},
    ],
    ids=lambda s: next(iter(s)),
)
def test_each_switch_is_worth_more_than_ten_tolerances(ref_loss, switch):
    cfg = _cfg()
    params = _weights(cfg)
    x, y = _batch(cfg)
    want = ref_loss
    off = replace(cfg, **switch)
    p = params
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), off))
    if jax.tree_util.tree_map(lambda a: a.shape, shapes) != (
        jax.tree_util.tree_map(lambda a: a.shape, params)
    ):
        # a tree the other kind can run: same draws where both have them
        p = _weights(off)
    got = float(jax.jit(lambda p: loss_fn(p, x, y, off, None))(p))
    assert abs(got - want) > 10 * RTOL * abs(want), (got, want)


def test_remat_gives_the_same_loss_and_gradients():
    cfg = _cfg()
    params = _weights(cfg)
    x, y = _batch(cfg)
    a, ga = jax.jit(
        jax.value_and_grad(lambda p: loss_fn(p, x, y, cfg, None))
    )(params)
    on = replace(cfg, remat=True)
    b, gb = jax.jit(
        jax.value_and_grad(lambda p: loss_fn(p, x, y, on, None))
    )(params)
    assert float(a) == pytest.approx(float(b), rel=1e-6)
    for u, v in zip(
        jax.tree_util.tree_leaves(ga), jax.tree_util.tree_leaves(gb)
    ):
        assert _rel(v, u) <= RTOL


# -- the delta rule -----------------------------------------------------------


def delta_rule_sequential(q, k, v, beta, g):
    """The recurrence ``gated_delta_chunked`` computes, one step at a
    time: S <- exp(g) S; S <- S + k (outer) beta (v - S^T k); o = S^T q."""
    B, T, Hk, dk = q.shape
    Hv, dv = v.shape[2], v.shape[3]
    rep = Hv // Hk

    def step(S, inp):
        q_t, k_t, v_t, beta_t, g_t = inp
        q_t, k_t = jnp.repeat(q_t, rep, 1), jnp.repeat(k_t, rep, 1)
        S = jnp.exp(g_t)[..., None, None] * S
        read = jnp.einsum("bhdv,bhd->bhv", S, k_t)
        S = S + jnp.einsum(
            "bhd,bhv->bhdv", k_t, beta_t[..., None] * (v_t - read)
        )
        return S, jnp.einsum("bhdv,bhd->bhv", S, q_t)

    S0 = jnp.zeros((B, Hv, dk, dv), jnp.float32)
    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, beta, g))
    _, o = jax.lax.scan(step, S0, xs)
    return jnp.moveaxis(o, 0, 1)


# where the decay and the write strength sit: alpha = exp(g) near 1 keeps
# the state over many chunks, near 0 forgets inside one; beta near 1
# replaces what a key reads, near 0 writes almost nothing
REGIMES = {
    "mid": (-1.0, 0.0),
    "alpha_near_1": (-9.0, 0.0),
    "alpha_near_0": (2.5, 0.0),
    "beta_near_1": (-1.0, 6.0),
    "beta_near_0": (-1.0, -6.0),
}


def _rule_inputs(regime="mid", seed=0, B=2, T=48, Hk=2, Hv=4, dk=8, dv=12):
    g_at, b_at = REGIMES[regime]
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = l2norm(jax.random.normal(ks[0], (B, T, Hk, dk))) * dk**-0.5
    k = l2norm(jax.random.normal(ks[1], (B, T, Hk, dk)))
    v = jax.random.normal(ks[2], (B, T, Hv, dv))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, Hv)) + b_at)
    g = -jnp.exp(g_at + 0.5 * jax.random.normal(ks[4], (B, T, Hv)))
    return q, k, v, beta, g


@pytest.mark.parametrize("regime", sorted(REGIMES))
@pytest.mark.parametrize("chunk", [8, 16, 48])
def test_chunked_delta_rule_is_the_recurrence(chunk, regime):
    """T = 48 is six, three or one chunk: the state crosses chunk
    boundaries, forward and in every gradient, in every regime of decay
    and write strength."""
    args = _rule_inputs(regime)
    alpha = np.exp(np.asarray(args[4]))
    if regime == "alpha_near_1":
        assert alpha.min() > 0.999
    if regime == "alpha_near_0":
        assert np.median(alpha) < 1e-4
    def chunked(*a):
        return gated_delta_chunked(*a, chunk)

    want = jax.jit(delta_rule_sequential)(*args)
    got = jax.jit(chunked)(*args)
    assert got.shape == want.shape == (2, 48, 4, 12)
    assert _rel(got, want) <= RTOL

    def grads(fn):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=range(5)
        ))(*args)

    g_want, g_got = grads(delta_rule_sequential), grads(chunked)
    for a, b in zip(g_got, g_want):
        assert _rel(a, b) <= GRAD_RTOL


def test_chunked_delta_rule_refuses_what_it_cannot_chunk():
    q, k, v, beta, g = _rule_inputs(T=40)
    with pytest.raises(ValueError, match="whole chunks"):
        gated_delta_chunked(q, k, v, beta, g, 16)
    with pytest.raises(ValueError, match="do not share"):
        gated_delta_chunked(q, k, v[:, :, :3], beta[..., :3], g[..., :3], 8)


def test_no_decay_and_full_strength_is_the_plain_delta_rule():
    """g = 0, beta = 1 and orthonormal keys: each step stores its value
    under its key, and a query that is a stored key reads its value."""
    T, d = 8, 8
    k = jnp.eye(d)[None, :T, None, :]  # one head, keys e_0 .. e_7
    v = jax.random.normal(jax.random.PRNGKey(0), (1, T, 1, 5))
    ones = jnp.ones((1, T, 1))
    o = gated_delta_chunked(k, k, v, ones, 0.0 * ones, 4)
    assert _rel(o, v) <= RTOL
    # a strong decay forgets everything but the step's own write
    far = gated_delta_chunked(
        jnp.roll(k, 1, axis=1), k, v, ones, -50.0 * ones, 4
    )
    assert float(jnp.max(jnp.abs(far))) <= 1e-12


@pytest.mark.parametrize("C", [1, 2, 5, 16, 64])
def test_unit_lower_inverse_is_the_inverse(C):
    # entries as the layer's: |beta (k_i . k_j) decay| of unit keys
    A = jnp.tril(
        jax.random.normal(jax.random.PRNGKey(C), (3, C, C)) * C**-0.5, -1
    )
    T = unit_lower_inverse(A)
    eye = jnp.eye(C)
    assert np.allclose(T @ (eye - A), eye, atol=2e-5)
    assert np.allclose(jnp.triu(T, 1), 0.0)
    if C == 1:
        return
    plain = jax.grad(lambda a: jnp.sum(jnp.sin(jnp.linalg.inv(eye - a))))(A)
    ours = jax.grad(lambda a: jnp.sum(jnp.sin(unit_lower_inverse(a))))(A)
    assert _rel(jnp.tril(ours, -1), jnp.tril(plain, -1)) <= GRAD_RTOL


# -- the chunk-local work as kernels (ISSUE 44) ----------------------------

# the layer's own decay rates, ``g = -A softplus(.)``: heads that forget
# inside a chunk (A >= 10), heads that remember for thousands of steps
# (A down to 1e-3), and the layer's start (A = U(0, 16])
KERNEL_REGIMES = {
    "fast_decay": (10.0, 16.0),
    "long_memory": (1e-3, 1e-2),
    "as_initialised": (1e-3, 16.0),
}
# float32: the kernels and the statement differ in the order of their sums
# (a cotangent sums more terms: ``RTOL``); bfloat16: two roundings of the
# largest value to 8 bits of mantissa
KERNEL_TOL = {"float32": (1e-5, RTOL), "bfloat16": (2e-2, 2e-2)}
KERNEL_SHAPE = dict(B=1, T=256, Hk=2, Hv=4, d=128, C=64)


def _kernel_inputs(regime, dtype, seed=0, B=1, T=256, Hk=2, Hv=4, d=128,
                   C=64):
    lo, hi = KERNEL_REGIMES[regime]
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = l2norm(jax.random.normal(ks[0], (B, T, Hk, d))) * d**-0.5
    k = l2norm(jax.random.normal(ks[1], (B, T, Hk, d)))
    v = jax.random.normal(ks[2], (B, T, Hv, d))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, Hv)))
    A = jnp.exp(jax.random.uniform(
        ks[4], (Hv,), minval=np.log(lo), maxval=np.log(hi)
    ))
    g = -A * jax.nn.softplus(jax.random.normal(ks[5], (B, T, Hv)) + 1.0)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), beta, g


def _both_ways(B, T, Hk, Hv, d, C):
    """``(wy, read_out)`` as the plain statement and as the kernels, both
    from token-major arguments to the same results."""
    nc, r = T // C, Hv // Hk
    rows = (nc, B, Hk, 1, r * C)

    def per_head(x):
        return jnp.transpose(x.reshape(B, nc, C, Hk, r), (1, 0, 3, 4, 2))

    def chunks(x):
        return gated_delta._chunks(x, nc, C)

    def plain_wy(k, v, beta, g):
        kc = chunks(k)
        vc = chunks(v).reshape(nc, B, Hk, r, C, d)
        return (*gated_delta._wy(kc, vc, per_head(beta), per_head(g)), kc)

    def kernel_wy(k, v, beta, g):
        U, W, kc, delta, a = kernels.wy(
            k.reshape(B, T, Hk * d), v.reshape(B, T, Hv * d),
            per_head(beta).reshape(rows), per_head(g).reshape(rows),
            Hk, r, C,
        )
        return U, W, delta, a, kc

    def plain_read(q, k, g, Vn, S_in):
        o = gated_delta._read_out(
            chunks(q), chunks(k), per_head(g), Vn, S_in
        )
        o = jnp.transpose(o, (1, 0, 4, 2, 3, 5)).reshape(B, T, Hv, d)
        return o.astype(k.dtype)

    def kernel_read(q, k, g, Vn, S_in):
        return kernels.read_out(
            q.reshape(B, T, Hk * d), k.reshape(B, T, Hk * d),
            per_head(g).reshape(rows), Vn, S_in,
        ).reshape(B, T, Hv, d)

    return (plain_wy, kernel_wy), (plain_read, kernel_read)


def _within(got, want, tol):
    """``_rel`` for results that may be all zeros (a fast head's ``a``)."""
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return np.max(np.abs(got - want)) <= tol * np.max(np.abs(want))


def _cotangents(outs, seed):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(outs))
    return tuple(
        jax.random.normal(key, o.shape).astype(o.dtype)
        for key, o in zip(keys, outs)
    )


@pytest.mark.parametrize("regime", sorted(KERNEL_REGIMES))
@pytest.mark.parametrize("dtype", sorted(KERNEL_TOL))
@pytest.mark.parametrize("stretch", ["wy", "read_out"])
def test_chunk_kernels_are_the_plain_statement(stretch, dtype, regime):
    """Heads of 128, chunks of 64, two key heads of two value heads, four
    chunks, under ``interpret=True``: what the kernels write (``U, W,
    delta, a`` and the chunk-major ``K``; ``o``) and every cotangent they
    return against ``_wy`` / ``_read_out`` and ``jax.vjp`` of those."""
    tol, grad_tol = KERNEL_TOL[dtype]
    q, k, v, beta, g = _kernel_inputs(regime, dtype)
    A_eff = -np.asarray(g).mean((0, 1))
    if regime == "fast_decay":
        assert A_eff.min() >= 10.0
    if regime == "long_memory":
        assert A_eff.max() <= 2e-2
    wy, read = _both_ways(**KERNEL_SHAPE)
    if stretch == "wy":
        (plain, kernel), args = wy, (k, v, beta, g)
    else:
        U, W, delta, a, kc = wy[0](k, v, beta, g)
        Vn, S_in = gated_delta.chunk_state_pass(U, W, kc, delta, a)
        (plain, kernel), args = read, (q, k, g, Vn, S_in)
    want, vjp_want = jax.vjp(plain, *args)
    got, vjp_got = jax.vjp(kernel, *args)
    one = not isinstance(want, tuple)
    for a, b in zip(*(((x,) if one else x) for x in (got, want))):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert _within(a, b, tol)
    cts = _cotangents((want,) if one else want, seed=7)
    cts = cts[0] if one else cts
    for a, b in zip(vjp_got(cts), vjp_want(cts)):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert _within(a, b, grad_tol)


@pytest.mark.parametrize("n,blocks", [(6, 3), (5, 5)])
@pytest.mark.parametrize("dtype", sorted(KERNEL_TOL))
def test_the_pass_kernels_are_the_plain_scan(dtype, n, blocks):
    """The serial pass over the chunk states with the state in VMEM (ISSUE
    65), ``interpret``: two key heads of two value heads of 128 / 128, the
    state carried across 3 and 5 runs of chunks, forward and reversed,
    against ``_pass_scan`` / ``_pass_scan_bwd``."""
    check_pass(n, 1, 2, 2, 16, 128, 128, False, dtype, blocks)


def test_a_pass_the_kernels_cannot_take_is_the_scan():
    """At a shape ``fits`` refuses (a head of 72) ``chunk_state_pass`` is
    the ``lax.scan`` it was, forward and reversed, and counts no kernel."""
    args = pass_inputs(4, 1, 2, 2, 16, 72, 72, False, jnp.float32)
    before = trace_counts.snapshot()

    def loss(*a):
        Vn, S_in = gated_delta.chunk_state_pass(*a)
        return jnp.sum(Vn) + jnp.sum(S_in)

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=range(5)))(*args))
    assert text.count("scan[") == 2 and "pallas_call" not in text
    assert added(before, GDN + PASS) == (1, 8, 0, 0)
    kernel = pass_inputs(4, 1, 2, 2, 16, 128, 128, False, jnp.float32)
    text = str(jax.make_jaxpr(jax.grad(loss, argnums=range(5)))(*kernel))
    assert text.count("pallas_call") == 2
    assert added(before, GDN + PASS) == (2, 16, 0, 1)


@pytest.mark.parametrize("regime", sorted(KERNEL_REGIMES))
def test_chunked_rule_through_the_kernels_is_the_recurrence(regime):
    """``gated_delta_chunked`` at shapes the kernels take, forward and in
    every gradient, against one step at a time; the counts say which way
    the site went."""
    before = trace_counts.snapshot()
    args = _kernel_inputs(regime, jnp.float32)

    def chunked(*a):
        return gated_delta_chunked(*a, 64)

    want = jax.jit(delta_rule_sequential)(*args)
    got = jax.jit(chunked)(*args)
    assert added(before, GDN) == (1, 4, 1)
    assert got.shape == want.shape == (1, 256, 4, 128)
    assert _rel(got, want) <= RTOL

    def grads(fn):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=range(5)
        ))(*args)

    for a, b in zip(grads(chunked), grads(delta_rule_sequential)):
        assert _rel(a, b) <= GRAD_RTOL
    assert added(before, GDN) == (2, 12, 2)


@pytest.mark.parametrize("d_k,d_v,chunk,T,dtype,kernel", [
    (128, 128, 64, 8192, "bfloat16", True),  # the cell
    (128, 256, 16, 64, "bfloat16", True),
    (128, 128, 8, 64, "float32", True),
    (128, 128, 8, 64, "bfloat16", False),  # half a bfloat16 sublane tile
    (64, 128, 64, 8192, "bfloat16", True),  # half a tile: head-major
    (128, 192, 64, 8192, "bfloat16", True),
    (72, 128, 64, 8192, "bfloat16", False),  # no whole quarter tiles
    (128, 200, 64, 8192, "bfloat16", False),
    (128, 128, 64, 8192 + 32, "bfloat16", False),  # a ragged sequence
    (128, 128, 40, 40, "float32", True),  # one short chunk, whole tiles
    (128, 128, 20, 20, "float32", False),
])
def test_the_shapes_decide_which_way_a_chunk_is_computed(
    d_k, d_v, chunk, T, dtype, kernel
):
    assert kernels.fits(d_k, d_v, chunk, T, dtype) is kernel


@pytest.mark.parametrize("d,T", [(72, 128), (128, 20)])
def test_a_site_the_kernels_cannot_take_is_plain_and_says_so(d, T):
    """A head of 72, or a sequence that is one chunk of no whole tiles:
    the plain statement runs, to the recurrence's result, and the counts
    are a site and none in the kernel."""
    before = trace_counts.snapshot()
    args = _kernel_inputs("as_initialised", jnp.float32, T=T, d=d)
    got = jax.jit(lambda *a: gated_delta_chunked(*a, min(64, T)))(*args)
    assert added(before, GDN) == (1, max(T // 64, 1), 0)
    assert _rel(got, jax.jit(delta_rule_sequential)(*args)) <= RTOL


@pytest.mark.parametrize("shape", ["fsdp2_tp2", "fsdp4", "tp2"])
def test_a_mesh_of_several_devices_runs_the_kernels_under_shard_map(shape):
    """A mixer whose heads fit the kernels, on a mesh GSPMD owns: the rule
    runs under ``shard_map`` (batch over the data axes, heads over tp), to
    the one-device layer's output and gradients."""
    from dlrover_tpu.ops.gated_delta import (
        gated_delta_mixer,
        init_gated_delta_params,
    )

    over = {"fsdp2_tp2": dict(fsdp=2, tp=2), "fsdp4": dict(fsdp=4),
            "tp2": dict(tp=2)}[shape]
    mesh = build_mesh(
        MeshConfig(**over), jax.devices()[:int(np.prod(list(over.values())))]
    )
    cfg = _cfg(gdn_key_dim=128, gdn_value_dim=128, gdn_chunk=16)
    p = init_gated_delta_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(1), (4, 32, cfg.model_dim))

    def layer(mesh):
        def loss(u, p):
            return jnp.sum(jnp.sin(gated_delta_mixer(u, p, cfg, 1e-6, mesh)))
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))

    text = layer(mesh).lower(u, p).as_text()
    assert "sdy.manual_computation" in text
    want, g_want = layer(None)(u, p)
    got, g_got = layer(mesh)(u, p)
    assert abs(float(got) - float(want)) <= RTOL * abs(float(want))
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        assert _rel(a, b) <= GRAD_RTOL


def test_the_gate_stands_outside_the_norm():
    """``gated_group_rmsnorm`` with ``norm_before_gate``: every head has
    mean square 1 before weight and gate; without, after the gate."""
    y = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 6))
    z = jax.random.normal(jax.random.PRNGKey(1), (1, 12, 6))
    w = jnp.arange(1.0, 7.0)
    got = gated_group_rmsnorm(y, z, w, 2, 0.0, norm_before_gate=True)
    yg = y.reshape(1, 12, 2, 3)
    yg = yg / jnp.sqrt(jnp.mean(yg * yg, -1, keepdims=True))
    assert _rel(got, yg.reshape(1, 12, 6) * w * jax.nn.silu(z)) <= RTOL
    inside = gated_group_rmsnorm(y, z, w, 2, 0.0)
    assert _rel(got, inside) > 0.1


# -- the attention layer's kinds ----------------------------------------------


def test_rotary_turns_the_leading_dims_and_leaves_the_rest_bit_equal():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 256))
    x = x.astype(jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(5), (2, 5))
    got = _rope(x, pos, 1e7, dims=64)
    assert got.dtype == x.dtype
    assert np.array_equal(np.asarray(got[..., 64:]), np.asarray(x[..., 64:]))
    # position 0 is not turned at all, every later one is
    assert np.array_equal(np.asarray(got[:, 0]), np.asarray(x[:, 0]))
    assert not np.allclose(got[:, 1:, :, :64], x[:, 1:, :, :64])
    # the turned dims are the whole-head rotation of a head of 64
    want = _rope(x[..., :64], pos, 1e7)
    assert np.array_equal(np.asarray(got[..., :64]), np.asarray(want))
    # pairs (i, i + 32): a turn keeps each pair's length
    f = got.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    assert np.allclose(
        f[..., :32] ** 2 + f[..., 32:64] ** 2,
        xf[..., :32] ** 2 + xf[..., 32:64] ** 2, rtol=0.05, atol=1e-2,
    )
    # and in the kernel's layout too
    bh = _rope(jnp.swapaxes(x, 1, 2), pos, 1e7, layout="bhtd", dims=64)
    assert np.array_equal(np.asarray(jnp.swapaxes(bh, 1, 2)), np.asarray(got))


def test_a_head_norm_is_not_the_all_heads_norm():
    """OLMoE's QK-norm takes one mean square over a token's whole
    projection; this family's takes one a head. Each configuration gets
    its own, with its own tree."""
    head = _cfg(norm_weight="")
    token = replace(head, qk_norm_span="token")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 4, 16))
    x = x * jnp.array([0.1, 1.0, 3.0, 10.0])[:, None]  # heads differ
    w = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (16,))
    a = _qk_norm(x, {"scale": w}, head)
    b = _qk_norm(x, {"scale": jnp.tile(w, (4, 1))}, token)
    assert np.allclose(jnp.mean(jnp.square(a / w), -1), 1.0, atol=1e-3)
    assert _rel(a, b) > 0.5
    ms = jnp.mean(jnp.square(b / w), axis=(2, 3))
    assert np.allclose(ms, 1.0, atol=1e-3)
    # the kernel's layout gives the same
    a2 = _qk_norm(jnp.swapaxes(x, 1, 2), {"scale": w}, head, "bhtd")
    assert np.allclose(jnp.swapaxes(a2, 1, 2), a, atol=1e-6)
    # zero-centred: the weight is the scale less one
    c = _qk_norm(x, {"scale": w - 1.0}, _cfg())
    assert np.allclose(c, a, atol=1e-6)
    trees = [
        init_params(jax.random.PRNGKey(0), c)["layers"][4]
        for c in (head, token)
    ]
    assert trees[0]["q_norm"]["scale"].shape == (16,)
    assert trees[0]["k_norm"]["scale"].shape == (16,)
    assert trees[1]["q_norm"]["scale"].shape == (4, 16)
    assert trees[1]["k_norm"]["scale"].shape == (2, 16)
    # OLMoE's own block keeps its tree
    olmoe = init_params(
        jax.random.PRNGKey(0), tiny(qk_norm=True)
    )["layers"][0]
    assert olmoe["q_norm"]["scale"].shape == (4, 8)
    assert np.all(np.asarray(olmoe["q_norm"]["scale"]) == 1.0)


def test_an_open_output_gate_is_the_ungated_layer():
    gated = _cfg(layer_pattern="*", num_layers=1)
    plain = replace(gated, attn_gate="")
    layer = _weights(gated)["layers"][0]
    wq = layer["attn"]["wq"]
    assert wq.shape == (48, 4, 32)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 48))
    pos = jnp.broadcast_to(jnp.arange(64), (2, 64))
    ungated = dict(layer, attn=dict(layer["attn"], wq=wq[..., :16]))
    want = _attention_block(x, ungated, plain, None, pos, "norm")
    got = _attention_block(x, layer, gated, None, pos, "norm")
    assert _rel(got - x, want - x) > 0.1  # a gate near 1/2 halves it
    # gate -> +inf: sigmoid is 1. The gate's columns read a constant
    # feature that the norm leaves large
    wide = wq.at[..., 16:].set(0.0).at[0, :, 16:].set(1e4)
    x1 = x.at[..., 0].set(5.0)
    open_ = dict(layer, attn=dict(layer["attn"], wq=wide))
    ungated = dict(layer, attn=dict(layer["attn"], wq=wide[..., :16]))
    got = _attention_block(x1, open_, gated, None, pos, "norm")
    want = _attention_block(x1, ungated, plain, None, pos, "norm")
    assert _rel(got - x1, want - x1) <= RTOL


def test_cached_decoding_refuses_the_kinds_it_does_not_know():
    for over in ({"attn_gate": "sigmoid"}, {"rope_dim": 4},
                 {"qk_norm": True, "qk_norm_span": "head"}):
        with pytest.raises(NotImplementedError, match="attention \\+ FFN"):
            init_kv_cache(tiny(**over), 1, 8)
    assert init_kv_cache(tiny(qk_norm=True), 1, 8)["k"].shape[2] == 8


# -- the experts --------------------------------------------------------------


def test_softmax_ten_of_512_renormalised():
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(0), (64, 512))
    idx, gates, aux = route(logits, 10, True)
    p = np.asarray(jax.nn.softmax(logits, axis=-1))
    order = np.argsort(-p, axis=1)[:, :10]
    assert np.array_equal(np.sort(idx, 1), np.sort(order, 1))
    chosen = np.take_along_axis(p, np.asarray(idx), 1)
    assert np.allclose(gates, chosen / chosen.sum(1, keepdims=True), rtol=1e-5)
    assert np.allclose(np.sum(gates, 1), 1.0, rtol=1e-5)
    assert float(np.sum(aux["load"])) == pytest.approx(1.0)
    assert int(np.sum(aux["counts"])) == 640
    # as they are where the source says so (OLMoE)
    _, raw, _ = route(logits, 10, False)
    assert np.allclose(raw, chosen, rtol=1e-6)


E, HELD = 32, 2


def _expert_block(held=0, seed=0):
    return init_moe_params(
        jax.random.PRNGKey(seed), E, 32, 24, gated=True, held=held,
        shared_dim=40, shared_out_gate=True,
    )


@functools.partial(jax.jit, static_argnames="held")
def _run(params, x, held=None):
    return moe_layer_local(
        params, x, axis_name=None, top_k=4, normalize=True, held=held
    )


def test_the_shares_add_up_to_the_whole_block(ref):
    """Over all 16 offsets, the held experts' parts plus the gated shared
    expert counted once are the uncut block, the program's and the
    reference's."""
    whole = _expert_block()
    assert whole.shared_gate.shape == (32, 40)
    assert whole.shared_out_gate.shape == (32,)
    x = jax.random.normal(jax.random.PRNGKey(5), (96, 32))
    want, aux = _run(whole, x)
    plain = jax.jit(ref._experts, static_argnums=(2, 3))
    assert _rel(want, plain(x, whole, 4, 0)[0]) <= RTOL
    shared_only = dict(
        shared_up=None, shared_down=None, shared_gate=None,
        shared_out_gate=None,
    )
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
            plain(x, whole._replace(**cut), 4, offset)[0],
        ) <= RTOL
        total = total + part
    h = jax.nn.silu(x @ whole.shared_gate) * (x @ whole.shared_up)
    shared = jax.nn.sigmoid(x @ whole.shared_out_gate)[:, None] * (
        h @ whole.shared_down
    )
    assert _rel(total + shared, want) <= RTOL
    # the gate is worth having: without it the block is another
    ungated, _ = _run(whole._replace(shared_out_gate=None), x)
    assert _rel(ungated, want) > 0.05


def test_share_gradients_match_the_reference(ref):
    share = _expert_block(held=HELD)
    x = jax.random.normal(jax.random.PRNGKey(7), (64, 32))

    def probe(fn):
        return lambda p, x: jnp.sum(jnp.sin(fn(p, x)))

    got = jax.grad(probe(lambda p, x: _run(p, x, held=(4, HELD))[0]), (0, 1))(
        share, x
    )
    want = jax.grad(
        probe(lambda p, x: ref._experts(x, p, 4, 4)[0]), (0, 1)
    )(share, x)
    for a, b in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    ):
        assert np.any(np.asarray(b))
        assert _rel(a, b) <= GRAD_RTOL


def test_an_ungated_pair_keeps_its_shared_expert_as_it_was():
    """Nemotron's shared expert: no gate projection, no output gate, and
    the same draws as before the new leaves came."""
    old = init_moe_params(jax.random.PRNGKey(0), 8, 32, 24, shared_dim=40)
    assert old.shared_gate is None and old.shared_out_gate is None
    new = init_moe_params(
        jax.random.PRNGKey(0), 8, 32, 24, gated=True, shared_dim=40,
        shared_out_gate=True,
    )
    assert np.array_equal(old.shared_up, new.shared_up)
    assert np.array_equal(old.shared_down, new.shared_down)
    assert np.array_equal(old.gate, new.gate)


# -- the configuration --------------------------------------------------------


def test_the_tree_has_one_mixer_a_layer_and_axes_to_match():
    cfg = _cfg(experts_held=2, experts_offset=6)
    params = init_params(jax.random.PRNGKey(0), cfg)
    kinds = [
        sorted(set(layer) - {"norm", "q_norm", "k_norm"})
        for layer in params["layers"]
    ]
    assert kinds == [["gdn"], ["moe"], ["gdn"], ["moe"], ["attn"], ["moe"]]
    assert "positions" not in params["embed"]
    # zero-centred norms start at 0, the DeltaNet's own at 1
    assert not np.any(np.asarray(params["final_norm"]["scale"]))
    for layer in params["layers"]:
        assert not np.any(np.asarray(layer["norm"]["scale"]))
    assert not np.any(np.asarray(params["layers"][4]["q_norm"]["scale"]))
    gdn = params["layers"][0]["gdn"]
    assert np.all(np.asarray(gdn["norm"]) == 1.0)
    assert gdn["norm"].shape == (12,)
    assert gdn["w_qkv"].shape == (48, 2 * 2 * 8 + 4 * 12)
    assert gdn["w_z"].shape == (48, 48) and gdn["w_ba"].shape == (48, 8)
    assert gdn["conv_w"].shape == (4, 80) and "conv_b" not in gdn
    assert gdn["w_out"].shape == (48, 48)
    assert np.all(np.asarray(gdn["dt_bias"]) == 1.0)
    a = np.exp(np.asarray(gdn["A_log"]))
    assert np.all(a > 0) and np.all(a <= 16)
    moe = params["layers"][1]["moe"]
    assert moe.gate.shape == (48, 8) and moe.bias is None
    assert moe.w_up.shape == (2, 48, 24) and moe.w_gate.shape == (2, 48, 24)
    assert moe.shared_gate.shape == (48, 40)
    assert moe.shared_out_gate.shape == (48,)
    assert params["layers"][4]["attn"]["wq"].shape == (48, 4, 32)

    def is_axes(x):
        return isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x
        )

    axes = logical_axes(cfg)
    assert jax.tree_util.tree_structure(
        params
    ) == jax.tree_util.tree_structure(axes, is_leaf=is_axes)
    for leaf, names in zip(
        jax.tree_util.tree_leaves(params),
        jax.tree_util.tree_leaves(axes, is_leaf=is_axes),
    ):
        assert leaf.ndim == len(names)


@pytest.mark.parametrize(
    "bad, match",
    [
        ({"norm_weight": "centred"}, "unknown norm_weight"),
        ({"qk_norm_span": "group"}, "unknown qk_norm_span"),
        ({"attn_gate": "tanh"}, "unknown attn_gate"),
        ({"shared_expert_gate": "softmax"}, "unknown shared_expert_gate"),
        ({"rope_dim": 7}, "even share of a head"),
        ({"rope_dim": 32}, "even share of a head"),
        ({"gdn_key_heads": 3}, "multiple of gdn_key_heads"),
        ({"gdn_value_dim": 0}, "both head widths"),
        ({"rmsnorm": False}, "rmsnorm is off"),
        ({"layer_pattern": "GEGEME"}, "Gated DeltaNet layers need|ssm"),
    ],
    ids=lambda v: next(iter(v)) if isinstance(v, dict) else "",
)
def test_a_configuration_that_cannot_be_is_refused(bad, match):
    if bad.get("layer_pattern") == "GEGEME":
        # a Mamba-2 layer beside DeltaNet layers is a legal pattern; it
        # is the missing Mamba-2 sizes that cannot run
        cfg = _cfg(**bad)
        with pytest.raises(Exception):
            init_params(jax.random.PRNGKey(0), cfg)
        return
    with pytest.raises(ValueError, match=match):
        _cfg(**bad)


# -- the counts ---------------------------------------------------------------


def test_the_counts_are_sites_and_chunk_steps_of_a_built_step():
    """Two DeltaNet mixers over 64 tokens in chunks of 16: a traced train
    step is 2 sites and 2 x 4 steps forward and as many backward; a
    forward alone is half the steps (how the trainer folds what a step's
    build traced: ``test_trace_counts.py``)."""
    before = trace_counts.snapshot()
    cfg = _cfg()
    tx = build_optimizer("adamw", lr=1e-3)
    mesh = build_mesh(MeshConfig(), jax.devices()[:1])
    params = _weights(cfg)
    x, y = _batch(cfg)
    # the worker's reference check: a forward pass before any step
    jax.jit(lambda p: loss_fn(p, x, y, cfg, None)).lower(params)
    assert added(before, GDN) == (2, 8, 0)
    state = TrainState(
        step=jnp.zeros((), jnp.int32), params=params,
        opt_state=tx.init(params),
    )
    build_train_step(cfg, mesh, tx, donate=False).lower(state, x, y)
    # heads of 12 / 8 are no lane tiles: both sites took the plain way
    assert added(before, GDN) == (4, 24, 0)
    # a step whose mixers have heads of whole lane tiles is built from the
    # kernels
    wide = _cfg(
        num_layers=2, layer_pattern="GE", gdn_key_dim=128,
        gdn_value_dim=128, gdn_chunk=16,
    )
    wide_params = _weights(wide)
    before = trace_counts.snapshot()
    build_train_step(wide, mesh, tx, donate=False).lower(
        TrainState(
            step=jnp.zeros((), jnp.int32), params=wide_params,
            opt_state=tx.init(wide_params),
        ), x, y,
    )
    assert added(before, GDN) == (1, 8, 1)
    # a model without the kind never moves it
    dense = tiny()
    before = trace_counts.snapshot()
    p = init_params(jax.random.PRNGKey(0), dense)
    xs = jnp.zeros((1, 16), jnp.int32)
    jax.jit(lambda p: loss_fn(p, xs, xs, dense, None)).lower(p)
    assert added(before, GDN) == (0, 0, 0)


def test_one_train_step_moves_every_leaf_and_reports_the_routing():
    cfg = _cfg(experts_held=4, experts_offset=4)
    tx = build_optimizer("adamw", lr=1e-2, weight_decay=0.1)
    mesh = build_mesh(MeshConfig(), jax.devices()[:1])
    params = _weights(cfg)
    state = TrainState(
        step=jnp.zeros((), jnp.int32), params=params,
        opt_state=tx.init(params),
    )
    x, y = _batch(cfg)
    new, metrics = build_train_step(cfg, mesh, tx, donate=False)(state, x, y)
    loss = loss_fn(params, x, y, cfg, None)
    assert float(metrics["loss"]) == pytest.approx(float(loss), rel=1e-5)
    assert float(metrics["moe_drop_rate"]) == 0.0
    assert metrics["moe_expert_load"].shape == (8,)
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(new.params),
        jax.tree_util.tree_leaves(params),
    ):
        assert not np.array_equal(a, b), jax.tree_util.keystr(path)
