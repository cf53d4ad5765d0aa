"""Latent attention whose query passes a latent of its own, a YaRN-scaled
rotary table on interleaved pairs and a query scaled by its position, each
layer before a share of softmax-routed experts, against its plain
reference (ISSUE 59).

A tiny ``mistral4`` (pattern ``*E*E``, width 64; latent attention of 4
heads, a query latent of 32 and a key/value latent of 16, scores 8 + 8 wide
and values 16 wide, no head norms; the rotary table made for 16 positions
and stretched 8 times, so that over 64 positions the ramp, the slowed
pairs and a position scale above 1 are all live; 8 SwiGLU experts of 32, 2
a token by softmax, renormalised, 4 held, beside an ungated SwiGLU shared
one of 32; plain norms, 64 tokens a row) in float32 on the CPU, seeded
weights: the program's ``loss_fn`` and every gradient leaf against
``benchmark/references/mistral4.py`` (loaded by path), each of the four
fields alone, each field's off value as the code before it, the table by
hand at the published constants, the pair layouts under the column
permutation between them, a chip's share of the experts adding up to the
whole layer, recomputation, a control in lower precision, the counts of a
built step, the refusals and the analytic profile.

The tolerance is 1e-5 relative (2e-4 for a gradient leaf).
"""

import importlib.util
import math
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.accel.profiler import profile_model
from dlrover_tpu.common import trace_counts
from dlrover_tpu.models.config import TransformerConfig, tiny
from dlrover_tpu.models.train import TrainState, build_train_step
from dlrover_tpu.models.transformer import (
    _pos_scale,
    _rope,
    _rope_table,
    forward,
    forward_step,
    init_kv_cache,
    init_params,
    logical_axes,
    loss_fn,
    yarn_frequencies,
    yarn_softmax_mscale,
)
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.moe import init_moe_params, moe_layer_local
from dlrover_tpu.trainer.elastic.trainer import build_optimizer
from trace_counted import LANES, SCALED, added

RTOL = 1e-5
GRAD_RTOL = 2e-4  # a gradient sums more terms in another order
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T = 64
# what the tiny model changes of the reference's defaults (the published
# constants): 2 experts a token, the table made for 16 positions and
# stretched 8 times
REF_KW = dict(top_k=2, rope_factor=8.0, rope_original_len=16)
# the four fields, each with its off value
FIELDS = {
    "q_latent_dim": dict(q_latent_dim=0),
    "rope_scaling": dict(
        rope_scaling="", rope_factor=1.0, rope_mscale_all_dim=0.0
    ),
    "rope_pairs": dict(rope_pairs=""),
    "attn_pos_scale_beta": dict(attn_pos_scale_beta=0.0),
}
# ... and what the reference is told where a field is off
REF_OFF = {
    "q_latent_dim": {},  # read off the tree: ``wq`` in place of the three
    "rope_scaling": dict(rope_factor=1.0, mscale_all_dim=0.0),
    "rope_pairs": {},  # its pairs are (2j, 2j + 1): the weights are permuted
    "attn_pos_scale_beta": dict(pos_scale_beta=0.0),
}


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmark", "references", "mistral4.py")
    spec = importlib.util.spec_from_file_location("mistral4_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg(**over):
    cfg = TransformerConfig(
        vocab_size=256, num_layers=4, layer_pattern="*E*E", model_dim=64,
        num_heads=4, mlp_dim=32, max_seq_len=T, rope=True, rope_theta=1e4,
        rope_scaling="yarn", rope_factor=8.0, rope_original_len=16,
        rope_beta_fast=32.0, rope_beta_slow=1.0, rope_mscale_all_dim=1.0,
        rope_pairs="interleaved", attn_pos_scale_beta=0.1, rmsnorm=True,
        norm_eps=1e-6, swiglu=True, tie_embeddings=False, attn_kind="latent",
        q_latent_dim=32, kv_latent_dim=16, qk_nope_dim=8, qk_rope_dim=8,
        v_head_dim=16, num_experts=8, experts_held=4, experts_offset=0,
        moe_top_k=2, norm_topk_prob=True, router="softmax", routed_scale=1.0,
        router_balance_weight=0.02, router_z_weight=0.0, shared_expert_dim=32,
        dtype="float32", param_dtype="float32",
    )
    return replace(cfg, **over)


def _weights(cfg, seed=1):
    """Seeded weights with every norm weight off its initial value, and a
    token table small enough that the norms' eps counts."""
    params = init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def jitter(path, leaf):
        if getattr(path[-1], "key", None) == "scale":
            return leaf + 0.1 * jax.random.normal(next(keys), leaf.shape)
        return leaf

    params = jax.tree_util.tree_map_with_path(jitter, params)
    params["embed"]["tokens"] = 0.1 * params["embed"]["tokens"]
    return params


def _batch(cfg, seed=0, rows=2):
    rng = np.random.default_rng(seed)
    data = rng.integers(0, cfg.vocab_size, (rows, T + 1)).astype(np.int32)
    return data[:, :-1], data[:, 1:]


def _rel(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _rope_columns(params, cfg, order):
    """``params`` with the rope columns of every ``w_qb`` (or ``wq``) and
    ``w_kva`` alike taken in ``order``."""
    nope, latent = cfg.qk_nope_dim, cfg.kv_latent_dim

    def layer(old):
        if "attn" not in old:
            return old
        a = dict(old["attn"])
        name = "w_qb" if "w_qb" in a else "wq"
        a[name] = jnp.concatenate(
            [a[name][..., :nope], a[name][..., nope:][..., order]], -1
        )
        a["w_kva"] = jnp.concatenate(
            [a["w_kva"][:, :latent], a["w_kva"][:, latent:][:, order]], -1
        )
        return dict(old, attn=a)

    return dict(params, layers=[layer(old) for old in params["layers"]])


def _firsts_then_seconds(cfg):
    rope = cfg.qk_rope_dim
    return np.concatenate([np.arange(0, rope, 2), np.arange(1, rope, 2)])


def _pairs_to_halves(params, cfg):
    """The checkpoint loader's one duty under ``rope_pairs``: the tree in
    which rope column ``j`` of ``w_qb`` (or ``wq``) and of ``w_kva`` holds
    what column ``2j`` held, and column ``rope / 2 + j`` what ``2j + 1``
    held. Rotate-half over it is the interleaved rotation over the
    original."""
    return _rope_columns(params, cfg, _firsts_then_seconds(cfg))


def _halves_to_pairs(params, cfg):
    """The inverse: the tree the reference's explicit pairs read as the
    program's rotate-half reads ``params``."""
    return _rope_columns(params, cfg, np.argsort(_firsts_then_seconds(cfg)))


def _both(ref, cfg, params, x, y, ref_params=None, **ref_kw):
    got = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, x, y, cfg, None)
    ))(params)
    kw = dict(REF_KW, experts_offset=cfg.experts_offset, **ref_kw)
    want = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, x, y, **kw)
    ))(params if ref_params is None else ref_params)
    return got, want


# -- the whole model against the reference --------------------------------


@pytest.mark.parametrize("held", [(4, 0), (4, 4), (0, 0)])
def test_loss_and_every_gradient_leaf_match_the_reference(ref, held):
    count, offset = held
    cfg = _cfg(experts_held=count, experts_offset=offset)
    params = _weights(cfg)
    x, y = _batch(cfg)
    (got, g_got), (want, g_want) = _both(ref, cfg, params, x, y)
    assert abs(float(got) - float(want)) <= RTOL * abs(float(want))
    got_leaves = jax.tree_util.tree_leaves_with_path(g_got)
    want_leaves = jax.tree_util.tree_leaves(g_want)
    # tables and final norm; 2 latent attentions of 5 matrices, 2 latent
    # norms and the layer's; 2 expert blocks of gate, 3 routed, 3 shared
    # and the layer's norm
    assert len(got_leaves) == len(want_leaves) == 3 + 2 * 8 + 2 * 8
    for (path, a), b in zip(got_leaves, want_leaves):
        name = jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(b))) > 0, name
        assert _rel(a, b) <= GRAD_RTOL, name
    names = [jax.tree_util.keystr(p) for p, _ in got_leaves]
    for wanted in ("['w_qa']", "['q_latent_norm']['scale']", "['w_qb']",
                   "['w_kva']", "['w_kvb']", ".gate"):
        assert any(n.endswith(wanted) for n in names), wanted


@pytest.mark.parametrize("alone", sorted(FIELDS))
def test_each_field_alone_is_the_reference_with_the_others_off(ref, alone):
    off, ref_kw = {}, {}
    for name in FIELDS:
        if name != alone:
            off.update(FIELDS[name])
            ref_kw.update(REF_OFF[name])
    cfg = _cfg(**off)
    params = _weights(cfg)
    x, y = _batch(cfg)
    # the reference turns explicit pairs: where the program's are halves,
    # it reads the tree whose rope columns are laid out as pairs
    ref_params = None if cfg.rope_pairs else _halves_to_pairs(params, cfg)
    (got, g_got), (want, g_want) = _both(
        ref, cfg, params, x, y, ref_params=ref_params, **ref_kw
    )
    assert abs(float(got) - float(want)) <= RTOL * abs(float(want))
    if ref_params is not None:
        g_want = _pairs_to_halves(g_want, cfg)
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(g_got),
        jax.tree_util.tree_leaves(g_want),
    ):
        assert _rel(a, b) <= GRAD_RTOL, jax.tree_util.keystr(path)
    # and the field is worth more than ten tolerances: off, with the same
    # weights where the tree allows, the loss is another
    if alone != "q_latent_dim":
        none = replace(cfg, **FIELDS[alone])
        other = float(jax.jit(lambda p: loss_fn(p, x, y, none, None))(params))
        assert abs(other - float(got)) > 10 * RTOL * abs(float(got))


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
        assert np.any(np.asarray(u))
        assert _rel(v, u) <= GRAD_RTOL


def test_a_reference_in_bfloat16_is_refused_by_the_limits(ref):
    """The control of the tolerance: the reference itself with every
    matmul operand rounded to bfloat16 is not the reference, by the loss's
    limit or by a gradient leaf's."""
    cfg = _cfg()
    params = _weights(cfg)
    x, y = _batch(cfg)
    kw = dict(REF_KW, experts_offset=0)
    want, g_want = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, x, y, **kw)
    ))(params)

    def to(a):
        return a.astype(jnp.bfloat16).astype(jnp.float32)

    def rounded(p):
        keep = ref.matmul, ref.einsum
        ref.matmul = lambda a, b: keep[0](to(a), to(b))
        ref.einsum = lambda s, a, b: keep[1](s, to(a), to(b))
        try:
            return ref.loss(p, x, y, **kw)
        finally:
            ref.matmul, ref.einsum = keep

    low, g_low = jax.jit(jax.value_and_grad(rounded))(params)
    loss_off = abs(float(low) - float(want)) / abs(float(want))
    worst = max(
        _rel(a, b) for a, b in zip(
            jax.tree_util.tree_leaves(g_low), jax.tree_util.tree_leaves(g_want)
        )
    )
    assert loss_off > RTOL and worst > 10 * GRAD_RTOL, (loss_off, worst)


# -- each field's off value is the code before it ----------------------------


def test_the_off_values_leave_the_tree_and_the_functions_as_they_were():
    off = {}
    for values in FIELDS.values():
        off.update(values)
    cfg = _cfg(**off, rope_original_len=0)
    a = init_params(jax.random.PRNGKey(0), cfg)["layers"][0]["attn"]
    assert sorted(a) == ["kv_norm", "w_kva", "w_kvb", "wo", "wq"]
    assert a["wq"].shape == (64, 4, 16)
    assert sorted(logical_axes(cfg)["layers"][0]["attn"]) == sorted(a)
    # no table, no pairs: ``_rope`` makes theta's own, as it always did
    assert _rope_table(cfg, 8) == {} and yarn_softmax_mscale(cfg) == 1.0
    x = jax.random.normal(jax.random.PRNGKey(1), (2, T, 4, 16))
    pos = jnp.broadcast_to(jnp.arange(T), (2, T))
    assert _pos_scale(x, pos, cfg) is x

    def before(x, positions, theta, dims):
        """``_rope`` as it stood before the table and the pairs."""
        half = dims // 2
        freqs = 1.0 / (
            theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
        )
        ang = positions[:, :, None].astype(jnp.float32) * freqs
        cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
        x1, x2 = x[..., :half], x[..., half:dims]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., dims:]], -1
        )

    assert np.array_equal(_rope(x, pos, 1e4, dims=8), before(x, pos, 1e4, 8))
    # the same program, not only the same numbers
    text = lambda f: jax.jit(f).lower(x, pos).as_text()  # noqa: E731
    assert text(lambda x, p: _rope(x, p, 1e4, dims=8, **_rope_table(cfg, 8))) \
        == text(lambda x, p: _rope(x, p, 1e4, "bthd", 8))


def test_a_query_projected_whole_lowers_as_before_the_latent():
    """``q_latent_dim`` 0 with the other fields off is the latent attention
    PR 45 brought: the same tree and the same lowered loss as a
    configuration that never names the new fields."""
    named = _cfg(q_latent_dim=0, rope_scaling="", rope_factor=1.0,
                 rope_original_len=0, rope_mscale_all_dim=0.0, rope_pairs="",
                 attn_pos_scale_beta=0.0)
    bare = TransformerConfig(**{
        k: v for k, v in named.__dict__.items()
        if not k.startswith(("rope_s", "rope_f", "rope_o", "rope_b",
                             "rope_m", "rope_p", "attn_pos", "q_latent"))
    })
    assert bare == named
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), bare))
    x = jax.ShapeDtypeStruct((2, T), jnp.int32)
    before = trace_counts.snapshot()
    jax.jit(lambda p, x: loss_fn(p, x, x, named, None)).lower(params, x)
    assert added(before, SCALED) == (0, 0, 0, 0)


# -- the table, the scale and the pairs by hand -------------------------------


def test_the_table_by_hand_at_the_published_constants(ref):
    f = yarn_frequencies(64, 1e4, 128.0, 8192, 32.0, 1.0)
    assert f.dtype == np.float32 and f.shape == (32,)
    corr = lambda n: 64 * math.log(8192 / (2 * math.pi * n)) / (  # noqa: E731
        2 * math.log(1e4)
    )
    assert (math.floor(corr(32)), math.ceil(corr(1))) == (12, 25)
    e = [1e4 ** (-2 * j / 64) for j in range(32)]
    assert f[0] == 1.0
    # pairs 0-12 as published for 8192 positions, 25-31 slowed 128 times
    np.testing.assert_allclose(f[:13], e[:13], rtol=1e-6)
    np.testing.assert_allclose(f[25:], np.array(e[25:]) / 128, rtol=1e-6)
    assert abs(f[31] / (1e4 ** (-62 / 64) / 128) - 1) < 1e-6
    # a linear blend between: pair 18 lies 6 / 13 of the way
    ramp = 6 / 13
    assert abs(f[18] / (e[18] * (1 - ramp) + e[18] / 128 * ramp) - 1) < 1e-6
    assert np.all(np.diff(f) < 0)
    table, low, high = ref.yarn_table(64, 1e4, 128.0, 8192, 32.0, 1.0)
    assert (low, high) == (12, 25)
    np.testing.assert_allclose(f, np.asarray(table), rtol=1e-6)
    # the softmax scale's m and the query's s(pos)
    published = _cfg(rope_factor=128.0, rope_original_len=8192)
    m = math.sqrt(yarn_softmax_mscale(published))
    assert abs(m - 1.48520) < 1e-5 and abs(m * m - 2.20583) < 1e-5
    assert abs(ref.softmax_mscale(128.0, 1.0) - m) < 1e-12
    assert yarn_softmax_mscale(replace(published, rope_mscale_all_dim=0.0)) \
        == 1.0
    pos = jnp.array([[0, 8191, 8192, 16383, 16384]])
    s = _pos_scale(jnp.ones((1, 5, 1, 1)), pos, published)[0, :, 0, 0]
    want = [1.0, 1.0, 1.069315, 1.069315, 1 + 0.1 * math.log(3)]
    np.testing.assert_allclose(np.asarray(s), want, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(ref.pos_scale(pos[0].astype(jnp.float32), 0.1, 8192)),
        want, rtol=1e-6,
    )


def test_a_ramp_between_equal_ends_is_a_step():
    # one turn and one turn: every pair past the corner is slowed
    f = yarn_frequencies(8, 1e4, 4.0, 64, 1.0 + 1e-9, 1.0)
    e = np.array([1e4 ** (-2 * j / 8) for j in range(4)])
    corner = math.ceil(8 * math.log(64 / (2 * math.pi)) / (2 * math.log(1e4)))
    assert corner == 2
    np.testing.assert_allclose(f[:2], e[:2], rtol=1e-6)
    np.testing.assert_allclose(f[2:], e[2:] / 4, rtol=1e-6)


def test_interleaved_pairs_are_the_halves_after_the_column_permutation():
    """The one statement of what a checkpoint loader must do: the
    interleaved rotation over a tree is rotate-half over the tree whose
    rope columns of ``w_qb`` and ``w_kva`` alike are ``[even | odd]``."""
    cfg = _cfg()
    params = _weights(cfg)
    x, y = _batch(cfg)
    halves = replace(cfg, rope_pairs="")
    run = lambda c, p: jax.jit(jax.value_and_grad(  # noqa: E731
        lambda p: loss_fn(p, x, y, c, None)
    ))(p)
    (a, ga), (b, gb) = run(cfg, params), run(
        halves, _pairs_to_halves(params, cfg)
    )
    assert abs(float(a) - float(b)) <= 1e-6 * abs(float(a))
    for u, v in zip(
        jax.tree_util.tree_leaves(_pairs_to_halves(ga, cfg)),
        jax.tree_util.tree_leaves(gb),
    ):
        assert _rel(u, v) <= 1e-5
    # and without the permutation they are two models
    other = float(run(halves, params)[0])
    assert abs(other - float(a)) > 10 * RTOL * abs(float(a))
    # ``_rope`` on pairs (2j, 2j + 1), written out
    t = jax.random.normal(jax.random.PRNGKey(3), (1, 5, 2, 8))
    pos = jnp.arange(5)[None]
    f = yarn_frequencies(8, 1e4, 8.0, 16, 32.0, 1.0)
    got = np.asarray(_rope(t, pos, 1e4, freqs=f, pairs="interleaved"))
    for j in range(4):
        ang = np.arange(5) * f[j]
        c, s = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
        first = np.asarray(t[..., 2 * j])
        second = np.asarray(t[..., 2 * j + 1])
        np.testing.assert_allclose(
            got[..., j], first * c - second * s, rtol=1e-5, atol=1e-6
        )
        np.testing.assert_allclose(
            got[..., 4 + j], second * c + first * s, rtol=1e-5, atol=1e-6
        )


def test_the_three_rotary_fields_serve_a_projected_attention_too():
    """The fields are the rotation's, not the latent attention's: a plain
    block model takes them, its cached decoding reads the same table, and
    each is worth more than ten tolerances there."""
    cfg = tiny(
        rope_scaling="yarn", rope_factor=8.0, rope_original_len=16,
        rope_pairs="interleaved", attn_pos_scale_beta=0.1, num_layers=1,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0, 256)
    whole = forward(params, tokens, cfg, None)[0]
    cache = init_kv_cache(cfg, 2, 48)
    first, cache = forward_step(params, tokens[:, :40], cfg, cache, 0)
    rest, _ = forward_step(params, tokens[:, 40:], cfg, cache, 40)
    got = jnp.concatenate([first, rest], axis=1)
    assert _rel(got, whole) <= 1e-4
    for off in (
        dict(rope_scaling="", rope_factor=1.0), dict(rope_pairs=""),
        dict(attn_pos_scale_beta=0.0),
    ):
        other = forward(params, tokens, replace(cfg, **off), None)[0]
        assert _rel(other, whole) > 1e-3, off


# -- a chip's share ----------------------------------------------------------

E, HELD = 8, 4


def _expert_block(held=0, seed=0):
    return init_moe_params(
        jax.random.PRNGKey(seed), E, 32, 24, gated=True, held=held,
        shared_dim=40,
    )


def _run(params, x, held=None):
    return jax.jit(lambda p, x: moe_layer_local(
        p, x, axis_name=None, top_k=2, normalize=True, router="softmax",
        routed_scale=1.0, held=held,
    ))(params, x)


def test_the_shares_add_up_to_the_whole_block(ref):
    """Over both offsets, the held experts' parts plus the shared expert
    counted once are the uncut block, the program's and the reference's,
    the choice made over all 8 columns every time."""
    whole = _expert_block()
    assert whole.shared_gate.shape == (32, 40) and whole.bias is None
    x = jax.random.normal(jax.random.PRNGKey(5), (96, 32))
    want, aux = _run(whole, x)
    plain = jax.jit(
        lambda x, p, offset: ref._experts(x, p, 2, 1.0, offset),
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
        # the router saw all 8, whatever is held
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


# -- the counts --------------------------------------------------------------


def test_the_counts_are_latent_queries_scaled_tables_and_scaled_rows():
    """Two latent attentions over 2 x 64 positions with a table made for
    16: a traced train step is 2 query latents, 2 scaled tables and 2 x 2
    x 48 of 2 x 2 x 64 query rows past the table's length, recomputed or
    not (how the trainer folds what a step's build traced:
    ``test_trace_counts.py``)."""
    cfg = _cfg()
    tx = build_optimizer("adamw", lr=1e-3)
    mesh = build_mesh(MeshConfig(), jax.devices()[:1])
    params = _weights(cfg)
    x, y = _batch(cfg)
    state = TrainState(
        step=jnp.zeros((), jnp.int32), params=params,
        opt_state=tx.init(params),
    )
    for c in (cfg, replace(cfg, remat=True)):
        before = trace_counts.snapshot()
        build_train_step(c, mesh, tx, donate=False).lower(state, x, y)
        assert added(before, SCALED) == (2, 2, 2 * 2 * 48, 2 * 2 * 64)
        # 16-wide scores and values called at one lane tile
        assert added(before, LANES) == (2 * 128, 2 * 16)
    # rows no longer than the table: every query's scale is 1
    short = replace(cfg, rope_original_len=T)
    before = trace_counts.snapshot()
    jax.jit(lambda p: loss_fn(p, x, y, short, None)).lower(params)
    assert added(before, SCALED) == (2, 2, 0, 2 * 2 * 64)
    # each field counts for itself
    before = trace_counts.snapshot()
    only = _cfg(**FIELDS["rope_scaling"], **FIELDS["attn_pos_scale_beta"])
    jax.jit(lambda p: loss_fn(p, x, y, only, None)).lower(params)
    assert added(before, SCALED) == (2, 0, 0, 0)


def test_one_train_step_moves_every_leaf_and_reports_the_routing():
    cfg = _cfg(experts_offset=4)
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
    assert metrics["moe_expert_load"].shape == (8,)
    assert abs(float(metrics["moe_expert_load"].sum()) - 1.0) < 1e-5
    assert float(metrics["moe_drop_rate"]) == 0.0
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(new.params),
        jax.tree_util.tree_leaves(params),
    ):
        assert np.all(np.isfinite(a)), jax.tree_util.keystr(path)
        assert not np.array_equal(a, b), jax.tree_util.keystr(path)


# -- the tree, the refusals, the profile --------------------------------------


def test_the_tree_holds_the_query_latent_and_axes_to_match():
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    a = params["layers"][0]["attn"]
    assert {k: getattr(v, "shape", None) for k, v in a.items()
            if k not in ("kv_norm", "q_latent_norm")} == {
        "w_qa": (64, 32), "w_qb": (32, 4, 16), "w_kva": (64, 24),
        "w_kvb": (16, 4, 24), "wo": (4, 16, 64),
    }
    assert a["q_latent_norm"]["scale"].shape == (32,)
    assert "wq" not in a and "q_norm" not in params["layers"][0]
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
        ({"attn_kind": "", "q_latent_dim": 32, "kv_latent_dim": 0,
          "qk_nope_dim": 0, "qk_rope_dim": 0, "v_head_dim": 0,
          **FIELDS["rope_scaling"]}, "query latent: attn_kind is ''"),
        ({"q_latent_dim": -1}, "q_latent_dim -1"),
        ({"rope_scaling": "ntk"}, "unknown rope_scaling 'ntk'"),
        ({"rope_pairs": "odd"}, "unknown rope_pairs 'odd'"),
        ({"rope": False}, "are of rotary positions: position_kind is "
                          "'learned'"),
        ({"positions": "none"}, "are of rotary positions"),
        ({"rope_original_len": 0, "attn_pos_scale_beta": 0.0},
         r"rope_original_len \(0\) positions"),
        ({"rope_factor": 0.5}, r"rope_factor \(0.5\) times, 1 or more"),
        ({"rope_beta_slow": 32.0}, r"a larger rope_beta_fast \(32.0\)"),
        ({"rope_scaling": "", "rope_factor": 1.0},
         "rope_mscale_all_dim 1.0 scales a latent attention's softmax "
         "under YaRN: rope_scaling is ''"),
        ({"rope_mscale_all_dim": -1.0}, "rope_mscale_all_dim -1.0"),
        ({**FIELDS["rope_scaling"], "rope_original_len": 0},
         "passes rope_original_len, which is 0"),
        ({"attn_pos_scale_beta": -0.1}, "attn_pos_scale_beta -0.1"),
    ],
    ids=lambda v: str(v)[:48],
)
def test_a_configuration_that_cannot_be_is_refused(bad, match):
    with pytest.raises(ValueError, match=match):
        _cfg(**bad)


def test_a_scaled_softmax_is_the_latent_attentions_alone():
    with pytest.raises(ValueError, match="attn_kind ''"):
        tiny(rope_scaling="yarn", rope_factor=8.0, rope_original_len=16,
             rope_mscale_all_dim=1.0)


def test_the_analytic_profile_counts_the_querys_two_projections():
    cfg = TransformerConfig(
        vocab_size=256, num_layers=1, model_dim=64, num_heads=4, mlp_dim=32,
        max_seq_len=T, rope=True, rmsnorm=True, swiglu=True,
        attn_kind="latent", q_latent_dim=32, kv_latent_dim=16, qk_nope_dim=8,
        qk_rope_dim=8, v_head_dim=16,
    )
    whole = replace(cfg, q_latent_dim=0)
    rest = 64 * 24 + 16 * 4 * 24 + 4 * 16 * 64  # w_kva, w_kvb, wo
    got, was = (
        profile_model(c, batch=2, seq=T).modules[1] for c in (cfg, whole)
    )
    assert got.name == was.name == "block0.attn"
    assert got.params == 64 * 32 + 32 * 4 * 16 + rest
    assert was.params == 64 * 4 * 16 + rest
    tokens = 2 * T
    scores_values = 2.0 * tokens * 4 * T * (16 + 16) / 2
    assert got.fwd_flops == 2.0 * tokens * got.params + scores_values
    assert was.fwd_flops == 2.0 * tokens * was.params + scores_values
    # the params the tree holds, the two latents' norms aside
    a = init_params(jax.random.PRNGKey(0), cfg)["layers"][0]["attn"]
    held = sum(x.size for x in jax.tree_util.tree_leaves(a))
    assert held == got.params + 32 + 16
