"""The ``afmoe`` family (Trinity-Mini's) at a small size on the CPU: the
program held to ``benchmark/references/afmoe.py`` (loss and every gradient
leaf; window layers with T > W; rotary positions in the window layers and
none in the global ones; a norm on every mixer's output; the embedding's
multiplier; the shares adding up), and what refuses a window. (The
attention call itself: ``test_window_attention.py``.)"""

import functools
import importlib.util
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.config import TransformerConfig
from dlrover_tpu.models.train import build_train_step
from dlrover_tpu.models.transformer import (
    _attention_block,
    forward,
    init_kv_cache,
    init_params,
    logical_axes,
    loss_fn,
)
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.moe import init_moe_params, moe_layer_local
from dlrover_tpu.parallel.pipeline import _check_pipeline_cfg
from dlrover_tpu.trainer.elastic.trainer import build_optimizer

RTOL = 2e-5
GRAD_RTOL = 2e-4  # a gradient sums more terms in another order
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOP_K = 3
WINDOW_KEYS = 24  # under T = 64, and no multiple of any block
PATTERN = "W-WE*EWEWE"  # the cut's: published layers 1-5
REF_KW = dict(top_k=TOP_K, window=WINDOW_KEYS, balance_weight=1e-2)


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmark", "references", "afmoe.py")
    spec = importlib.util.spec_from_file_location("afmoe_ref", path)
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
        vocab_size=256, num_layers=10, layer_pattern=PATTERN,
        attn_window=WINDOW_KEYS, positions="window", mixer_out_norm=True,
        embed_scale=True, model_dim=48, num_heads=4, num_kv_heads=2,
        attn_head_dim=8, mlp_dim=24, dense_mlp_dim=40, max_seq_len=64,
        rope_theta=1e4, rmsnorm=True, norm_eps=1e-5, swiglu=True,
        tie_embeddings=False, qk_norm=True, qk_norm_span="head",
        attn_gate="sigmoid", num_experts=16, moe_top_k=TOP_K,
        norm_topk_prob=True, router="sigmoid", routed_scale=2.826,
        router_balance_weight=1e-2, router_z_weight=0.0,
        shared_expert_dim=24, dtype="float32", param_dtype="float32",
    )
    return replace(cfg, **over)


def _weights(cfg, seed=1):
    """Seeded weights with every norm weight and selection bias off its
    initial value."""
    params = init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 256))

    def jitter(path, leaf):
        name = getattr(path[-1], "key", None) or getattr(
            path[-1], "name", None
        )
        if name in ("scale", "bias"):
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
    # tables and final norm; 5 attention layers of 4 + 2 head norms + 2
    # norms; the dense layer of 3 + 2 norms; 4 expert blocks of gate, 3
    # routed, bias, 3 shared + 2 norms
    assert len(got_leaves) == len(want_leaves) == 3 + 5 * 8 + 5 + 4 * 10
    for (path, a), b in zip(got_leaves, want_leaves):
        name = jax.tree_util.keystr(path)
        if name.endswith(".bias"):  # steers the choice, takes no gradient
            assert not np.any(np.asarray(a)) and not np.any(np.asarray(b))
            continue
        assert float(jnp.max(jnp.abs(b))) > 0, name
        assert _rel(a, b) <= GRAD_RTOL, name


def test_the_tree_and_its_axes_hold_the_two_norms_of_every_layer():
    cfg = _cfg()
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    axes = logical_axes(cfg)
    for kind, layer, names in zip(PATTERN, shapes["layers"], axes["layers"]):
        assert set(layer) == set(names)
        assert layer["norm"]["scale"].shape == (48,)
        assert layer["out_norm"]["scale"].shape == (48,)
        assert ("q_norm" in layer) == (kind in "*W")
    # a window layer's parameters are a global layer's
    window, full = shapes["layers"][0], shapes["layers"][4]
    assert jax.tree_util.tree_map(lambda a: a.shape, window["attn"]) == (
        jax.tree_util.tree_map(lambda a: a.shape, full["attn"])
    )
    assert window["attn"]["wq"].shape == (48, 4, 16)  # [query | gate]


@pytest.mark.parametrize(
    "switch",
    [
        {"attn_window": 16},
        {"attn_window": 64},  # every layer global in all but positions
        {"layer_pattern": "W-WEWE*EWE"},  # the global layer elsewhere
        {"layer_pattern": "W-WEWEWEWE"},
        {"positions": "none"},
        {"positions": "", "rope": True},  # rotary in the global layer too
        {"mixer_out_norm": False},
        {"embed_scale": False},
        {"attn_gate": ""},
        {"qk_norm": False},
        {"rope_theta": 1e6},
        {"routed_scale": 1.0},
        {"shared_expert_dim": 0},
        {"norm_topk_prob": False},
        {"router_balance_weight": 0.0},
        {"dense_mlp_dim": 24},
        {"norm_eps": 1e-3},
    ],
    ids=lambda s: "-".join(f"{k}={v}" for k, v in s.items()),
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


@pytest.mark.parametrize("kind", ["*", "W"])
def test_positions_reach_the_window_layers_and_no_global_layer(kind):
    """Other positions (the tokens' order kept, their distances doubled)
    leave a global layer's output as it was, bit for bit, and move a
    window layer's."""
    cfg = _cfg()
    layer = _weights(cfg)["layers"][PATTERN.index(kind)]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 48))
    positions = jnp.broadcast_to(jnp.arange(64), (2, 64))
    run = jax.jit(lambda pos: _attention_block(
        x, layer, cfg, None, pos, "norm", kind
    ))
    same, other = run(positions), run(2 * positions)
    if kind == "*":
        assert np.array_equal(same, other)
    else:
        assert _rel(other, same) > 1e-2


def test_the_embedding_alone_is_multiplied():
    cfg = _cfg(num_layers=1, layer_pattern="W")
    params = _weights(cfg)
    x, _ = _batch(cfg)
    plain = replace(cfg, embed_scale=False)
    scaled = dict(params, embed={
        "tokens": params["embed"]["tokens"] * jnp.sqrt(jnp.float32(48))
    })
    got, _ = forward(params, x, cfg)
    want, _ = forward(scaled, x, plain)  # the head's table as it was
    assert _rel(got, want) <= RTOL


# -- the shares add up -------------------------------------------------------

E, HELD = 32, 4  # eight shares, as the deployment's eight chips a layer


def _expert_block(held=0, seed=0):
    block = init_moe_params(
        jax.random.PRNGKey(seed), E, 32, 24, gated=True, held=held,
        selection_bias=True, shared_dim=24,
    )
    return block._replace(
        bias=0.2 * jax.random.normal(jax.random.PRNGKey(seed + 1), (E,))
    )


@functools.partial(jax.jit, static_argnames="held")
def _run(params, x, held=None):
    return moe_layer_local(
        params, x, axis_name=None, top_k=4, normalize=True, router="sigmoid",
        routed_scale=2.826, held=held,
    )


def test_the_shares_add_up_to_the_whole_block(ref):
    """Over all 8 offsets, the held experts' parts plus the shared expert
    counted once are the uncut block, the program's and the reference's,
    the choice made over all 32 columns every time."""
    whole = _expert_block()
    assert whole.shared_gate.shape == (32, 24)
    assert whole.shared_out_gate is None and whole.bias.shape == (E,)
    x = jax.random.normal(jax.random.PRNGKey(5), (96, 32))
    want, aux = _run(whole, x)
    plain = jax.jit(
        lambda x, p, offset: ref._experts(x, p, 4, 2.826, offset),
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


# -- what refuses a window, and nonsense --------------------------------------


def test_sequence_parallel_attention_refuses_a_window():
    cfg = _cfg()
    mesh = build_mesh(MeshConfig(sp=2), jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="window"):
        build_train_step(cfg, mesh, build_optimizer("adamw", lr=1e-3))
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    x = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    with pytest.raises(NotImplementedError, match="window"):
        jax.eval_shape(lambda p, x: forward(p, x, cfg, mesh), params, x)


def test_the_pipeline_refuses_a_window():
    with pytest.raises(ValueError, match="window"):
        _check_pipeline_cfg(_cfg(), 2)


def test_cached_decoding_refuses_a_window():
    with pytest.raises(NotImplementedError, match="window"):
        init_kv_cache(_cfg(), 1, 64)
    with pytest.raises(NotImplementedError, match="embedding"):
        init_kv_cache(TransformerConfig(embed_scale=True), 1, 64)


@pytest.mark.parametrize("nonsense", [
    dict(attn_window=0),
    dict(attn_window=-1),
    dict(attn_window=True),
    dict(layer_pattern="*-*E*E*E*E"),  # a window and no layer to have it
    dict(layer_pattern="*-*E*E*E*E", attn_window=0),  # positions "window"
    dict(attn_kind="latent", kv_latent_dim=16, qk_nope_dim=8, qk_rope_dim=8,
         v_head_dim=8, attn_gate="", num_kv_heads=None),
    dict(layer_pattern="", num_layers=2, attn_window=0, positions=""),
    dict(embed_scale="sqrt_dim"),
    dict(positions="windows"),
])
def test_construction_refuses(nonsense):
    with pytest.raises(ValueError):
        _cfg(**nonsense)
