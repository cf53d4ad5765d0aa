"""OLMoE against its plain reference (ISSUE 27).

A tiny OLMoE (2 layers, width 64, 4 heads of 16, 8 gated experts of 32,
2 or 4 a token, 64 tokens a row) in float32 on the CPU, seeded weights:
the program's ``loss_fn`` and every gradient leaf against
``benchmark/references/olmoe.py`` (loaded by path: there is no second copy
to drift), what each of the architecture's switches is worth in the loss,
the dropless dispatch under a rigged router and with empty groups, and the
routing counters of ``PipelineStats``.

The tolerance is 1e-5 relative: program and reference both compute in
float32 and differ only in the order of their sums (sorted grouped matmuls
against every-expert-on-every-token, flash attention's jnp path against a
plain softmax). A switch left out moves the loss by more than ten times
that.
"""

import importlib.util
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.accel.strategy import Strategy
from dlrover_tpu.models.config import TransformerConfig
from dlrover_tpu.models.transformer import init_params, loss_fn
from dlrover_tpu.parallel.mesh import MeshConfig
from dlrover_tpu.parallel.moe import (
    MoEParams,
    _moe_dropless,
    init_moe_params,
    moe_layer_local,
)
from dlrover_tpu.trainer.elastic.trainer import ElasticTrainer, TrainerConfig

RTOL = 1e-5
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmark", "references", "olmoe.py")
    spec = importlib.util.spec_from_file_location("olmoe_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg(top_k=2, **over):
    cfg = TransformerConfig(
        vocab_size=256, num_layers=2, model_dim=64, num_heads=4,
        num_kv_heads=4, mlp_dim=32, max_seq_len=64, rope=True,
        rope_theta=10000.0, rmsnorm=True, norm_eps=1e-5, swiglu=True,
        qk_norm=True, tie_embeddings=False, num_experts=8, moe_every=1,
        moe_top_k=top_k, norm_topk_prob=False, router_z_weight=1e-3,
        dtype="float32", param_dtype="float32",
    )
    return replace(cfg, **over)


def _weights(cfg, seed=0):
    """Seeded weights with every scale off one, and a token table small
    enough (mean square 6e-6) that the norms' eps is worth something."""
    params = init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def jitter(path, leaf):
        if getattr(path[-1], "key", None) == "scale":
            return leaf + 0.1 * jax.random.normal(next(keys), leaf.shape)
        return leaf

    params = jax.tree_util.tree_map_with_path(
        jitter, params, is_leaf=lambda x: isinstance(x, jnp.ndarray)
    )
    params["embed"]["tokens"] = 0.02 * params["embed"]["tokens"]
    return params


def _batch(cfg, seed=0):
    rng = np.random.default_rng(seed)
    data = rng.integers(0, cfg.vocab_size, (2, 65)).astype(np.int32)
    return data[:, :-1], data[:, 1:]


def _rel(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


@pytest.mark.parametrize("top_k", [2, 4])
def test_loss_and_every_gradient_leaf_match_the_reference(ref, top_k):
    cfg = _cfg(top_k)
    params = _weights(cfg)
    x, y = _batch(cfg)
    got, g_got = jax.value_and_grad(
        lambda p: loss_fn(p, x, y, cfg, None)
    )(params)
    want, g_want = jax.value_and_grad(
        lambda p: ref.loss(p, x, y, top_k=top_k)
    )(params)
    assert abs(float(got) - float(want)) <= RTOL * abs(float(want))
    got_leaves = jax.tree_util.tree_leaves_with_path(g_got)
    want_leaves = jax.tree_util.tree_leaves(g_want)
    assert len(got_leaves) == len(want_leaves) == 3 + 2 * 12
    for (path, a), b in zip(got_leaves, want_leaves):
        assert float(jnp.max(jnp.abs(b))) > 0, path
        assert _rel(a, b) <= RTOL, jax.tree_util.keystr(path)


@pytest.mark.parametrize(
    "switch",
    [
        {"qk_norm": False},
        {"norm_topk_prob": True},
        {"moe_top_k": 3},
        {"norm_eps": 1e-6},
    ],
    ids=lambda s: next(iter(s)),
)
def test_each_switch_is_worth_more_than_ten_tolerances(ref, switch):
    cfg = _cfg(4)
    params = _weights(cfg)
    x, y = _batch(cfg)
    want = float(ref.loss(params, x, y, top_k=4))
    assert abs(float(loss_fn(params, x, y, cfg, None)) - want) <= RTOL * want
    off = float(loss_fn(params, x, y, replace(cfg, **switch), None))
    assert abs(off - want) > 10 * RTOL * want, (switch, off, want)


def _rigged(top_k, E=8, d=16, f=32, T=64):
    """Every token sends its k assignments to experts 0..k-1: feature 0
    is a constant and only its row of the router is non-zero."""
    moe = init_moe_params(jax.random.PRNGKey(3), E, d, f, gated=True)
    gate = jnp.zeros((d, E)).at[0].set(jnp.arange(E, 0, -1.0))
    x = jax.random.normal(jax.random.PRNGKey(4), (T, d)).at[:, 0].set(4.0)
    return moe._replace(gate=gate), x


@pytest.mark.parametrize("top_k", [1, 4])
def test_a_rigged_router_drops_nothing(ref, top_k):
    moe, x = _rigged(top_k)
    out, aux = moe_layer_local(
        moe, x, axis_name=None, top_k=top_k, normalize=False,
        capacity_factor=1.25,  # 1.25 * k * 64 / 8 slots would keep 10k of 64k
    )
    want, balance, z = ref._sparse_ffn(x, moe, top_k, False)
    assert float(aux["drop"]) == 0.0
    np.testing.assert_allclose(
        np.asarray(aux["load"]), [1.0 / top_k] * top_k + [0.0] * (8 - top_k)
    )
    assert _rel(out, want) <= RTOL
    np.testing.assert_allclose(float(aux["balance"]), float(balance), RTOL)
    np.testing.assert_allclose(float(aux["z"]), float(z), RTOL)


def test_a_rigged_router_in_the_model_reports_no_drop(ref):
    cfg = _cfg(4)
    params = _weights(cfg)
    x, y = _batch(cfg)
    for layer in params["layers"]:
        # a router of zeros: every expert is as likely, so every token's
        # four go to the same four experts and the other four get none
        layer["moe"] = layer["moe"]._replace(
            gate=jnp.zeros_like(layer["moe"].gate)
        )
    got, aux = loss_fn(params, x, y, cfg, None, return_aux=True)
    want = float(ref.loss(params, x, y, top_k=4))
    assert float(aux["drop"]) == 0.0
    np.testing.assert_allclose(  # summed over the two layers
        np.asarray(aux["load"]), [2 * 0.25] * 4 + [0.0] * 4
    )
    assert abs(float(got) - want) <= RTOL * want


def _per_expert_loop(moe, x, idx, gates):
    out = jnp.zeros_like(x)
    for e in range(moe.w_up.shape[0]):
        y = (
            jax.nn.silu(x @ moe.w_gate[e]) * (x @ moe.w_up[e])
        ) @ moe.w_down[e]
        out = out + jnp.sum(gates * (idx == e), axis=1)[:, None] * y
    return out


def test_grouped_matmul_matches_a_per_expert_loop_with_empty_groups():
    E, d, f, T, k = 8, 16, 32, 40, 2
    moe = init_moe_params(jax.random.PRNGKey(5), E, d, f, gated=True)
    x = jax.random.normal(jax.random.PRNGKey(6), (T, d))
    rng = np.random.default_rng(7)
    # experts 0, 3 and 7 get nothing; 5 gets a row of every token
    idx = np.stack(
        [np.full(T, 5), rng.choice([1, 2, 4, 6], T)], axis=1
    ).astype(np.int32)
    gates = jnp.asarray(rng.random((T, k)), jnp.float32)
    counts = jnp.asarray(np.bincount(idx.reshape(-1), minlength=E), jnp.int32)
    assert sorted(np.flatnonzero(np.asarray(counts) == 0)) == [0, 3, 7]

    def grouped(moe, x, gates):
        return _moe_dropless(moe, x, jnp.asarray(idx), gates, counts, None)

    def looped(moe, x, gates):
        return _per_expert_loop(moe, x, idx, gates)

    assert _rel(grouped(moe, x, gates), looped(moe, x, gates)) <= RTOL
    w = jax.random.normal(jax.random.PRNGKey(8), (T, d))
    grads = [
        jax.grad(lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2))(
            moe, x, gates
        )
        for fn in (grouped, looped)
    ]
    got, want = (jax.tree_util.tree_leaves(g) for g in grads)
    assert len(got) == len(want) == 6  # four expert tensors, x, gates
    for a, b in zip(got, want):
        if float(jnp.max(jnp.abs(b))) == 0:  # the router is not on this path
            assert float(jnp.max(jnp.abs(a))) == 0
        else:
            assert _rel(a, b) <= RTOL
    # an expert nobody chose gets exactly no gradient
    assert float(jnp.max(jnp.abs(grads[0][0].w_up[jnp.array([0, 3, 7])]))) == 0


def test_experts_are_the_gelu_pair_without_swiglu():
    moe = init_moe_params(jax.random.PRNGKey(9), 4, 16, 32)
    assert moe.w_gate is None and isinstance(moe, MoEParams)
    x = jax.random.normal(jax.random.PRNGKey(10), (32, 16))
    out, aux = moe_layer_local(moe, x, axis_name=None, top_k=1)
    probs = jax.nn.softmax(x @ moe.gate, -1)
    e = jnp.argmax(probs, -1)
    h = jax.nn.gelu(jnp.einsum("tm,tmh->th", x, moe.w_up[e]))
    want = jnp.einsum("th,thm->tm", h, moe.w_down[e]) * jnp.max(
        probs, -1, keepdims=True
    )
    assert _rel(out, want) <= RTOL and float(aux["drop"]) == 0.0


class _Tokens:
    def __init__(self, n=256, seq=64, vocab=256):
        rng = np.random.default_rng(0)
        self.data = rng.integers(0, vocab, (n, seq + 1), dtype=np.int32)

    def __len__(self):
        return len(self.data)

    def __getitem__(self, i):
        return {"x": self.data[i][:-1], "y": self.data[i][1:]}


def test_routing_counters_rise_at_the_log_cadence():
    trainer = ElasticTrainer(
        _cfg(2), optax.adamw(1e-3), _Tokens(),
        TrainerConfig(
            batch_size=4, seq_len=64, report_metrics=False, log_interval=3,
        ),
        strategy=Strategy(mesh=MeshConfig()),
        devices=jax.devices()[:1],
    )
    try:
        stats = trainer.pipeline_stats
        assert stats.moe_reports == 0
        trainer.train(num_steps=5)
        assert stats.moe_reports == 1  # step 3; the next is due at 6
        trainer.train(num_steps=10)
        assert stats.moe_reports == 3  # steps 3, 6, 9
        assert stats.moe_drop_rate_sum == 0.0
        # the largest expert's share x E: 1 when even, E when one takes all
        assert 3 * 1.0 <= stats.moe_max_load_sum <= 3 * 8.0
        d = stats.as_dict()
        assert d["moe_reports"] == 3 and "moe_max_load_sum" in d
    finally:
        trainer.close()
