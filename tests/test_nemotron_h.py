"""A Mamba-2 / attention / sparse-expert hybrid against its plain reference
(ISSUE 37).

A tiny ``nemotron_h`` (pattern ``MEM*E``, width 48, 4 query and 2 key/value
heads of 16, Mamba-2 of 4 heads x 8 with 2 groups of state 16, 8 relu^2
experts of 24 beside a shared one of 40, 3 a token by sigmoid score + bias,
64 tokens a row) in float32 on the CPU, seeded weights: the program's
``loss_fn`` and every gradient leaf against
``benchmark/references/nemotron_h.py`` (loaded by path), the chunked scan
against the step-by-step recurrence, a chip's share of the experts adding up
to the whole layer, the selection bias, a stated head width, and the three
configurations the benchmark already had lowering to the step they lowered
to before.

The tolerance is 2e-5 relative (5e-5 for a gradient leaf): program and
reference both compute in float32 and differ in the order of their sums (the scan in chunks against
one step at a time, sorted grouped matmuls against every expert on every
token, flash attention's jnp path against a plain softmax).
"""

import hashlib
import importlib.util
import json
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.accel.profiler import PipelineStats
from dlrover_tpu.models.config import TransformerConfig, num_moe_layers
from dlrover_tpu.models.train import TrainState, build_train_step
from dlrover_tpu.models.transformer import (
    init_params,
    logical_axes,
    loss_fn,
)
from dlrover_tpu.ops import gated_delta
from dlrover_tpu.ops.mamba2 import (
    causal_conv1d,
    gated_group_rmsnorm,
    ssd_chunked,
)
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.moe import (
    fold_routing_report,
    init_moe_params,
    moe_layer_local,
    relu2,
    route,
)
from dlrover_tpu.trainer.elastic.trainer import build_optimizer
from lowering_fingerprint import (
    configuration,
    fingerprint,
    inner_numbers_off,
    lowered,
    without_names,
)

RTOL = 2e-5
GRAD_RTOL = 5e-5  # a gradient sums more terms in another order
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOP_K = 3


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmark", "references", "nemotron_h.py")
    spec = importlib.util.spec_from_file_location("nemotron_h_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg(**over):
    cfg = TransformerConfig(
        vocab_size=256, num_layers=5, layer_pattern="MEM*E", model_dim=48,
        num_heads=4, num_kv_heads=2, attn_head_dim=16, mlp_dim=24,
        max_seq_len=64, positions="none", rmsnorm=True, norm_eps=1e-5,
        tie_embeddings=False, num_experts=8, moe_top_k=TOP_K,
        norm_topk_prob=True, router="sigmoid", routed_scale=2.5,
        router_bias_rate=1e-3, router_balance_weight=1e-2,
        router_z_weight=0.0, shared_expert_dim=40, mlp_activation="relu2",
        ssm_heads=4, ssm_head_dim=8, ssm_state=16, ssm_groups=2,
        ssm_chunk=16, dtype="float32", param_dtype="float32",
    )
    return replace(cfg, **over)


def _weights(cfg, seed=0):
    """Seeded weights with every scale, bias and skip off its initial
    value, and a token table small enough that the norms' eps counts."""
    params = init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 256))

    def jitter(path, leaf):
        name = getattr(path[-1], "key", None) or getattr(
            path[-1], "name", None
        )
        if name in ("scale", "norm", "D", "conv_b", "bias"):
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


def _ref_loss(ref, p, x, y, **kw):
    return ref.loss(
        p, x, y, top_k=TOP_K, ssm_groups=2, balance_weight=1e-2, **kw
    )


# -- the whole model against the reference --------------------------------


@pytest.mark.parametrize("held", [(0, 0), (2, 4)])
def test_loss_and_every_gradient_leaf_match_the_reference(ref, held):
    count, offset = held
    cfg = _cfg(experts_held=count, experts_offset=offset)
    params = _weights(cfg)
    x, y = _batch(cfg)
    got, g_got = jax.value_and_grad(
        lambda p: loss_fn(p, x, y, cfg, None)
    )(params)
    want, g_want = jax.value_and_grad(
        lambda p: _ref_loss(ref, p, x, y, experts_offset=offset)
    )(params)
    assert abs(float(got) - float(want)) <= RTOL * abs(float(want))
    got_leaves = jax.tree_util.tree_leaves_with_path(g_got)
    want_leaves = jax.tree_util.tree_leaves(g_want)
    # tables and final norm; 2 Mamba-2 layers of 10 + norm; 1 attention
    # of 4 + norm; 2 expert layers of gate, 2 routed, bias, 2 shared + norm
    assert len(got_leaves) == len(want_leaves) == 3 + 2 * 11 + 5 + 2 * 7
    for (path, a), b in zip(got_leaves, want_leaves):
        name = jax.tree_util.keystr(path)
        if name.endswith(".bias"):
            # chooses, never weighs: no gradient, in either
            assert not np.any(np.asarray(a)) and not np.any(np.asarray(b))
            continue
        assert float(jnp.max(jnp.abs(b))) > 0, name
        assert _rel(a, b) <= GRAD_RTOL, name


@pytest.mark.parametrize(
    "switch",
    [
        {"routed_scale": 1.0},
        {"norm_topk_prob": False},
        {"shared_expert_dim": 0},
        {"mlp_activation": ""},
        {"router": "softmax"},
        {"positions": "", "rope": True},
        {"router_balance_weight": 0.0},
        {"norm_eps": 1e-6},
    ],
    ids=lambda s: next(iter(s)),
)
def test_each_switch_is_worth_more_than_ten_tolerances(ref, switch):
    cfg = _cfg()
    params = _weights(cfg)
    x, y = _batch(cfg)
    want = float(_ref_loss(ref, params, x, y))
    off = replace(cfg, **switch)
    p = params
    if "shared_expert_dim" in switch or "router" in switch:
        # a tree the other switch can run: same leaves where both have them
        p = _weights(off)
    got = float(loss_fn(p, x, y, off, None))
    assert abs(got - want) > 10 * RTOL * abs(want), (got, want)


# -- the scan ---------------------------------------------------------------


def ssd_sequential(x, dt, a, Bm, Cm):
    """The recurrence ``ssd_chunked`` computes, one step at a time."""
    Bsz, T, H, P = x.shape
    G = Bm.shape[2]
    rep = H // G
    f32 = jnp.float32

    def step(S, inp):
        x_t, dt_t, B_t, C_t = inp
        B_h = jnp.repeat(B_t, rep, axis=1)  # [B, H, N]
        C_h = jnp.repeat(C_t, rep, axis=1)
        decay = jnp.exp(dt_t * a)[..., None, None]
        S = decay * S + (dt_t[..., None] * x_t)[..., None] * B_h[:, :, None]
        return S, jnp.einsum("bhpn,bhn->bhp", S, C_h)

    S0 = jnp.zeros((Bsz, H, P, Bm.shape[3]), f32)
    xs = tuple(
        jnp.moveaxis(t.astype(f32), 1, 0) for t in (x, dt, Bm, Cm)
    )
    _, ys = jax.lax.scan(step, S0, xs)
    return jnp.moveaxis(ys, 0, 1)


def _scan_inputs(seed=0, B=2, T=48, H=4, P=8, G=2, N=16):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, T, H)) - 2.0)
    a = -jnp.exp(jax.random.normal(k[2], (H,)))
    Bm = jax.random.normal(k[3], (B, T, G, N))
    Cm = jax.random.normal(k[4], (B, T, G, N))
    return x, dt, a, Bm, Cm


@pytest.mark.parametrize("chunk", [8, 16, 48])
def test_chunked_scan_is_the_recurrence(chunk):
    """T = 48 is six, three or one chunk: the state crosses chunk
    boundaries, forward and in every gradient."""
    args = _scan_inputs()
    want = ssd_sequential(*args)
    got = ssd_chunked(*args, chunk)
    assert _rel(got, want) <= RTOL

    def probe(fn):
        return lambda *a: jnp.sum(jnp.sin(fn(*a)))

    g_want = jax.grad(probe(ssd_sequential), argnums=range(5))(*args)
    g_got = jax.grad(
        probe(lambda *a: ssd_chunked(*a, chunk)), argnums=range(5)
    )(*args)
    for a, b in zip(g_got, g_want):
        assert _rel(a, b) <= 5 * RTOL


def test_chunked_scan_refuses_a_ragged_last_chunk():
    with pytest.raises(ValueError, match="whole chunks"):
        ssd_chunked(*_scan_inputs(T=40), 16)


def test_a_strong_decay_forgets_and_none_remembers():
    """a -> -inf: y_t = dt_t x_t (B_t . C_t); a = 0 with dt = 1: the
    state is the plain running sum."""
    x, dt, _, Bm, Cm = _scan_inputs(T=16, G=4)
    y = ssd_chunked(x, dt, jnp.full((4,), -1e4), Bm, Cm, 8)
    own = dt[..., None] * x * jnp.sum(Bm * Cm, -1)[..., None]
    assert _rel(y, own) <= RTOL
    ones = jnp.ones_like(dt)
    y = ssd_chunked(x, ones, jnp.zeros((4,)), Bm, Cm, 8)
    S = jnp.cumsum(jnp.einsum("bthp,bthn->bthpn", x, Bm), axis=1)
    assert _rel(y, jnp.einsum("bthpn,bthn->bthp", S, Cm)) <= RTOL


def test_conv_is_causal_and_the_gate_norms_by_group():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    b = jnp.arange(6.0)
    y = causal_conv1d(x, w, b)
    # token t sees t-3 .. t, nothing later
    y2 = causal_conv1d(x.at[:, 7:].set(0.0), w, b)
    assert np.allclose(y[:, :7], y2[:, :7]) and not np.allclose(y, y2)
    want = b + sum(w[k] * x[0, 5 - 3 + k] for k in range(4))
    assert np.allclose(y[0, 5], want, atol=1e-6)
    # each group has mean square 1 before the weight
    z = jax.random.normal(jax.random.PRNGKey(2), (1, 12, 6))
    g = gated_group_rmsnorm(x, z, jnp.ones((6,)), 2, 0.0)
    ms = jnp.mean(jnp.square(g.reshape(1, 12, 2, 3)), -1)
    assert np.allclose(ms, 1.0, atol=1e-5)


# -- a chip's share of the experts -------------------------------------------


def _expert_layer(held=0, seed=0):
    params = init_moe_params(
        jax.random.PRNGKey(seed), 8, 32, 24, held=held,
        selection_bias=True, shared_dim=40,
    )
    return params._replace(
        bias=0.2 * jax.random.normal(jax.random.PRNGKey(seed + 1), (8,))
    )


def _run(params, x, held=None, **kw):
    return moe_layer_local(
        params, x, axis_name=None, top_k=TOP_K, normalize=True,
        router="sigmoid", routed_scale=2.5, activation=relu2, held=held,
        **kw,
    )


@pytest.mark.parametrize("count", [1, 2, 4])
def test_the_shares_add_up_to_the_whole_layer(ref, count):
    """Over all offsets, the held experts' parts plus the shared expert
    counted once are the uncut layer, the program's and the reference's."""
    whole = _expert_layer()
    x = jax.random.normal(jax.random.PRNGKey(5), (96, 32))
    want, aux = _run(whole, x)
    plain, _ = ref._experts(x, whole, TOP_K, 2.5, 0)
    assert _rel(want, plain) <= RTOL
    no_shared = whole._replace(shared_up=None, shared_down=None)
    total = jnp.zeros_like(want)
    for offset in range(0, 8, count):
        share = no_shared._replace(
            w_up=whole.w_up[offset:offset + count],
            w_down=whole.w_down[offset:offset + count],
        )
        part, part_aux = _run(share, x, held=(offset, count))
        # the router saw all eight, whatever is held
        assert np.array_equal(part_aux["load"], aux["load"])
        with_shared = share._replace(
            shared_up=whole.shared_up, shared_down=whole.shared_down
        )
        assert _rel(
            _run(with_shared, x, held=(offset, count))[0],
            ref._experts(x, with_shared, TOP_K, 2.5, offset)[0],
        ) <= RTOL
        total = total + part
    shared = relu2(x @ whole.shared_up) @ whole.shared_down
    assert _rel(total + shared, want) <= RTOL


def test_no_held_assignment_is_dropped_however_uneven(ref):
    """A router rigged so that every token picks the three held experts:
    all 3 T rows land in the held groups and all are computed."""
    whole = _expert_layer()
    gate = jnp.zeros((32, 8)).at[:, 2:5].set(0.0)
    rigged = whole._replace(
        gate=gate, bias=jnp.zeros((8,)).at[2:5].set(1.0)
    )
    share = rigged._replace(
        w_up=whole.w_up[2:5], w_down=whole.w_down[2:5]
    )
    x = jax.random.normal(jax.random.PRNGKey(6), (64, 32))
    got, aux = _run(share, x, held=(2, 3))
    assert float(aux["drop"]) == 0.0
    assert np.allclose(aux["load"][2:5], 1 / 3)
    assert _rel(got, _run(rigged, x)[0]) <= RTOL
    # and a share no token chose adds the shared expert alone
    none = rigged._replace(w_up=whole.w_up[5:], w_down=whole.w_down[5:])
    got, _ = _run(none, x, held=(5, 3))
    assert _rel(got, relu2(x @ whole.shared_up) @ whole.shared_down) <= RTOL


@pytest.mark.parametrize("hot", [False, True])
def test_an_overloaded_share_takes_more_rounds_and_drops_nothing(ref, hot):
    """2048 tokens x 3 = 6144 assignments, one expert of 8 held: a round
    is 1536 rows (twice the balanced 768). Balanced, the held rows fit one
    round; with a bias that sends every token to the held expert they
    are 2048, two rounds' worth, and all are computed, forward and in
    the gradients."""
    from dlrover_tpu.parallel.moe import share_rows

    assert share_rows(6144, 1, 8) == 1536
    assert share_rows(8192 * 6, 8, 128) == 6144  # the cell's: 8 rounds
    assert share_rows(192, 3, 8) == 192  # never more than there are
    whole = _expert_layer()
    if hot:
        whole = whole._replace(bias=jnp.zeros((8,)).at[5].set(4.0))
    share = whole._replace(w_up=whole.w_up[5:6], w_down=whole.w_down[5:6])
    x = jax.random.normal(jax.random.PRNGKey(8), (2048, 32))
    got, aux = _run(share, x, held=(5, 1))
    rows = round(float(aux["load"][5]) * 6144)
    assert (rows == 2048) if hot else (0 < rows < 1536)
    want, _ = ref._experts(x, share, TOP_K, 2.5, 5)
    assert _rel(got, want) <= RTOL

    def probe(fn):
        return lambda p, x: jnp.sum(jnp.sin(fn(p, x)))

    g_got = jax.grad(probe(lambda p, x: _run(p, x, held=(5, 1))[0]), (0, 1))(
        share, x
    )
    g_want = jax.grad(
        probe(lambda p, x: ref._experts(x, p, TOP_K, 2.5, 5)[0]), (0, 1)
    )(share, x)
    for a, b in zip(
        jax.tree_util.tree_leaves(g_got), jax.tree_util.tree_leaves(g_want)
    ):
        if np.any(np.asarray(b)):
            assert _rel(a, b) <= 5 * RTOL


def test_share_gradients_match_the_reference(ref):
    share = _expert_layer(held=2)
    x = jax.random.normal(jax.random.PRNGKey(7), (64, 32))

    def probe(fn):
        return lambda p, x: jnp.sum(jnp.sin(fn(p, x)))

    got = jax.grad(probe(lambda p, x: _run(p, x, held=(4, 2))[0]), (0, 1))(
        share, x
    )
    want = jax.grad(
        probe(lambda p, x: ref._experts(x, p, TOP_K, 2.5, 4)[0]), (0, 1)
    )(share, x)
    for a, b in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    ):
        if np.any(np.asarray(b)):
            assert _rel(a, b) <= 5 * RTOL


def test_a_share_over_an_ep_axis_is_refused():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = build_mesh(MeshConfig(ep=2), jax.devices()[:2])
    share = _expert_layer(held=2)
    with pytest.raises(ValueError, match="one-device layout"):
        shard_map(
            lambda x: moe_layer_local(
                share, x, axis_name="ep", router="sigmoid", held=(0, 2)
            )[0],
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False,
        )(jnp.zeros((8, 32)))


# -- the router ---------------------------------------------------------------


def test_bias_changes_who_is_chosen_and_not_their_gates():
    logits = jax.random.normal(jax.random.PRNGKey(0), (64, 8))
    idx0, gates0, aux0 = route(logits, 3, True, kind="sigmoid", scale=2.5)
    bias = jnp.zeros((8,)).at[7].set(5.0)  # expert 7 wins everywhere
    idx1, gates1, aux1 = route(
        logits, 3, True, kind="sigmoid", bias=bias, scale=2.5
    )
    assert np.all(np.any(np.asarray(idx1) == 7, axis=1))
    assert not np.array_equal(np.sort(idx0, 1), np.sort(idx1, 1))
    assert float(aux1["load"][7]) == pytest.approx(1 / 3)
    # the gate values are the chosen scores over their sum, times 2.5,
    # with no trace of the bias
    s = np.asarray(jax.nn.sigmoid(logits))
    chosen = np.take_along_axis(s, np.asarray(idx1), 1)
    want = 2.5 * chosen / chosen.sum(1, keepdims=True)
    assert np.allclose(gates1, want, rtol=1e-6)
    assert np.allclose(np.sum(gates1, 1), 2.5, rtol=1e-6)
    assert float(aux1["z"]) == 0.0
    # where the bias changes no choice it changes nothing at all
    idx2, gates2, _ = route(
        logits, 3, True, kind="sigmoid", bias=jnp.full((8,), 0.3), scale=2.5
    )
    assert np.array_equal(idx0, idx2) and np.array_equal(gates0, gates2)


def test_bias_takes_no_gradient_and_no_decay_and_moves_by_the_rule():
    cfg = _cfg(experts_held=4, experts_offset=4)
    tx = build_optimizer("adamw", lr=1e-2, weight_decay=0.5)
    mesh = build_mesh(MeshConfig(), jax.devices()[:1])
    params = _weights(cfg)
    state = TrainState(
        step=jnp.zeros((), jnp.int32), params=params,
        opt_state=tx.init(params),
    )
    step = build_train_step(cfg, mesh, tx, donate=False)
    x, y = _batch(cfg, rows=2)
    loss, aux = loss_fn(params, x, y, cfg, None, return_aux=True)
    new, metrics = step(state, x, y)
    assert aux["layer_load"].shape == (num_moe_layers(cfg), 8)
    sparse = [i for i, k in enumerate(cfg.layer_pattern) if k == "E"]
    for row, i in enumerate(sparse):
        before = params["layers"][i]["moe"].bias
        after = new.params["layers"][i]["moe"].bias
        load = aux["layer_load"][row]
        want = before + 1e-3 * jnp.sign(jnp.mean(load) - load)
        # exactly the rule: a decay of 0.5 * 1e-2 * b would show
        assert np.allclose(after, want, atol=1e-7)
        assert float(jnp.max(jnp.abs(after - before))) == pytest.approx(
            1e-3, rel=1e-3
        )
        # while a leaf beside it did decay and move
        assert not np.allclose(
            new.params["layers"][i]["moe"].gate,
            params["layers"][i]["moe"].gate,
        )
    assert float(metrics["loss"]) == pytest.approx(float(loss), rel=1e-5)


def test_held_share_counter():
    stats = PipelineStats()
    load = np.array([0.05, 0.05, 0.2, 0.1, 0.3, 0.1, 0.1, 0.1], np.float32)
    metrics = {"moe_drop_rate": np.float32(0.0), "moe_expert_load": load}
    fold_routing_report(metrics, stats, (2, 3))
    fold_routing_report(metrics, stats, (2, 3))
    assert stats.moe_reports == 2
    assert stats.moe_held_share_sum == pytest.approx(2 * 0.6)
    assert "moe_held_share_sum" in stats.as_dict()
    whole = PipelineStats()
    fold_routing_report(metrics, whole)
    assert whole.moe_held_share_sum == pytest.approx(1.0)
    fold_routing_report({"loss": 1.0}, whole)  # a dense model: nothing
    assert whole.moe_reports == 1


# -- the configuration --------------------------------------------------------


def test_a_stated_head_width_that_is_not_model_dim_over_heads():
    cfg = _cfg()
    assert cfg.head_dim == 16 and cfg.model_dim // cfg.num_heads == 12
    attn = init_params(jax.random.PRNGKey(0), cfg)["layers"][3]["attn"]
    assert attn["wq"].shape == (48, 4, 16)
    assert attn["wk"].shape == (48, 2, 16)
    assert attn["wo"].shape == (4, 16, 48)
    # the legacy block takes it too, and without it derives as before
    dense = TransformerConfig(
        vocab_size=64, num_layers=1, model_dim=48, num_heads=4,
        attn_head_dim=32, max_seq_len=16, dtype="float32",
    )
    p = init_params(jax.random.PRNGKey(0), dense)
    assert p["layers"][0]["attn"]["wq"].shape == (48, 4, 32)
    x = jnp.zeros((1, 16), jnp.int32)
    assert np.isfinite(float(loss_fn(p, x, x, dense, None)))
    assert replace(dense, attn_head_dim=None).head_dim == 12


def test_the_tree_has_one_mixer_a_layer_and_axes_to_match():
    cfg = _cfg(experts_held=2, experts_offset=6)
    params = init_params(jax.random.PRNGKey(0), cfg)
    kinds = [sorted(set(layer) - {"norm"}) for layer in params["layers"]]
    assert kinds == [["ssm"], ["moe"], ["ssm"], ["attn"], ["moe"]]
    assert "positions" not in params["embed"]
    moe = params["layers"][1]["moe"]
    assert moe.gate.shape == (48, 8) and moe.bias.shape == (8,)
    assert moe.w_up.shape == (2, 48, 24) and moe.w_down.shape == (2, 24, 48)
    assert moe.shared_up.shape == (48, 40) and moe.w_gate is None
    ssm = params["layers"][0]["ssm"]
    assert ssm["w_xbc"].shape == (48, 32 + 2 * 2 * 16)
    assert ssm["conv_w"].shape == (4, 96) and ssm["A_log"].shape == (4,)
    # dt_bias is the inverse softplus of a step inside the stated range
    dt = np.asarray(jax.nn.softplus(ssm["dt_bias"]))
    assert np.all(dt >= 1e-3 * 0.999) and np.all(dt <= 0.1 * 1.001)
    assert np.all(np.exp(ssm["A_log"]) >= 1) and np.all(
        np.exp(ssm["A_log"]) <= 16
    )

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
        ({"layer_pattern": "MEX*E"}, "kinds are"),
        ({"layer_pattern": "ME"}, "num_layers is 5"),
        ({"scan_layers": True}, "homogeneous"),
        ({"positions": "alibi"}, "unknown positions"),
        ({"router": "hash"}, "unknown router"),
        ({"mlp_activation": "tanh"}, "unknown mlp_activation"),
        ({"experts_held": 4, "experts_offset": 6}, "not among the 8"),
    ],
    ids=lambda v: next(iter(v)) if isinstance(v, dict) else "",
)
def test_a_configuration_that_cannot_be_is_refused(bad, match):
    with pytest.raises(ValueError, match=match):
        _cfg(**bad)


def test_remat_gives_the_same_loss_and_gradients():
    cfg = _cfg()
    params = _weights(cfg)
    x, y = _batch(cfg)
    a, ga = jax.value_and_grad(lambda p: loss_fn(p, x, y, cfg, None))(params)
    on = replace(cfg, remat=True)
    b, gb = jax.value_and_grad(lambda p: loss_fn(p, x, y, on, None))(params)
    assert float(a) == pytest.approx(float(b), rel=1e-6)
    for u, v in zip(
        jax.tree_util.tree_leaves(ga), jax.tree_util.tree_leaves(gb)
    ):
        if np.any(np.asarray(u)):
            assert _rel(v, u) <= RTOL


# -- what was there before stays as it was ------------------------------------

with open(os.path.join(ROOT, "tests", "data", "step_lowering.json")) as f:
    RECORDED = json.load(f)


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_existing_configurations_lower_to_the_step_they_had(name):
    """``tests/lowering_fingerprint.py``: the parameter tree and the
    lowered train step of the configurations the benchmark had before
    ISSUE 37, against what the commit before it gave."""
    want = {k: RECORDED[name][k] for k in ("tree", "step")}
    assert fingerprint(name) == want


WITHOUT_NAMES = sorted(
    n for n in RECORDED if "step_without_names" in RECORDED[n]
)


@pytest.mark.parametrize("name", WITHOUT_NAMES)
def test_a_delta_rule_step_without_its_names_is_the_step_it_was(name):
    """The names the delta rule gives a recomputed layer to keep
    (``ops/gated_delta.KEPT``, ISSUE 56) taken off, a step lowers byte
    for byte to the text recorded before they came; and where nothing
    recomputes, the step with the names is that text but for the numbers
    that tell an inner function's copies apart: a name lowers to nothing
    outside a policy."""
    plain = without_names(name)
    sha = hashlib.sha256(plain.encode()).hexdigest()
    assert sha == RECORDED[name]["step_without_names"]
    named = lowered(name)[1]
    assert not any(kept in named for kept in gated_delta.KEPT)
    remat = configuration(name)["model"].get("remat", False)
    same = inner_numbers_off(named) == inner_numbers_off(plain)
    assert same == (not remat)
