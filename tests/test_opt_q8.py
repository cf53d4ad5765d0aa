"""The one-pass int8-AdamW step (``ops/quantized_optim._q8_adam_step``, the
Pallas call ``q8_adam_step``) against the statement it is held to:
``update`` + ``optax.apply_updates``. On the CPU the kernel is interpreted;
the rule that takes a leaf asks for a TPU backend, so the tests that want the
kernel say so where the rule asks (``quantized_optim._on_tpu``), in the test
and not through an option of the program."""

import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.common import trace_counts
from dlrover_tpu.ops import quantized_optim as q8
from dlrover_tpu.trainer.elastic.optimizer import build_optimizer

STEPS = 5


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(q8, "_on_tpu", lambda: True)


def _leaf(shape, seed=0):
    """Rows of unlike sizes, as a layer's gradient has them."""
    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    return jax.random.normal(ka, shape, jnp.float32) * jnp.exp(
        jax.random.normal(kb, (*shape[:-1], 1))
    )


def _calls(fn, *args):
    """The Pallas calls of ``fn``'s jaxpr, inner jaxprs included."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def _moments(state):
    return [
        q for q in jax.tree.leaves(
            state, is_leaf=lambda x: isinstance(x, q8.Quantized8)
        ) if isinstance(q, q8.Quantized8)
    ]


def _random_state(tx, params, seed=7):
    """Moments a run would hold, not the zeros of ``init``: one step of
    the statement on a gradient of its own."""
    grads = jax.tree.map(
        lambda p: 0.3 * _leaf(p.shape, seed).astype(p.dtype), params
    )
    return jax.jit(tx.update)(grads, tx.init(params), params)[1]


def _held_to_the_statement(tx, params, grads_at, scale):
    """``STEPS`` steps of ``update_and_apply`` beside ``update`` +
    ``apply_updates`` from one random state: scales equal to their last
    bits, codes equal but at rounding ties (counted: a tie moves a code by
    one), parameters to 1e-6 of the leaf's size."""
    def statement(g, st, p):
        u, st = tx.update(g, st, p)
        if scale is not None:
            u = jax.tree.map(lambda x: scale * x, u)
        return optax.apply_updates(p, u), st

    extra = {} if scale is None else {"scale": scale}
    fused = jax.jit(lambda g, st, p: tx.update_and_apply(g, st, p, **extra))
    statement = jax.jit(statement)
    want_p, want_st = params, _random_state(tx, params)
    got_p, got_st = want_p, want_st
    for i in range(STEPS):
        g = grads_at(i)
        want_p, want_st = statement(g, want_st, want_p)
        got_p, got_st = fused(g, got_st, got_p)
        assert jax.tree.structure(got_st) == jax.tree.structure(want_st)
        ties = elements = 0
        for got, want in zip(_moments(got_st), _moments(want_st)):
            assert got.layout == want.layout and got.shape == want.shape
            assert got.codes.shape == want.codes.shape
            assert got.scales.shape == want.scales.shape
            # equal on the chip (``tools/q8_update_bench.py`` compares
            # there); the CPU's compiler is free to round ``b1 m + (1 - b1)
            # g`` once in one of the two programs and twice in the other,
            # and a last bit of one step's scale is in the next step's
            np.testing.assert_allclose(got.scales, want.scales, rtol=1e-6)
            moved = np.abs(
                np.asarray(got.codes, np.int32) - np.asarray(want.codes)
            )
            assert moved.max(initial=0) <= 1
            ties += int(moved.sum())
            elements += moved.size
        assert ties <= 1e-4 * elements, (i, ties, elements)
        for a, b in zip(jax.tree.leaves(got_p), jax.tree.leaves(want_p)):
            assert a.dtype == b.dtype
            size = float(jnp.abs(b).max())
            off = np.abs(np.asarray(a) - np.asarray(b)) > 1e-6 * (
                size + np.abs(np.asarray(b))
            )
            # a bfloat16 gradient's delta is rounded to bfloat16: where the
            # float32 delta stands at a tie, its last bit decides 1/256 of it
            assert off.mean() <= (1e-4 if g[next(iter(g))].dtype != a.dtype
                                  else 0.0), (i, int(off.sum()))
            np.testing.assert_allclose(a, b, rtol=0, atol=1e-3 * size)
    return got_p, got_st


# [16, 256]: one row of tiles; [3, 16, 384]: three lane tiles a row (an odd
# count, like 2688's 21) under a leading dimension; [2, 4, 24, 128]: two
# leading dimensions, three rows of one tile
SHAPES = [(16, 256), (3, 16, 384), (2, 4, 24, 128)]
CASES = list(itertools.product(
    SHAPES, ["float32", "bfloat16"], [True, False], [0.0, 0.01], [1.0, 0.5]
))


@pytest.mark.parametrize(
    "shape, grad, classic_eps, decay, retune", CASES,
    ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v),
)
def test_kernel_is_update_then_apply(
    shape, grad, classic_eps, decay, retune, on_tpu
):
    eps = {"eps": 1e-8} if classic_eps else {"eps": 0.0, "eps_root": 1e-12}
    tx = q8.adamw_8bit(
        1e-2, weight_decay=decay, min_quantized_size=1024,
        **eps,
    )
    params = {"w": 0.05 * _leaf(shape)}
    before = trace_counts.snapshot()
    _held_to_the_statement(
        tx, params,
        lambda i: {"w": ((0.1 + i) * _leaf(shape, seed=i + 1)).astype(grad)},
        None if retune == 1.0 else retune,
    )
    # traced once: both moments of the leaf
    assert trace_counts.since(before)["opt_q8_kernel_elems"] == (
        2 * math.prod(shape)
    )


@pytest.mark.parametrize(
    "shape, tiles, strip", [
        # blocks of tiles that hang over the leaf along both blocked
        # dimensions, and a last strip that starts early
        ((3, 40, 640), 8, 4),
        # the blocks of a row along the scales' lanes (wider than tall)
        ((24, 128 * 130), 64, 16),
        # a leaf of many small matrices: the leading dimension a lane
        ((144, 16, 128), 256, 16),
        # whole strips along the leaf's rows: the data read as rows (the
        # codes 32 rows a register), a last block of 8 rows of tiles of
        # 128 and a last block one quantization block of two
        ((1088, 384), 256, 16),
        ((2, 256, 256), 64, 16),
    ], ids=str,
)
def test_kernel_at_the_edges_of_its_blocks(
    shape, tiles, strip, on_tpu, monkeypatch
):
    monkeypatch.setattr(q8, "_STEP_TILES", tiles)
    monkeypatch.setattr(q8, "_STRIP", strip)
    q8._q8_adam_step.clear_cache()
    tx = q8.adamw_8bit(
        1e-2, weight_decay=0.1, min_quantized_size=1024
    )
    params = {"w": 0.05 * _leaf(shape)}
    grad = jnp.bfloat16 if len(shape) == 3 else jnp.float32
    try:
        _held_to_the_statement(
            tx, params,
            lambda i: {
                "w": ((0.1 + i) * _leaf(shape, seed=i + 1)).astype(grad)
            }, 0.7,
        )
    finally:
        q8._q8_adam_step.clear_cache()


def test_blocking_is_one_rule_for_every_width():
    """The lane dimension is the one that pads least to whole lane rows
    (the later of two alike), as the chip lays the scales out; a program
    is ``_STEP_TILES`` tiles whatever the leaf's width."""
    # [64, 2048, 1024]: rows of tiles along the lanes, two blocks wide
    assert q8._step_blocking((64, 256, 8)) == (1, 2, 128, 2)
    # 2688 and 3712 wide: the same strip and the same block
    assert q8._step_blocking((336, 29)) == (0, 1, 128, 2)
    assert q8._step_blocking((464, 21)) == (0, 1, 128, 2)
    # [2688, 16384]: 128 blocks a row pad nothing, 336 rows of tiles do
    assert q8._step_blocking((336, 128)) == (1, 0, 128, 2)
    # [2048, 16, 128]: the leading dimension along the lanes
    assert q8._step_blocking((2048, 2, 1)) == (0, 1, 128, 2)
    assert q8._step_blocking((16, 16, 16))[0] == 2
    # a small leaf is one program
    assert q8._step_blocking((2, 2)) == (1, 0, 2, 2)


def test_kernel_writes_parameter_and_moments_in_place(on_tpu):
    """One Pallas call a leaf, its parameter, codes and scales aliased
    onto its results: nothing new is held."""
    tx = q8.adamw_8bit(1e-3, weight_decay=0.1, min_quantized_size=1024)
    params = {"a": _leaf((64, 256)), "b": _leaf((2, 32, 128))}
    calls = _calls(
        lambda g, st, p: tx.update_and_apply(g, st, p, scale=0.5),
        params, tx.init(params), params,
    )
    assert len(calls) == 2
    for eqn in calls:
        assert eqn.params["name"] == "q8_adam_step"
        # inputs: scalars, g, p, mu codes, mu scales, nu codes, nu scales
        assert tuple(eqn.params["input_output_aliases"]) == (
            (2, 0), (3, 1), (4, 2), (5, 3), (6, 4)
        )


class _OneDevice:
    fsdp = tp = ep = sp = pp = 1


def _mixed_tree():
    return {
        "tiles": _leaf((32, 256)),
        "blocks_odd_width": _leaf((32, 200), seed=1),
        "blocks_1d": _leaf((4096,), seed=2),
        "fp32_small": _leaf((8, 16), seed=3),
    }


def _bit_identical(a, b):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("bits", [8, 4])
def test_other_leaves_are_left_bit_identical(bits, on_tpu):
    """A ``BLOCKS`` leaf (odd width, 1-D), an fp32-moment leaf and a
    ``bits=4`` state keep the statement inside ``update_and_apply``, bit
    for bit what ``update`` + ``apply_updates`` make of them, and the
    counter counts what took the kernel."""
    tx = q8.adamw_8bit(
        1e-2, weight_decay=0.1, min_quantized_size=1024, bits=bits
    )
    params = _mixed_tree()
    if bits == 8:
        del params["tiles"]
    state = _random_state(tx, params)
    if bits == 8:
        assert state.mu["blocks_odd_width"].layout == q8.BLOCKS
        assert state.mu["blocks_1d"].layout == q8.BLOCKS
    else:
        assert isinstance(state.mu["tiles"], q8.Quantized4)
        assert state.nu["tiles"].layout == q8.BLOCKS
    assert not isinstance(state.mu["fp32_small"], q8.Quantized8)
    before = trace_counts.snapshot()
    assert not _calls(tx.update_and_apply, params, state, params)
    assert not trace_counts.since(before)["opt_q8_kernel_elems"]

    def statement(g, st, p):
        u, st = tx.update(g, st, p)
        return optax.apply_updates(p, jax.tree.map(lambda x: 0.9 * x, u)), st

    fused = jax.jit(lambda g, st, p: tx.update_and_apply(g, st, p, scale=0.9))
    want_p, want_st = got_p, got_st = params, state
    for i in range(3):
        g = jax.tree.map(lambda p: (1.0 + i) * jnp.cos(p), params)
        want_p, want_st = jax.jit(statement)(g, want_st, want_p)
        got_p, got_st = fused(g, got_st, got_p)
        _bit_identical((got_p, got_st), (want_p, want_st))
    # ... and ``update`` itself, beside a tiles leaf that takes the kernel
    # in the other entry: the same numbers for every leaf but that one
    params = _mixed_tree()
    state = _random_state(tx, params)
    g = jax.tree.map(jnp.sin, params)
    tiles, blocks = q8.int8_moments_on(state, _OneDevice())
    before = trace_counts.snapshot()
    got_p, got_st = fused(g, state, params)
    assert trace_counts.since(before)["opt_q8_kernel_elems"] == (
        tiles if bits == 8 else 0
    )
    want_p, want_st = jax.jit(statement)(g, state, params)
    for name in params:
        if name != "tiles" or bits == 4:
            _bit_identical(
                (got_p[name], got_st.mu[name], got_st.nu[name]),
                (want_p[name], want_st.mu[name], want_st.nu[name]),
            )


def test_another_backend_takes_the_statement():
    """Off the TPU no leaf takes the kernel: ``update_and_apply`` is
    ``update`` + ``apply_updates`` leaf by leaf, and counts nothing."""
    assert jax.default_backend() != "tpu"
    tx = q8.adamw_8bit(1e-2, weight_decay=0.1, min_quantized_size=1024)
    params = _mixed_tree()
    before = trace_counts.snapshot()
    assert not _calls(tx.update_and_apply, params, tx.init(params), params)
    assert not trace_counts.since(before)["opt_q8_kernel_elems"]
    _held_to_the_statement(
        tx, params, lambda i: jax.tree.map(jnp.sin, params), None
    )


@pytest.mark.parametrize(
    "shape, bits, want",
    [
        ((256, 256), 8, q8.TILES), ((4, 64, 256), 8, q8.TILES),
        ((48, 100), 8, q8.BLOCKS), ((8192,), 8, q8.BLOCKS),
        ((256, 256), 4, q8.BLOCKS),
    ],
    ids=str,
)
def test_layout_follows_the_leaf_on_a_tpu_too(shape, bits, want, on_tpu):
    """``adamw_8bit()`` as a caller writes it: on a TPU as anywhere the
    moments of a leaf lie where ``_layout_for`` says, from its shape alone
    (whole (8, 128) tiles in ``TILES``: no backend makes the leaf's layout
    another); the 4-bit state, whose update reads rows, keeps ``BLOCKS``."""
    state = q8.adamw_8bit(bits=bits).init({"w": jnp.zeros(shape)})
    assert state.nu["w"].layout == want
    if bits == 8:
        assert state.mu["w"].layout == want == q8._layout_for(shape)
    else:
        assert isinstance(state.mu["w"], q8.Quantized4)


@pytest.mark.parametrize(
    "shape, dtype, tpu, want",
    [
        ((32, 256), "float32", True, True),
        ((32, 256), "bfloat16", True, False),  # the kernel writes float32
        ((32, 200), "float32", True, False),  # BLOCKS
        ((32, 256), "float32", False, False),
    ],
    ids=str,
)
def test_the_rule_reads_the_leaf_and_the_backend(
    shape, dtype, tpu, want, monkeypatch
):
    monkeypatch.setattr(q8, "_on_tpu", lambda: tpu)
    p = jnp.zeros(shape, dtype)
    m = q8.adamw_8bit(min_quantized_size=1024).init({"w": p}).mu["w"]
    assert q8.takes_kernel(p, m) is want


def test_a_4bit_state_never_takes_the_kernel(on_tpu):
    """``bits=4`` on a TPU, a whole-tile float32 leaf: the rule answers no
    for the nibble-packed first moment, and ``update_and_apply`` is the
    statement, bit for bit."""
    tx = q8.adamw_4bit(learning_rate=1e-2, min_quantized_size=1024)
    params = {"w": _leaf((32, 256))}
    state = _random_state(tx, params)
    assert not q8.takes_kernel(params["w"], state.mu["w"])
    assert not q8.takes_kernel(params["w"], state.nu["w"])  # BLOCKS
    assert not _calls(tx.update_and_apply, params, state, params)
    g = jax.tree.map(jnp.sin, params)
    u, want_st = jax.jit(tx.update)(g, state, params)
    got_p, got_st = jax.jit(tx.update_and_apply)(g, state, params)
    _bit_identical((got_p, got_st), (optax.apply_updates(params, u), want_st))


@pytest.mark.parametrize("mesh", [{"dp": 8}, {"fsdp": 8}], ids=str)
def test_moments_are_counted_by_layout_on_any_mesh(mesh):
    """``int8_moments_on``: both moments of every ``Quantized8`` leaf by
    its layout tag, whole leaves whatever the mesh shards; fp32 moments
    count for nothing."""
    from dlrover_tpu.parallel.mesh import MeshConfig

    params = _mixed_tree()
    state = q8.adamw_8bit(min_quantized_size=1024).init(params)
    assert q8.int8_moments_on(state, MeshConfig(**mesh)) == (
        2 * params["tiles"].size,
        2 * (params["blocks_odd_width"].size + params["blocks_1d"].size),
    )
    fp32 = optax.adamw(1e-3).init(params)
    assert q8.int8_moments_on(fp32, MeshConfig(**mesh)) == (0, 0)


def test_state_at_rest_is_the_parents_and_round_trips(on_tpu):
    """The kernel hands back codes ``[..., R/8, C/128, 8, 128]`` and scales
    ``[..., R/8, C/128, 8]``, the layout the statement keeps: a checkpoint
    written from the statement's state restores, and the kernel goes on
    from it as the statement does (and the other way round)."""
    from dlrover_tpu.ckpt.sharding import host_shard_records, restore_state

    tx = q8.adamw_8bit(1e-2, weight_decay=0.1, min_quantized_size=1024)
    shape = (3, 16, 384)
    params = {"w": 0.05 * _leaf(shape), "b": _leaf((256,))}
    fused = jax.jit(tx.update_and_apply)

    def statement(g, st, p):
        u, st = tx.update(g, st, p)
        return optax.apply_updates(p, u), st

    g = jax.tree.map(jnp.cos, params)
    by_statement = jax.jit(statement)(g, tx.init(params), params)
    by_kernel = fused(g, tx.init(params), params)
    for written, goes_on in (
        (by_statement, fused), (by_kernel, jax.jit(statement))
    ):
        _, state = written
        assert state.mu["w"].codes.shape == (3, 2, 3, 8, 128)
        assert state.mu["w"].scales.shape == (3, 2, 3, 8)
        records = {r.path: r for r in host_shard_records(written)}
        restored = restore_state(written, lambda p: [records[p]])
        _bit_identical(restored, written)
        p, st = restored
        got = goes_on(g, st, p)
        want = goes_on(g, written[1], written[0])
        _bit_identical(got, want)
    # both ways wrote the same state but for rounding ties
    for a, b in zip(jax.tree.leaves(by_kernel), jax.tree.leaves(by_statement)):
        if a.dtype == jnp.int8:
            assert np.abs(np.asarray(a, np.int32) - np.asarray(b)).max() <= 1
        else:
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("schedule, warmup", [("cosine", 3), ("constant", 0)])
@pytest.mark.parametrize("retune", [1.0, 0.25])
def test_built_optimizer_reads_its_knobs_as_update_does(
    retune, schedule, warmup, on_tpu
):
    """``build_optimizer("adamw_8bit")``'s ``update_and_apply`` under the
    same ``inject_hyperparams`` state as ``update``: the learning rate (a
    schedule at its own count, or the number a constant rate is kept as)
    and a ``retune_scale`` the trainer wrote, the whole update scaled,
    decay included; the states it hands back are ``update``'s."""
    tx = build_optimizer(
        "adamw_8bit", lr=1e-2, schedule=schedule, warmup_steps=warmup,
        total_steps=50, weight_decay=0.1, min_quantized_size=1024,
    )
    assert isinstance(tx, q8.InPlaceTransformation)
    init, update = tx  # still the pair optax unpacks
    params = _mixed_tree()

    def retuned(state):
        hyper = dict(state.hyperparams)
        hyper["retune_scale"] = jnp.asarray(retune, jnp.float32)
        return state._replace(hyperparams=hyper)

    want_p, want_st = params, retuned(init(params))
    got_p, got_st = params, retuned(init(params))
    statement = jax.jit(update)
    fused = jax.jit(tx.update_and_apply)
    assert len(_calls(tx.update_and_apply, params, got_st, params)) == 1
    for i in range(STEPS):
        g = jax.tree.map(lambda p: (1.0 + i) * jnp.cos(p + i), params)
        u, want_st = statement(g, want_st, want_p)
        want_p = optax.apply_updates(want_p, u)
        got_p, got_st = fused(g, got_st, got_p)
        assert (
            jax.tree.structure(got_st) == jax.tree.structure(want_st)
        )
        assert int(got_st.count) == int(want_st.count) == i + 1
        for name in ("learning_rate", "retune_scale"):
            np.testing.assert_array_equal(
                got_st.hyperparams[name], want_st.hyperparams[name]
            )
        for a, b in zip(jax.tree.leaves(got_st), jax.tree.leaves(want_st)):
            if a.dtype == jnp.int8:
                assert np.abs(
                    np.asarray(a, np.int32) - np.asarray(b)
                ).max() <= 1
            else:
                np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-30)
        for a, b in zip(jax.tree.leaves(got_p), jax.tree.leaves(want_p)):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
    assert float(jnp.abs(got_p["tiles"] - params["tiles"]).max()) > 0


@pytest.mark.parametrize("name", ["adamw", "adam", "agd", "sgd"])
def test_other_optimizers_have_no_second_entry(name):
    tx = build_optimizer(name, lr=1e-3)
    assert not hasattr(tx, "update_and_apply")
    assert q8.in_place_entry(tx, devices=1, donate=True) is None


def test_retired_key_is_dropped_where_false():
    """The benchmark's configurations still write ``"use_pallas": false``:
    ``build_optimizer`` builds what it builds without the key."""
    params = _mixed_tree()
    with_key, without = (
        build_optimizer(
            "adamw_8bit", lr=1e-2, min_quantized_size=1024, **kwargs
        ).init(params)
        for kwargs in ({"use_pallas": False}, {})
    )
    _bit_identical(with_key, without)  # the layout tags too: aux data


@pytest.mark.parametrize(
    "name, kwargs, message",
    [
        ("adamw_8bit", {"use_pallas": True}, "use_pallas is retired"),
        ("adamw_8bit_flat", {}, "unknown optimizer 'adamw_8bit_flat'"),
    ],
    ids=["use_pallas", "adamw_8bit_flat"],
)
def test_what_went_is_refused_by_name(name, kwargs, message):
    with pytest.raises(ValueError, match=message):
        build_optimizer(name, lr=1e-3, **kwargs)


def _tiny_step(tx, mesh_devices=1, **kwargs):
    from dlrover_tpu.models.config import TransformerConfig
    from dlrover_tpu.models.train import TrainState, build_train_step
    from dlrover_tpu.models.transformer import init_params
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh

    cfg = TransformerConfig(
        vocab_size=256, model_dim=128, num_layers=1, num_heads=2,
        mlp_dim=256, max_seq_len=32,
    )
    mesh = build_mesh(
        MeshConfig(fsdp=mesh_devices), jax.devices()[:mesh_devices]
    )

    def state():
        params = init_params(jax.random.PRNGKey(0), cfg)
        return TrainState(
            step=jnp.zeros((), jnp.int32), params=params,
            opt_state=tx.init(params),
        )

    return build_train_step(cfg, mesh, tx, **kwargs), state


def test_train_step_calls_the_entry_where_it_is(on_tpu):
    """``build_train_step`` with a transformation that has
    ``update_and_apply``: the whole-tile leaves' steps are Pallas calls
    inside the step program and the counter says how many elements they
    hold; the same step with the entry taken off is ``update`` +
    ``apply_updates``, and both train the same model."""
    def make():
        return build_optimizer(
            "adamw_8bit", lr=1e-2, weight_decay=0.1, min_quantized_size=4096
        )

    tx = make()
    step, state = _tiny_step(tx)
    x = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 256)
    before = trace_counts.snapshot()
    got, metrics = step(state(), x, x)
    tiles, _ = q8.int8_moments_on(state().opt_state, _OneDevice())
    assert tiles
    assert trace_counts.since(before)["opt_q8_kernel_elems"] == tiles
    plain = optax.GradientTransformation(*make())
    want, _ = _tiny_step(plain)[0](state(), x, x)
    assert np.isfinite(float(metrics["loss"]))
    for a, b in zip(
        jax.tree.leaves(got.params), jax.tree.leaves(want.params)
    ):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize(
    "kwargs",
    [{"offload_opt_state": True}, {"donate": False}, {"mesh_devices": 2}],
    ids=str,
)
def test_train_step_keeps_its_two_lines(kwargs, on_tpu):
    """An offloaded state keeps ``update`` + ``apply_updates``, and so do
    the non-donating twin (in place would mean a copy of every leaf
    first) and a mesh of several devices (GSPMD refuses to partition a
    Mosaic call): the step holds no Pallas call of the update."""
    tx = build_optimizer("adamw_8bit", lr=1e-2, min_quantized_size=4096)
    step, state = _tiny_step(tx, **kwargs)
    x = jnp.zeros((2, 32), jnp.int32)
    before = trace_counts.snapshot()
    step.lower(jax.eval_shape(state), x, x)
    assert not trace_counts.since(before)["opt_q8_kernel_elems"]


def test_adamw_train_step_lowers_to_the_text_it_lowered_to():
    """fp32 ``adamw`` has no second entry: the step is the two lines, and
    its text that of a transformation that is the bare optax pair."""
    tx = build_optimizer("adamw", lr=1e-3, weight_decay=0.1)
    x = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    texts = []
    for t in (tx, optax.GradientTransformation(tx.init, tx.update)):
        step, state = _tiny_step(t)
        texts.append(step.lower(jax.eval_shape(state), x, x).as_text())
    assert texts[0] == texts[1]
    assert "pallas" not in texts[0]


def test_sites_of_one_shape_share_one_lowered_function(monkeypatch):
    """A toy MoE step lowered for ``tpu`` from the CPU: one call of
    ``_q8_adam_step`` a ``TILES`` leaf, and as many functions of that name
    as the leaves have distinct (shape, gradient dtype) pairs, each
    holding the step's one Mosaic call: what differs between two leaves of
    one shape, or two steps, rides in the SMEM scalars."""
    import collections
    import re

    from dlrover_tpu.models.config import tiny
    from dlrover_tpu.models.train import TrainState, build_train_step
    from dlrover_tpu.models.transformer import init_params
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh

    monkeypatch.setattr(q8, "_on_tpu", lambda: True)
    monkeypatch.setattr(q8, "_interpret", lambda: False)
    cfg = tiny(
        num_layers=2, model_dim=128, mlp_dim=256, num_heads=2,
        num_kv_heads=2, num_experts=4, moe_top_k=2, vocab_size=512,
    )
    tx = build_optimizer(
        "adamw_8bit", lr=1e-3, weight_decay=0.01, min_quantized_size=4096,
    )
    mesh = build_mesh(MeshConfig(), jax.devices()[:1])

    def state():
        params = init_params(jax.random.PRNGKey(0), cfg)
        return TrainState(
            step=jnp.zeros((), jnp.int32), params=params,
            opt_state=tx.init(params),
        )

    abstract = jax.eval_shape(state)
    x = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    before = trace_counts.snapshot()
    text = build_train_step(cfg, mesh, tx).trace(abstract, x, x).lower(
        lowering_platforms=("tpu",)
    ).as_text()
    mu = _moments(abstract.opt_state.inner_state[0].mu)
    tiles = [q.shape for q in mu if q.layout == q8.TILES]
    assert len(tiles) > len(set(tiles)) > 1  # the toy has leaves alike
    functions = re.findall(r"func\.func private @(_q8_adam_step\w*)\(", text)
    calls = collections.Counter(
        re.findall(r"call @(_q8_adam_step\w*)\(", text)
    )
    # parameters are float32, and so is every gradient
    assert len(functions) == len(set(tiles))
    assert sorted(calls.values()) == sorted(
        collections.Counter(tiles).values()
    )
    assert text.count("tpu_custom_call") == len(functions)
    assert "q8_adam_step" in text
    assert trace_counts.since(before)["opt_q8_kernel_elems"] == (
        2 * sum(math.prod(shape) for shape in tiles)
    )


# -- a second moment that is not zero keeps a code ---------------------------


def test_a_second_moment_never_rounds_to_zero():
    """One element 1000 times its block's others: theirs would round to
    code 0 (sqrt(v / max) 127 < 0.5) and read the least code, 1; a block
    of zeros still reads zeros (its scale is 0), and no other code moves."""
    v = jnp.full((3, q8.BLOCK), 1e-6).at[0, 3].set(1.0).at[0, 7].set(0.0)
    v = v.at[2].set(0.0)
    codes, scale = q8._quant_block_math(v, signed=False)
    assert int(codes[0, 3]) == 127
    assert set(np.asarray(codes[0]).tolist()) == {1, 127}
    assert set(np.asarray(codes[1]).tolist()) == {127}
    back = q8._dequant_block_math(codes, scale)
    assert float(back[0, 0]) == pytest.approx(1.0 / 127**2)
    assert not np.asarray(back[2]).any()
    # a first moment rounds to the nearest code as before, zero among them
    m, _ = q8._quant_block_math(v.at[0, 3].set(-1.0), signed=True)
    assert set(np.asarray(m[0]).tolist()) == {-127, 0}


@pytest.mark.parametrize("name", ["adamw_8bit", "adamw"])
def test_a_spike_beside_small_gradients_moves_no_element_far(name):
    """A block whose one element takes a gradient 1000 times its
    neighbours' (a rare token's column of the head under a loss weight of
    1 / t), and steps after it in which the neighbours' gradient is all
    but nothing (the token is absent): their updates stay within Adam's
    few learning rates, where a second moment rounded to 0 beside a live
    first moment gave 1,770 of them."""
    lr = 1e-3
    kwargs = {"min_quantized_size": 0} if name == "adamw_8bit" else {}
    tx = build_optimizer(name, lr=lr, weight_decay=0.0, **kwargs)
    params = {"w": jnp.zeros((8, 256), jnp.float32)}
    state = tx.init(params)
    small = 1e-3 * jax.random.normal(jax.random.PRNGKey(0), (8, 256))
    spike = small.at[:, 5].set(1.0)
    worst = 0.0
    for g in (small, small, spike, 1e-3 * small, 1e-3 * small):
        updates, state = tx.update({"w": g}, state, params)
        worst = max(worst, float(jnp.max(jnp.abs(updates["w"]))))
    assert worst < 2 * lr
