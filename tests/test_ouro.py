"""The ``ouro`` family (a looped language model: one stack of sandwich-
normed attention blocks applied ``ut_steps`` times over shared weights, an
exit through the one head and a gate after every pass) at a small size on
the CPU: the program held to ``benchmark/references/ouro.py`` (loss and
every gradient leaf); a shared leaf's gradient the sum over the passes;
the stopping distribution and its two saturated limits; ``ut_steps = 1``
the plain model; ``remat``; the unrolled loop against a scan; a lower
precision refused; the exits' own backward rule (``exits_nll``) against
``jax.grad`` of the plain form; the counters of a whole step; the step's
one logits array and its three products an exit; and what refuses a looped
model."""

import importlib
import importlib.util
import os
import re
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.accel.profiler import PipelineStats, profile_model
from dlrover_tpu.common import trace_counts
from dlrover_tpu.models import transformer
from dlrover_tpu.models.config import TransformerConfig
from dlrover_tpu.models.train import (
    TrainState,
    build_train_step,
    fold_exit_report,
)
from dlrover_tpu.models.transformer import (
    forward,
    forward_step,
    forward_step_ragged,
    init_kv_cache,
    init_params,
    logical_axes,
    loss_fn,
)
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.pipeline import _check_pipeline_cfg
from dlrover_tpu.trainer.elastic.trainer import build_optimizer
from trace_counted import EDGE, KEPT, LANES, STREAM, UT, added

# `dlrover_tpu.ops.flash_attention` the attribute is the function
fa = importlib.import_module("dlrover_tpu.ops.flash_attention")

RTOL = 1e-5
GRAD_RTOL = 2e-4  # a gradient sums more terms in another order
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BLOCKS, PASSES, T = 3, 4, 64


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmark", "references", "ouro.py")
    spec = importlib.util.spec_from_file_location("ouro_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg(**over):
    cfg = TransformerConfig(
        vocab_size=256, num_layers=2 * BLOCKS, layer_pattern="*-" * BLOCKS,
        mixer_out_norm=True, model_dim=64, num_heads=4, num_kv_heads=4,
        attn_head_dim=16, dense_mlp_dim=96, rope=True, rope_theta=1e6,
        rmsnorm=True, norm_eps=1e-6, swiglu=True, tie_embeddings=False,
        max_seq_len=T, ut_steps=PASSES, ut_entropy_weight=0.05,
        dtype="float32", param_dtype="float32",
    )
    return replace(cfg, **over)


def _weights(cfg, seed=1):
    """Seeded weights with every norm and the gate's bias off its initial
    value."""
    params = init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def jitter(leaf):
        if leaf.size > 64:
            return leaf
        return leaf + 0.1 * jax.random.normal(next(keys), leaf.shape)

    return jax.tree_util.tree_map(jitter, params)


def _batch(cfg, seed=0, rows=2):
    rng = np.random.default_rng(seed)
    data = rng.integers(0, cfg.vocab_size, (rows, T + 1)).astype(np.int32)
    return data[:, :-1], data[:, 1:]


def _rel(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _grads(fn, params):
    return jax.jit(jax.value_and_grad(fn))(params)


def _hold_leaves(g_got, g_want, rtol=GRAD_RTOL):
    got = jax.tree_util.tree_leaves_with_path(g_got)
    want = jax.tree_util.tree_leaves(g_want)
    assert len(got) == len(want)
    names = []
    for (path, a), b in zip(got, want):
        name = jax.tree_util.keystr(path)
        names.append(name)
        assert np.isfinite(np.asarray(a)).all(), name
        assert float(jnp.max(jnp.abs(b))) > 0, name
        assert _rel(a, b) <= rtol, name
    return names


# -- the whole model against the reference --------------------------------


def test_loss_and_every_gradient_leaf_match_the_reference(ref):
    cfg = _cfg()
    params = _weights(cfg)
    x, y = _batch(cfg)
    got, g_got = _grads(lambda p: loss_fn(p, x, y, cfg, None), params)
    want, g_want = _grads(lambda p: ref.loss(p, x, y), params)
    assert abs(float(got) - float(want)) <= RTOL * abs(float(want))
    names = _hold_leaves(g_got, g_want)
    # the table, the head, the final norm, the gate's two; a block's four
    # norm weights, four attention and three feed-forward matrices
    assert len(names) == 5 + BLOCKS * (4 + 4 + 3)
    assert {"['exit_gate']['w']", "['exit_gate']['b']"} <= set(names)
    assert sum("norm']['scale']" in n and "layers" in n for n in names) == (
        4 * BLOCKS
    )


def test_a_shared_leafs_gradient_is_the_sum_over_the_passes(ref):
    """The reference with a copy of the layers a pass: the program's
    gradient of a leaf is the sum of the reference's gradients of its
    copies, and no pass's alone."""
    cfg = _cfg()
    params = _weights(cfg)
    x, y = _batch(cfg)
    g_got = jax.jit(jax.grad(lambda p: loss_fn(p, x, y, cfg, None)))(params)
    by_pass = jax.jit(jax.grad(
        lambda copies: ref.loss(params, x, y, layers_by_pass=copies)
    ))([params["layers"]] * PASSES)
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *by_pass)
    for (path, a), b, first in zip(
        jax.tree_util.tree_leaves_with_path(g_got["layers"]),
        jax.tree_util.tree_leaves(summed),
        jax.tree_util.tree_leaves(by_pass[0]),
    ):
        name = jax.tree_util.keystr(path)
        assert _rel(a, b) <= GRAD_RTOL, name
        assert _rel(a, first) > 1e-2, name


# -- the stopping distribution ---------------------------------------------


def _aux(cfg, params, x, y):
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, x, y, cfg, None, return_aux=True), has_aux=True
    ))(params)
    return float(loss), aux, grads


def test_the_stopping_distribution_sums_to_one(ref):
    g = 8.0 * jax.random.normal(jax.random.PRNGKey(5), (PASSES, 2, T))
    g = g.at[:, 0, :4].set(jnp.asarray([[40.0], [-40.0], [0.0], [90.0]]))
    for log_p in (transformer.ut_stopping(g), jnp.stack(
        ref.stopping(list(g))
    )):
        total = jnp.sum(jnp.exp(log_p), 0)
        assert float(jnp.max(jnp.abs(total - 1.0))) <= 1e-6
    assert _rel(transformer.ut_stopping(g), jnp.stack(
        ref.stopping(list(g))
    )) <= 1e-6


@pytest.mark.parametrize("bias,exit_at", [(30.0, 0), (-30.0, PASSES - 1)])
def test_a_saturated_gate_leaves_one_exits_loss(bias, exit_at):
    """``b_gate = +30``: every token stops after the first pass and the
    loss is that exit's mean NLL; ``-30``: the last's. The entropy is 0
    in both, and neither the loss nor any gradient is a ``nan``."""
    cfg = _cfg()
    params = _weights(cfg)
    params["exit_gate"]["b"] = jnp.full((1,), bias)
    x, y = _batch(cfg)
    loss, aux, grads = _aux(cfg, params, x, y)
    want = float(aux["ut_exit_nll"][exit_at])
    assert abs(loss - want) <= 1e-6 * want
    assert 0.0 <= float(aux["ut_entropy"]) <= 1e-6
    assert abs(float(aux["ut_exit_step"]) - (exit_at + 1)) <= 1e-6
    for leaf in jax.tree_util.tree_leaves(grads):
        assert np.isfinite(np.asarray(leaf)).all()


def test_the_reported_exits_are_the_losss_terms(ref):
    cfg = _cfg()
    params = _weights(cfg)
    x, y = _batch(cfg)
    loss, aux, _ = _aux(cfg, params, x, y)
    f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        exits = ref.passes_of(f32, x, y, [f32["layers"]] * PASSES)
    log_p = jnp.stack(ref.stopping([g for _, g in exits]))
    p = jnp.exp(log_p)
    nll = jnp.stack([n for n, _ in exits])
    assert _rel(aux["ut_exit_nll"], jnp.mean(nll, (1, 2))) <= RTOL
    entropy = float(jnp.mean(-jnp.sum(p * log_p, 0)))
    assert abs(float(aux["ut_entropy"]) - entropy) <= 1e-5
    assert 0.0 < entropy <= np.log(PASSES)
    at = jnp.arange(1, PASSES + 1)[:, None, None]
    assert abs(
        float(aux["ut_exit_step"]) - float(jnp.mean(jnp.sum(at * p, 0)))
    ) <= 1e-5
    # and what the trainer folds of them
    stats = PipelineStats()
    said = fold_exit_report({k: np.asarray(v) for k, v in aux.items()}, stats)
    assert stats.ut_reports == 1 and "ut_exit_nll=[" in said
    assert stats.ut_entropy_sum == pytest.approx(entropy, abs=1e-5)
    assert fold_exit_report({"loss": 1.0}, stats) == ""
    assert stats.ut_reports == 1


def test_row_weights_weigh_a_rows_bracket():
    cfg = _cfg()
    params = _weights(cfg)
    x, y = _batch(cfg, rows=3)
    real = float(jax.jit(
        lambda p: loss_fn(p, x[:2], y[:2], cfg, None)
    )(params))
    padded = float(jax.jit(lambda p: loss_fn(
        p, x, y, cfg, None, row_weights=jnp.asarray([1.5, 1.5, 0.0])
    ))(params))
    assert abs(padded - real) <= RTOL * real


# -- the exits' own backward rule ---------------------------------------------


def _plain_exits(passes, w_head, targets, a, cfg):
    """``sum(a * nll)`` through ``_head_logits`` and ``_nll_each``, for
    ``jax.grad`` to differentiate as it finds it."""
    params = (
        {"embed": {"tokens": w_head}} if cfg.tie_embeddings
        else {"lm_head": w_head}
    )
    nll = jnp.stack([
        transformer._nll_each(transformer._head_logits(params, h, cfg),
                              targets)
        for h in passes
    ])
    return jnp.sum(a * nll), nll


@pytest.mark.parametrize("weighed", [False, True], ids=["even", "rows"])
@pytest.mark.parametrize("mult", [1.0, 0.5])
@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_the_exits_rule_matches_the_plain_forms_gradients(
    tied, mult, weighed
):
    """``exits_nll``'s value, its reported NLLs and its gradients to the
    passes, the head (the table [V, D] where tied) and the weights ``a``
    are ``jax.grad``'s of the plain form to float32 rounding, under a
    cotangent that is not 1 and with ``a`` read a second time beside it."""
    cfg = _cfg(tie_embeddings=tied, mup_output_mult=mult)
    rows, width, vocab = 3, cfg.model_dim, cfg.vocab_size
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    passes = jax.random.normal(keys[0], (PASSES, rows, T, width))
    shape = (vocab, width) if tied else (width, vocab)
    w_head = 0.2 * jax.random.normal(keys[1], shape)
    targets = jax.random.randint(keys[2], (rows, T), 0, vocab)
    a = jax.nn.softmax(jax.random.normal(keys[3], (PASSES, rows, T)), 0)
    if weighed:
        a = a * jnp.asarray([1.5, 1.5, 0.0])[:, None]
    a = a / (rows * T)

    def scaled(exits):
        def fn(passes, w_head, a):
            total, nll = exits(passes, w_head, targets, a, cfg)
            return 3.0 * total + jnp.sum(a * a), nll
        return jax.jit(jax.value_and_grad(fn, (0, 1, 2), has_aux=True))

    (got, nll_got), g_got = scaled(transformer.exits_nll)(passes, w_head, a)
    (want, nll_want), g_want = scaled(_plain_exits)(passes, w_head, a)
    assert abs(float(got) - float(want)) <= 1e-6 * abs(float(want))
    assert _rel(nll_got, nll_want) <= 1e-6
    for name, x, y in zip(("passes", "head", "a"), g_got, g_want):
        assert x.shape == y.shape and x.dtype == y.dtype, name
        assert _rel(x, y) <= 2e-6, name
    # no gradient asked: the primal, one exit at a time
    total, nll = jax.jit(
        lambda *args: transformer.exits_nll(*args, cfg)
    )(passes, w_head, targets, a)
    assert abs(3.0 * float(total) + float(jnp.sum(a * a)) - float(want)) <= (
        1e-6 * abs(float(want))
    )
    assert _rel(nll, nll_want) <= 1e-6


def test_the_reported_nll_carries_no_gradient():
    """The second output is a report: a loss that reads it alone has no
    gradient, and the rule's backward pass is given none for it."""
    cfg = _cfg()
    passes = jax.random.normal(jax.random.PRNGKey(1), (PASSES, 1, T, 64))
    w_head = 0.2 * jax.random.normal(jax.random.PRNGKey(2), (64, 256))
    targets = jnp.zeros((1, T), jnp.int32)
    a = jnp.full((PASSES, 1, T), 0.25 / T)
    grads = jax.grad(
        lambda h, w, a: jnp.sum(
            transformer.exits_nll(h, w, targets, a, cfg)[1]
        ), (0, 1, 2),
    )(passes, w_head, a)
    for g in grads:
        assert float(jnp.max(jnp.abs(g))) == 0.0


# -- one pass is the plain model ---------------------------------------------


def test_one_pass_is_the_plain_model_bit_for_bit():
    """``ut_steps = 1`` is the pattern model as it was: no gate leaf, and
    with the looped model's layer leaves its loss is the plain one's bit
    for bit; the looped model's first exit is that loss."""
    cfg = _cfg()
    plain = replace(cfg, ut_steps=1, ut_entropy_weight=0.0)
    params = _weights(cfg)
    once = {k: v for k, v in params.items() if k != "exit_gate"}
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), plain))
    assert jax.tree_util.tree_structure(shapes) == (
        jax.tree_util.tree_structure(once)
    )
    assert set(logical_axes(plain)) == set(once)
    assert set(logical_axes(cfg)) == set(params)
    x, y = _batch(cfg)
    got = jax.jit(lambda p: loss_fn(p, x, y, plain, None))(once)
    logits, _ = jax.jit(lambda p: forward(p, x, plain))(once)
    want = jax.jit(transformer.token_nll)(logits, y)
    assert float(got) == float(want)
    _, aux, _ = _aux(cfg, params, x, y)
    assert abs(float(aux["ut_exit_nll"][0]) - float(got)) <= RTOL * float(got)
    # a caller that wants logits or the trunk gets the last pass's
    passes, _ = jax.jit(
        lambda p: forward(p, x, cfg, return_passes=True)
    )(params)
    hidden, _ = jax.jit(lambda p: forward(p, x, cfg, return_hidden=True))(
        params
    )
    assert passes.shape == (PASSES, 2, T, 64)
    assert np.array_equal(np.asarray(hidden), np.asarray(passes[-1]))
    last, _ = jax.jit(lambda p: forward(p, x, cfg))(params)
    nll = float(jax.jit(transformer.token_nll)(last, y))
    assert abs(nll - float(aux["ut_exit_nll"][-1])) <= RTOL * nll


def test_remat_gives_the_same_loss_and_gradients():
    cfg = _cfg()
    params = _weights(cfg)
    x, y = _batch(cfg)
    a, ga = _grads(lambda p: loss_fn(p, x, y, cfg, None), params)
    b, gb = _grads(
        lambda p: loss_fn(p, x, y, replace(cfg, remat=True), None), params
    )
    assert abs(float(a) - float(b)) <= RTOL * abs(float(a))
    _hold_leaves(gb, ga)


def _scanned(one_pass, x, steps):
    """``ut_passes`` as a ``lax.scan`` over one pass: the form that is
    not committed (the compiled step keeps its stacked residuals twice)."""
    return jax.lax.scan(
        lambda s, _: (one_pass(s),) * 2, x, None, length=steps
    )[1]


@pytest.mark.parametrize("remat", [False, True])
def test_the_unrolled_loop_and_a_scan_agree(monkeypatch, remat):
    cfg = _cfg(remat=remat)
    params = _weights(cfg)
    x, y = _batch(cfg)
    a, ga = _grads(lambda p: loss_fn(p, x, y, cfg, None), params)
    monkeypatch.setattr(transformer, "ut_passes", _scanned)
    b, gb = _grads(lambda p: loss_fn(p, x, y, cfg, None), params)
    assert abs(float(a) - float(b)) <= 1e-6 * abs(float(a))
    _hold_leaves(ga, gb, rtol=2e-5)


# -- the tolerance refuses a lower precision ----------------------------------


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_bfloat16_matmuls_fail_the_toys_limits(ref, seed):
    """The reference with both operands of every matmul rounded to
    bfloat16, a precision below the float32 this toy states, is refused
    by the limit that holds the program (over 2 x 64 tokens it reads
    5e-5 to 3e-4 of the loss, the program under 2e-6)."""
    cfg = _cfg()
    params = _weights(cfg, seed)
    x, y = _batch(cfg, seed)

    def to(a):
        return a.astype(jnp.bfloat16).astype(jnp.float32)

    def rounded(p):
        keep = ref.matmul, ref.einsum
        ref.matmul = lambda a, b: keep[0](to(a), to(b))
        ref.einsum = lambda s, a, b: keep[1](s, to(a), to(b))
        try:
            return ref.loss(p, x, y)
        finally:
            ref.matmul, ref.einsum = keep

    want = float(jax.jit(lambda p: ref.loss(p, x, y))(params))
    got = float(jax.jit(lambda p: loss_fn(p, x, y, cfg, None))(params))
    low = float(jax.jit(rounded)(params))
    assert abs(got - want) <= RTOL * abs(want)
    assert abs(got - want) <= 0.2 * RTOL * abs(want)
    assert abs(low - want) > 3 * RTOL * abs(want), (low, want)


# -- what a traced step counts ---------------------------------------------


@pytest.fixture
def kernels(monkeypatch):
    """The attention kernels' own path, interpreted: where sites count."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fa, "_interpret_default", lambda: True)


def _lower_step(cfg, rows=1):
    tx = build_optimizer("adamw", lr=1e-3)
    mesh = build_mesh(MeshConfig(), jax.devices()[:1])
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    state = jax.eval_shape(lambda p: TrainState(
        step=jnp.zeros((), jnp.int32), params=p, opt_state=tx.init(p),
    ), params)
    x = jax.ShapeDtypeStruct((rows, cfg.max_seq_len), jnp.int32)
    return build_train_step(cfg, mesh, tx, donate=False).lower(state, x, x)


def test_the_counters_are_of_a_whole_step(kernels):
    """Every pass's layers are traced for themselves: every site, tile,
    lane and kept count of the looped step is ``ut_steps`` times the plain
    model's of the same layers, and the ``ut_*`` counters say the loop."""
    # grouped heads at T = 2048: the streaming kernels' triangle path
    looped = _cfg(remat=True, num_kv_heads=2, max_seq_len=2048)
    plain = replace(looped, ut_steps=1, ut_entropy_weight=0.0)
    names = STREAM + EDGE + LANES + KEPT
    before = trace_counts.snapshot()
    _lower_step(plain)
    once = added(before, names)
    assert added(before, UT) == (0, 0, 0, 0)
    before = trace_counts.snapshot()
    _lower_step(looped)
    assert added(before, names) == tuple(PASSES * n for n in once)
    assert added(before, KEPT) == (BLOCKS * PASSES,)
    assert once[0] > 0 and once[names.index("attn_edge_tiles")] > 0
    # passes, one-mixer layers summed over the passes, exits, and the exits
    # whose gradients the head's forward rule makes: a step's, every one
    assert added(before, UT) == (
        PASSES, 2 * BLOCKS * PASSES, PASSES, PASSES
    )
    # a program that only evaluates traces no forward rule
    before = trace_counts.snapshot()
    x = jax.ShapeDtypeStruct((1, looped.max_seq_len), jnp.int32)
    jax.jit(lambda p, x: loss_fn(p, x, x, looped, None)).lower(
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), looped)), x
    )
    assert added(before, UT)[2:] == (PASSES, 0)
    fields = set(PipelineStats.__dataclass_fields__)
    folded = {"ut_reports", "ut_entropy_sum", "ut_exit_step_sum"}
    assert set(UT) | folded <= fields


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_step_holds_one_exits_logits_at_a_time(dtype):
    """A vocabulary wide enough that an exit's [B, T, V] float32 logits
    outweigh everything else the toy step holds: the compiled step's
    temporaries stay under 2.9 such arrays (it reads 2.65 in either dtype
    here: one exit's logits, the softmax and softmax minus one-hot; the
    checkpointed map this form replaced read 2.64 in float32 and 3.62 in
    bfloat16, its logits, their float32 cotangent, its cast and a copy),
    where four exits held together with their cotangents would need
    eight. No array of any dtype holds all four exits' [B, T, V]."""
    cfg = _cfg(vocab_size=16384, model_dim=32, num_heads=2, num_kv_heads=2,
               dense_mlp_dim=32, remat=True, dtype=dtype)
    logits = 2 * T * cfg.vocab_size * 4
    text = _lower_step(cfg, rows=2)
    assert not re.search(
        rf"tensor<{PASSES}x2x{T}x{cfg.vocab_size}x\w+>", text.as_text()
    )
    temp = text.compile().memory_analysis().temp_size_in_bytes
    assert logits < temp < 2.9 * logits, (temp, logits)


def test_an_exit_is_three_products_and_one_rounding():
    """The lowered step of a bfloat16 toy: the exits' loop body, which
    runs ``ut_steps`` times, holds the three products of the vocabulary's
    width (the logits, and the two gradient products: R + 2R a step, where
    the checkpointed map made the logits again for 4R), all with bfloat16
    operands. The one [B, T, V] float32 array a ``convert`` reads is
    softmax minus one-hot on its way to bfloat16, and both gradient
    products read what it writes: no float32 cotangent of the logits is
    cast. The only other float32 operand of that width is the head's leaf
    itself, cast once."""
    vocab, rows = 1000, 2
    cfg = _cfg(vocab_size=vocab, remat=True, dtype="bfloat16")
    lines = _lower_step(cfg, rows=rows).as_text().splitlines()
    wide = re.compile(rf"x{vocab}x(bf16|f32)>")
    products = [
        l for l in lines if "stablehlo.dot_general" in l and wide.search(l)
    ]
    assert len(products) == 3, products
    logits = f"tensor<{rows}x{T}x{vocab}x"
    (forward,) = [l for l in products if f"-> {logits}bf16>" in l]
    backward = [l for l in products if l is not forward]
    casts = [
        l for l in lines
        if "stablehlo.convert" in l and f": ({logits}f32>)" in l
    ]
    assert len(casts) == 1, casts
    rounded = casts[0].split("=")[0].strip()
    assert f"-> {logits}bf16>" in casts[0]
    for product in backward:
        assert re.search(rf"{rounded}\b", product.split(":")[0]), product
        assert "f32>, " not in product.split(" : ")[1]
    leaf = [
        l for l in lines if "stablehlo.convert" in l
        and f": (tensor<{cfg.model_dim}x{vocab}xf32>)" in l
    ]
    assert len(leaf) == 1, leaf


# -- the analytic step cost ---------------------------------------------------


def test_the_analytic_cost_counts_the_passes():
    """A looped toy's operations are those of the plain model with
    ``ut_steps`` times the layers, and ``ut_steps - 1`` more heads; its
    parameters are the layers' once."""
    cfg = _cfg()
    plain = replace(cfg, ut_steps=1, ut_entropy_weight=0.0)
    deep = replace(plain, num_layers=2 * BLOCKS * PASSES,
                   layer_pattern="*-" * BLOCKS * PASSES)
    looped, once, unrolled = (
        profile_model(c, batch=2, seq=T) for c in (cfg, plain, deep)
    )
    head = once.modules[-1].fwd_flops
    assert looped.modules[-1].fwd_flops == PASSES * head
    assert looped.fwd_flops == unrolled.fwd_flops + (PASSES - 1) * head
    assert looped.step_flops == 3.0 * looped.fwd_flops
    assert looped.total_params == once.total_params
    assert looped.modules[0].activation_bytes == (
        once.modules[0].activation_bytes
    )


# -- what refuses a looped model ---------------------------------------------


def test_cached_decoding_refuses_a_looped_model():
    cfg = _cfg()
    with pytest.raises(NotImplementedError, match="a layer AND pass"):
        init_kv_cache(cfg, 1, T)
    tokens = jnp.zeros((1, 1), jnp.int32)
    with pytest.raises(NotImplementedError, match="ut_steps 4"):
        forward_step(None, tokens, cfg, None, 0)
    with pytest.raises(NotImplementedError, match="ut_steps 4"):
        forward_step_ragged(None, tokens[0], cfg, None, tokens[0])


def test_the_pipeline_refuses_a_looped_model():
    with pytest.raises(ValueError, match="knows one visit"):
        _check_pipeline_cfg(_cfg(), 2)


def test_sequence_parallelism_refuses_a_looped_model():
    mesh = build_mesh(MeshConfig(sp=2), jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="times a step"):
        build_train_step(_cfg(), mesh, build_optimizer("adamw", lr=1e-3))


@pytest.mark.parametrize("nonsense,said", [
    (dict(ut_steps=0), "count of passes"),
    (dict(ut_entropy_weight=-0.1), "no less than 0"),
    (dict(ut_steps=1), "ut_steps is 1"),  # an entropy weight and one pass
    (dict(layer_pattern="", num_layers=2, mixer_out_norm=False,
          dense_mlp_dim=0), "layer_pattern's walk"),
    (dict(layer_pattern="", num_layers=2, mixer_out_norm=False,
          dense_mlp_dim=0, scan_layers=True), "scan_layers form"),
    (dict(layer_pattern="*E" * BLOCKS, num_experts=4, mlp_dim=32),
     "one visit a step"),
])
def test_construction_refuses(nonsense, said):
    with pytest.raises(ValueError, match=said):
        _cfg(**nonsense)
