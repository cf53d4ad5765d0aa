"""Training by diffusion over blocks (``cfg.objective`` "block_diffusion":
SDAR's) at a small size on the CPU: the program held to
``benchmark/references/sdar.py`` (loss and every gradient leaf, the same
noise), the answer that does not leak, the walk of
``ops/flash_attention.block_diffusion_attention`` against the rule as a
``mask_fn`` (interpreted kernels), the noise's arithmetic, the share adding
up on a doubled row, a lower-precision control, the counters, and what
refuses the objective."""

import functools
import importlib
import importlib.util
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.accel.profiler import PipelineStats, profile_model
from dlrover_tpu.common import trace_counts
from dlrover_tpu.models.config import TransformerConfig
from dlrover_tpu.models.train import (
    TrainState,
    build_train_step,
    fold_diffusion_report,
)
from dlrover_tpu.models.transformer import (
    diffusion_noise,
    forward,
    forward_step,
    init_kv_cache,
    init_params,
    loss_fn,
    token_nll,
)
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.moe import init_moe_params, moe_layer_local
from dlrover_tpu.parallel.pipeline import _check_pipeline_cfg
from dlrover_tpu.trainer.elastic.trainer import build_optimizer
from trace_counted import EDGE, KEPT, added

# `dlrover_tpu.ops.flash_attention` the attribute is the function
fa = importlib.import_module("dlrover_tpu.ops.flash_attention")

RTOL = 1e-5
GRAD_RTOL = 2e-4  # a gradient sums more terms in another order
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
L, B, V = 64, 4, 256
TOP_K = 2
# the toy trains under MDLM's own lower end (the configuration's default),
# the reference's default being the benchmark configuration's
REF_KW = dict(top_k=TOP_K, t_min=1e-3)
BD = ("attn_bd_sites",) + fa._BD
ROW = ("diffusion_positions", "diffusion_data_tokens")


def _cfg(**over):
    cfg = TransformerConfig(
        vocab_size=V, num_layers=4, layer_pattern="*E*E",
        objective="block_diffusion", diffusion_block=B,
        model_dim=64, num_heads=4, num_kv_heads=2, attn_head_dim=16,
        mlp_dim=32, max_seq_len=L, rope=True, rope_theta=1e6, rmsnorm=True,
        norm_eps=1e-6, swiglu=True, tie_embeddings=False, qk_norm=True,
        qk_norm_span="head", num_experts=8, moe_top_k=TOP_K,
        norm_topk_prob=True, router_z_weight=1e-3, dtype="float32",
        param_dtype="float32",
    )
    return replace(cfg, **over)


SHARE = dict(experts_held=4, experts_offset=4)
LEAVES = [
    jax.tree_util.keystr(path)
    for path, _ in jax.tree_util.tree_leaves_with_path(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), _cfg(**SHARE))
    ))
]


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmark", "references", "sdar.py")
    spec = importlib.util.spec_from_file_location("sdar_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _weights(cfg, seed=1):
    """Seeded weights with every norm weight off its initial value."""
    params = init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def jitter(path, leaf):
        if getattr(path[-1], "key", None) == "scale":
            return leaf + 0.1 * jax.random.normal(next(keys), leaf.shape)
        return leaf

    return jax.tree_util.tree_map_with_path(jitter, params)


def _rows(seed=0, rows=2):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, V - 1, (rows, L)), jnp.int32)


def _rel(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# -- the whole model against the reference --------------------------------


@pytest.fixture(scope="module")
def both(ref):
    """Loss and gradients of the share case, the program's and the
    reference's, made once for every leaf's test."""
    cfg = _cfg(**SHARE)
    params, x = _weights(cfg), _rows()
    got = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, x, x, cfg, None)
    ))(params)
    want = jax.jit(jax.value_and_grad(lambda p: ref.loss(
        p, x, x, experts_offset=SHARE["experts_offset"], **REF_KW
    )))(params)
    flat = lambda g: dict(zip(LEAVES, jax.tree_util.tree_leaves(g)))  # noqa
    return (got[0], flat(got[1])), (want[0], flat(want[1]))


def test_the_loss_matches_the_reference(both, ref):
    (got, _), (want, _) = both
    assert abs(float(got) - float(want)) <= RTOL * abs(float(want))
    # and with every expert held, the whole model's
    cfg = _cfg()
    params, x = _weights(cfg), _rows(3)
    whole = jax.jit(lambda p: loss_fn(p, x, x, cfg, None))(params)
    plain = jax.jit(lambda p: ref.loss(p, x, x, **REF_KW))(params)
    assert abs(float(whole) - float(plain)) <= RTOL * abs(float(plain))


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_leaf_matches_the_reference(both, leaf):
    """Tables, final norm and head; an attention entry's four matrices,
    two head norms and norm; an expert entry's router, three matrices and
    norm: 3 + 2 x (7 + 5) leaves."""
    assert len(LEAVES) == 3 + 2 * (7 + 5)
    (_, got), (_, want) = both
    assert float(jnp.max(jnp.abs(want[leaf]))) > 0, leaf
    assert _rel(got[leaf], want[leaf]) <= GRAD_RTOL, leaf


def test_the_reference_with_bfloat16_operands_is_refused(both, ref,
                                                         monkeypatch):
    """The lower-precision control: the reference itself with every
    matmul operand rounded to bfloat16 misses the toy's limit."""
    def rounded(f):
        def g(*args, **kw):
            return f(*(
                a.astype(jnp.bfloat16).astype(jnp.float32)
                if isinstance(a, jax.Array) else a for a in args
            ), **kw)
        return g

    monkeypatch.setattr(ref, "matmul", rounded(jnp.matmul))
    monkeypatch.setattr(ref, "einsum", rounded(jnp.einsum))
    cfg = _cfg(**SHARE)
    low = jax.jit(lambda p: ref.loss(
        p, _rows(), None, experts_offset=SHARE["experts_offset"], **REF_KW
    ))(_weights(cfg))
    want = both[1][0]
    assert abs(float(low) - float(want)) > 10 * RTOL * abs(float(want))


def test_remat_gives_the_same_loss_and_gradients(both):
    cfg = _cfg(remat=True, **SHARE)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, _rows(), None, cfg, None)
    ))(_weights(cfg))
    (want, want_grads), _ = both
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    for name, g in zip(LEAVES, jax.tree_util.tree_leaves(grads)):
        assert _rel(g, want_grads[name]) <= 1e-5, name


def test_the_objective_adds_no_leaf_and_its_defaults_are_the_parents():
    """Every field's default is next-token prediction: the default
    configuration states no objective, and a model under the objective
    has the tree of the same model without it."""
    plain = TransformerConfig()
    assert (plain.objective, plain.diffusion_block) == ("", 0)
    assert plain.diffusion_mask_id is None and plain.diffusion_t_min == 1e-3
    assert plain.diffusion_noise_seed == 0
    tree = lambda cfg: jax.tree_util.tree_structure(jax.eval_shape(  # noqa
        lambda: init_params(jax.random.PRNGKey(0), cfg)
    ))
    assert tree(_cfg()) == tree(_cfg(objective="", diffusion_block=0))
    assert _cfg().mask_id == V - 1
    assert _cfg(diffusion_mask_id=7).mask_id == 7


# -- the answer does not leak ----------------------------------------------


@pytest.fixture(scope="module")
def logits_of():
    cfg = _cfg()
    params = _weights(cfg)
    run = jax.jit(lambda ids: forward(params, ids, cfg)[0])

    def logits(x_t, x_0):
        return np.asarray(run(jnp.concatenate([x_t, x_0], axis=1)))

    return logits


@pytest.mark.parametrize("block", [0, 5, L // B - 2])
def test_the_answer_does_not_leak(logits_of, block):
    """Changing ``x_0`` inside block ``b`` leaves the logits of noised
    block ``b`` and of every earlier block bit for bit, and moves those of
    block ``b + 1``; changing ``x_t`` in block ``b`` moves no other
    block's logits. The head sees the noised half alone: [1, L, V]."""
    x_0 = _rows(7, rows=1)
    x_t = jnp.where(jnp.arange(L) % 3 == 0, V - 1, x_0)
    base = logits_of(x_t, x_0)
    assert base.shape == (1, L, V)
    inside = slice(block * B, (block + 1) * B)
    after = slice((block + 1) * B, (block + 2) * B)
    moved = logits_of(x_t, x_0.at[0, inside].add(1))
    assert np.array_equal(moved[:, :after.start], base[:, :after.start])
    assert np.max(np.abs(moved[:, after] - base[:, after])) > 1e-4
    noised = logits_of(x_t.at[0, inside].set(3), x_0)
    assert np.array_equal(noised[:, :inside.start], base[:, :inside.start])
    assert np.array_equal(noised[:, after.start:], base[:, after.start:])
    assert np.max(np.abs(noised[:, inside] - base[:, inside])) > 1e-4


# -- the walk against the rule as a mask -----------------------------------


def _qkv(seq, heads=2, kv_heads=1, width=8, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = lambda h: (1, h, 2 * seq, width)  # noqa: E731
    return (
        jax.random.normal(keys[0], shape(heads)),
        jax.random.normal(keys[1], shape(kv_heads)),
        jax.random.normal(keys[2], shape(kv_heads)),
        jax.random.normal(keys[3], shape(heads)),
    )


# (row, diffusion block, kernel block, blocks walked of the grid's)
WALKS = {
    "B=1": (32, 1, 8, 24),
    "B=4": (32, 4, 8, 24),
    # a diffusion block of two kernel blocks: clean blocks over the
    # diagonal are visible, noised x noised ones beside it
    "B=16": (32, 16, 8, 24),
    # one block: the noised half is bidirectional and sees no clean key
    "B=L": (32, 32, 8, 32),
    # diffusion blocks of 12 cross the kernel's blocks of 8
    "crossing": (48, 12, 8, 56),
}


@pytest.mark.parametrize("walk", list(WALKS))
def test_the_walk_is_the_rule_as_a_mask(walk):
    """Forward and both gradients of the interpreted ``flash_attn_bd_*``
    kernels against ``flash_attention_reference`` under
    ``block_diffusion_mask``, and the blocks they walk."""
    seq, block_len, blk, walked = WALKS[walk]
    q, k, v, w = _qkv(seq)
    mask = fa.block_diffusion_mask(seq, block_len)

    def plain(q, k, v):
        o = fa.flash_attention_reference(
            *(t.transpose(0, 2, 1, 3) for t in (q, k, v)), causal=False,
            mask_fn=mask,
        )
        return jnp.sum(o.transpose(0, 2, 1, 3) * w)

    def walks(q, k, v):
        return jnp.sum(fa.block_diffusion_attention(
            q, k, v, block_len=block_len, layout="bhtd", block=blk,
            force="pallas",
        ) * w)

    before = trace_counts.snapshot()
    got, g_got = jax.value_and_grad(walks, (0, 1, 2))(q, k, v)
    grid = (2 * seq // blk) ** 2
    # the forward rule's kernel and the backward's
    assert added(before, fa._BD) == (2 * walked, 2 * grid)
    assert len(fa._bd_blocks(seq, block_len, blk)) == walked
    want, g_want = jax.value_and_grad(plain, (0, 1, 2))(q, k, v)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(g_got, g_want):
        assert _rel(a, b) <= 1e-5
    if walk == "B=L":
        seen = np.asarray(mask(
            jnp.arange(2 * seq)[:, None], jnp.arange(2 * seq)[None, :]
        ))
        assert seen[:seq, :seq].all() and not seen[:seq, seq:].any()
        assert not seen[seq:, :seq].any() and seen[seq:, seq:].all()
    if walk == "B=1":
        seen = np.asarray(mask(
            jnp.arange(2 * seq)[:, None], jnp.arange(2 * seq)[None, :]
        ))
        assert np.array_equal(seen[:seq, :seq], np.eye(seq, dtype=bool))
        assert np.array_equal(
            seen[:seq, seq:], np.tri(seq, k=-1, dtype=bool)
        )


def test_the_walk_at_the_cells_sizes_is_80_blocks_of_256():
    """L = 8192 in blocks of 4, kernels in blocks of 1024: 36 clean x
    clean, 36 noised x clean, the 8 noised x noised blocks on the
    diagonal; every query block's first step holds its own positions."""
    blocks = fa._bd_blocks(8192, 4, 1024)
    assert len(blocks) == 80 and fa._bd_block(8192, 128, 2, None) == 1024
    kinds = [kind for _, _, kind in blocks]
    assert kinds.count(fa._BD_WHOLE) == 56
    assert kinds.count(fa._BD_EQ) == kinds.count(fa._BD_LE) == 8
    assert kinds.count(fa._BD_LT) == 8
    qi, kj, code = (
        np.asarray(t) for t in fa._bd_steps(8192, 4, 1024, by_key=False)
    )
    first = (code & fa._BD_FIRST) != 0
    assert first.sum() == 16 and np.array_equal(qi[first], kj[first])
    assert ((code & fa._BD_LAST) != 0).sum() == 16
    # a shape the kernels cannot run falls to the rule as a mask
    assert fa._bd_block(8192, 128, 2, 1000) is None
    assert fa._bd_block(65536, 128, 2, None) is None  # dq of a head: 64 MiB


def test_an_edge_block_multiplies_its_strips_spans():
    """Strips of an edge block: a noised x noised block's see their own
    span, a clean key block's every key up to their last row's."""
    assert fa._bd_strips(1024, 4, fa._BD_EQ, 8) == tuple(
        (r, r + 128, r, r + 128) for r in range(0, 1024, 128)
    )
    assert fa._bd_strips(1024, 4, fa._BD_LE, 4) == tuple(
        (r, r + 256, 0, r + 256) for r in range(0, 1024, 256)
    )
    # diffusion blocks that cross the kernel's: whole, masked by position
    assert fa._bd_strips(8, 12, fa._BD_LT, 4) == ((0, 8, 0, 8),)
    before = trace_counts.snapshot()
    fa._count_bd_site(8192, 4, 1024, (8, 4), forward=False)
    # 8 "eq" blocks of 64 tiles, 8 multiplied; 16 clean edges of 16, 10
    assert added(before, EDGE) == (8 * 8 + 16 * 10, 8 * 64 + 16 * 16)


# -- the noise ----------------------------------------------------------------


def test_the_noise_is_a_function_of_the_row_and_the_seed(ref):
    cfg = _cfg()
    x = _rows(11, rows=3)
    noise = jax.jit(lambda x: diffusion_noise(x, cfg))
    noise_at = jax.jit(lambda x, step: diffusion_noise(x, cfg, step))
    x_t, masked, weight = noise(x)
    assert all(np.array_equal(a, b) for a, b in zip(
        (x_t, masked, weight), noise(x)
    ))
    # traced or not, the same positions are masked (1 / t may round
    # differently in a fused program)
    assert np.array_equal(diffusion_noise(x, cfg)[1], masked)
    other = diffusion_noise(x, replace(cfg, diffusion_noise_seed=1))
    assert not np.array_equal(masked, other[1])
    # a train step folds its number in: the same row is noised anew in
    # another step, and the same in the same one
    at_3 = diffusion_noise(x, cfg, jnp.int32(3))
    assert not np.array_equal(masked, at_3[1])
    assert not np.array_equal(at_3[1], diffusion_noise(x, cfg, 4)[1])
    assert np.array_equal(at_3[1], noise_at(x, 3)[1])
    # a row's noise is its own: rows differ, and a row keeps its noise
    # whatever stands beside it
    assert not np.array_equal(masked[0], masked[1])
    alone = diffusion_noise(x[1:2], cfg)
    assert np.array_equal(alone[1][0], masked[1])
    # x_t reads the mask id where masked and the row elsewhere
    assert np.array_equal(x_t, np.where(masked, cfg.mask_id, x))
    # the weight is 1 / t, one t a block, t in [t_min, 1)
    t = 1.0 / np.asarray(weight)[np.asarray(masked)]
    assert t.min() >= cfg.diffusion_t_min and t.max() < 1.0
    by_block = np.asarray(weight).reshape(3, L // B, B)
    for block in by_block.reshape(-1, B):
        assert len(set(block[block > 0].tolist())) <= 1
    assert not np.asarray(weight)[~np.asarray(masked)].any()
    # the reference draws the same m and t (equality of integers)
    ref_masked, ref_t = ref.noise(x, t_min=cfg.diffusion_t_min)
    assert np.array_equal(np.asarray(ref_masked, np.int32),
                          np.asarray(masked, np.int32))
    np.testing.assert_allclose(
        np.asarray(weight)[np.asarray(masked)],
        (1.0 / np.asarray(ref_t))[np.asarray(masked)], rtol=1e-6,
    )


def test_the_masked_share_is_the_mean_noise_level():
    """4,096 positions: within four binomial deviations of E[t]."""
    cfg = _cfg(max_seq_len=4096)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.integers(0, V, (1, 4096)), jnp.int32)
    share = float(jnp.mean(diffusion_noise(x, cfg)[1]))
    mean = (1 + cfg.diffusion_t_min) / 2
    assert abs(share - mean) <= 4 * (mean * (1 - mean) / 4096) ** 0.5


def test_a_block_with_nothing_masked_adds_exactly_nothing():
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.normal(size=(1, 8, 16)), jnp.float32)
    targets = jnp.asarray(rng.integers(0, 16, (1, 8)), jnp.int32)
    weight = jnp.asarray([[2.0, 2.0, 0.0, 2.0, 0.0, 0.0, 0.0, 0.0]])
    base = token_nll(logits, targets, token_weights=weight)
    moved = token_nll(
        logits.at[0, 4:].add(3.0), targets, token_weights=weight
    )
    assert float(base) == float(moved)
    each = jax.scipy.special.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, targets[..., None], -1
    )[..., 0]
    assert float(base) == pytest.approx(float(jnp.sum(weight * each) / 8))
    # no weights: the plain mean, as it was
    assert float(token_nll(logits, targets)) == pytest.approx(
        float(jnp.mean(each))
    )


# -- the share on a doubled row ---------------------------------------------


def test_the_shares_add_up_to_the_whole_layer_on_a_doubled_row(ref):
    """Over the 2 shares of 4 experts the held experts' parts are the
    uncut layer, the program's and the reference's, on the 2L positions of
    a doubled row, the choice made over all 8 columns every time."""
    whole = init_moe_params(jax.random.PRNGKey(0), 8, 32, 24, gated=True)
    x = jax.random.normal(jax.random.PRNGKey(5), (2 * L, 32))

    @functools.partial(jax.jit, static_argnames="held")
    def run(params, x, held=None):
        return moe_layer_local(
            params, x, axis_name=None, top_k=TOP_K, normalize=True,
            held=held,
        )

    want, aux = run(whole, x)
    assert _rel(want, ref._experts(x, whole, TOP_K, 0)[0]) <= RTOL
    total = jnp.zeros_like(want)
    for offset in (0, 4):
        cut = whole._replace(**{
            name: getattr(whole, name)[offset:offset + 4]
            for name in ("w_up", "w_down", "w_gate")
        })
        part, part_aux = run(cut, x, held=(offset, 4))
        assert np.array_equal(part_aux["load"], aux["load"])
        assert _rel(part, ref._experts(x, cut, TOP_K, offset)[0]) <= RTOL
        total = total + part
    assert _rel(total, want) <= RTOL


# -- counters, the analytic cost, the report --------------------------------


def test_a_traced_step_counts_the_sites_the_row_and_what_is_kept():
    """The toy's step on the CPU: two sites under the rule, the jnp path
    (no blocks to count, nothing named to keep), 2 x 64 positions a row of
    64 data tokens."""
    cfg = _cfg(remat=True)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    x = jax.ShapeDtypeStruct((2, L), jnp.int32)
    before = trace_counts.snapshot()
    jax.jit(jax.grad(lambda p, x: loss_fn(p, x, x, cfg, None))).lower(
        params, x
    )
    assert added(before, BD) == (2, 0, 0)
    assert added(before, ROW) == (2 * 2 * L, 2 * L)
    assert added(before, KEPT) == (0,)
    fields = set(PipelineStats.__dataclass_fields__)
    assert set(BD) | set(ROW) <= fields
    assert {"diffusion_reports", "diffusion_masked_sum",
            "diffusion_weight_sum"} <= fields


def test_the_kernels_count_their_blocks_and_a_recomputed_site_is_kept():
    q, k, v, _ = _qkv(32)
    call = lambda q, k, v: fa.block_diffusion_attention(  # noqa: E731
        q, k, v, block_len=4, layout="bhtd", block=8, force="pallas",
        interpret=True,
    )
    before = trace_counts.snapshot()
    with trace_counts.keeping_outputs():
        jax.jit(call).lower(q, k, v)
    assert added(before, fa._BD) == (24, 64)
    assert added(before, KEPT) == (1,)
    # a shape the walk cannot run: the rule over the rectangular grid
    before = trace_counts.snapshot()
    jax.jit(lambda q, k, v: fa.block_diffusion_attention(
        q, k, v, block_len=4, layout="bhtd", block=24, force="pallas",
    )).lower(q, k, v)
    walked, square = added(before, fa._BD)
    assert walked == square > 0


def test_the_analytic_cost_is_of_a_doubled_stack_and_a_single_head():
    """``profile_model``: 2L positions through every block, L through the
    head, L^2 + L B pairs a head."""
    cfg = _cfg(num_experts=0, layer_pattern="", num_layers=2, mlp_dim=32)
    plain = replace(cfg, objective="", diffusion_block=0)
    got = {m.name: m for m in profile_model(cfg, 1, L).modules}
    was = {m.name: m for m in profile_model(plain, 1, L).modules}
    assert got["lm_head"].fwd_flops == was["lm_head"].fwd_flops
    assert got["block0.mlp"].fwd_flops == 2 * was["block0.mlp"].fwd_flops
    h, hd = cfg.num_heads, cfg.head_dim
    pairs = lambda m, n: m.fwd_flops - 2.0 * n * m.params  # noqa: E731
    assert pairs(got["block0.attn"], 2 * L) == pytest.approx(
        2.0 * (L * L + L * B) * h * 2 * hd
    )
    assert pairs(was["block0.attn"], L) == pytest.approx(
        2.0 * (L * L / 2) * h * 2 * hd
    )


def test_the_report_folds_the_noise_into_the_stats():
    stats = PipelineStats()
    assert fold_diffusion_report({"loss": 1.0}, stats) == ""
    said = fold_diffusion_report(
        {"diffusion_masked_share": jnp.float32(0.5),
         "diffusion_mean_weight": jnp.float32(1.25)}, stats,
    )
    assert said == " masked=0.5000 weight=1.2500"
    assert (stats.diffusion_reports, stats.diffusion_masked_sum,
            stats.diffusion_weight_sum) == (1, 0.5, 1.25)
    # and a step's aux carries both, scalars beside the routers' terms
    cfg = _cfg()
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    _, aux = jax.eval_shape(
        lambda p, x: loss_fn(p, x, None, cfg, return_aux=True), params,
        jax.ShapeDtypeStruct((2, L), jnp.int32),
    )
    assert aux["diffusion_masked_share"].shape == ()
    assert aux["diffusion_mean_weight"].shape == () and "balance" in aux


def test_a_train_step_noises_a_row_by_its_own_number():
    """The same batch in two steps: each reports the masked share of
    ``diffusion_noise`` at its own step, not the row's alone."""
    cfg = _cfg()
    mesh = build_mesh(MeshConfig(), jax.devices()[:1])
    tx = build_optimizer("adamw", lr=1e-3)
    params = init_params(jax.random.PRNGKey(0), cfg)
    state = TrainState(
        step=jnp.zeros((), jnp.int32), params=params,
        opt_state=tx.init(params),
    )
    step = build_train_step(cfg, mesh, tx, donate=False)
    x = _rows(5, rows=2)
    shares = []
    for n in range(2):
        state, metrics = step(state, x, x)
        shares.append(float(metrics["diffusion_masked_share"]))
        assert shares[-1] == pytest.approx(
            float(jnp.mean(diffusion_noise(x, cfg, n)[1])), abs=1e-7
        )
    assert shares[0] != shares[1]
    assert shares[0] != float(jnp.mean(diffusion_noise(x, cfg)[1]))


# -- what refuses the objective ---------------------------------------------


@pytest.mark.parametrize("nonsense,said", [
    (dict(diffusion_block=5), "does not divide"),
    (dict(diffusion_block=0), "does not divide"),
    (dict(diffusion_block=True), "does not divide"),
    (dict(diffusion_mask_id=V), "outside the table"),
    (dict(diffusion_mask_id=-1), "outside the table"),
    (dict(diffusion_t_min=0.0), "outside"),
    (dict(objective="diffusion"), "unknown objective"),
    (dict(objective=""), "is of the objective"),
    (dict(layer_pattern="WEWE", attn_window=8), "carries state"),
    (dict(layer_pattern="ME*E", ssm_heads=2, ssm_head_dim=8, ssm_state=8),
     "carries state"),
    (dict(layer_pattern="GE*E", gdn_value_heads=2, gdn_key_heads=2,
          gdn_key_dim=8, gdn_value_dim=8), "carries state"),
    (dict(ut_steps=2, num_experts=0, layer_pattern="*-*-"), "ut_steps"),
    (dict(rope=False), "learned absolute positions"),
    (dict(attn_kind="latent", kv_latent_dim=16, qk_nope_dim=8,
          qk_rope_dim=8, v_head_dim=8, num_kv_heads=None),
     "plain projected attention"),
])
def test_construction_refuses(nonsense, said):
    with pytest.raises(ValueError, match=said):
        _cfg(**nonsense)


def test_a_row_of_no_whole_blocks_is_refused_when_it_is_traced():
    cfg = _cfg()
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    with pytest.raises(ValueError, match="whole number of diffusion blocks"):
        jax.eval_shape(
            lambda p, x: loss_fn(p, x, x, cfg), params,
            jax.ShapeDtypeStruct((1, 30), jnp.int32),
        )
    with pytest.raises(ValueError, match="two copies"):
        jax.eval_shape(
            lambda p, x: forward(p, x, cfg), params,
            jax.ShapeDtypeStruct((1, 2 * L - 2), jnp.int32),
        )


def test_sequence_parallel_attention_refuses_the_objective():
    cfg = _cfg()
    mesh = build_mesh(MeshConfig(sp=2), jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="sequence-parallel"):
        build_train_step(cfg, mesh, build_optimizer("adamw", lr=1e-3))
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    x = jax.ShapeDtypeStruct((2, 2 * L), jnp.int32)
    with pytest.raises(NotImplementedError, match="noised position sees"):
        jax.eval_shape(lambda p, x: forward(p, x, cfg, mesh), params, x)


def test_the_pipeline_refuses_the_objective():
    with pytest.raises(ValueError, match="feeds a row twice"):
        _check_pipeline_cfg(_cfg(), 2)


def test_cached_decoding_refuses_the_objective():
    cfg = _cfg()
    with pytest.raises(NotImplementedError, match="denoising it over"):
        init_kv_cache(cfg, 1, L)
    with pytest.raises(NotImplementedError, match="one token a sequence"):
        forward_step(None, jnp.zeros((1, 1), jnp.int32), cfg, None, 0)
