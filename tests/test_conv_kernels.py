"""The convolution stretch before a scan as kernels
(``ops/conv_kernels.py``), interpreted on the CPU, held to the plain
statement ``silu(causal_conv1d(x, w, b))`` and to ``jax.grad`` of it; the
rule that chooses between them (``fits``), and the counters the mixers
keep of which way each site went (``ops/mamba2.conv_silu``)."""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.common import trace_counts
from dlrover_tpu.models.config import TransformerConfig
from dlrover_tpu.models.train import TrainState, build_train_step
from dlrover_tpu.models.transformer import init_params, loss_fn
from dlrover_tpu.ops import conv_kernels, mamba2
from dlrover_tpu.ops.gated_delta import gated_delta_mixer
from dlrover_tpu.ops.mamba2 import causal_conv1d, conv_silu
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.trainer.elastic.trainer import build_optimizer
from trace_counted import CONV, added

F32 = jnp.float32
# three time blocks of 512 (a halo crosses two boundaries) and one
# channel block; two rows of a batch
B, T, C, K = 2, 1536, 256, 4


def plain(x, w, b=None):
    return jax.nn.silu(causal_conv1d(x, w, b)).astype(x.dtype)


def _inputs(dtype, bias, seed=0, shape=(B, T, C), taps=K):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], shape).astype(dtype)
    w = 0.5 * jax.random.normal(ks[1], (taps, shape[2]))
    b = 0.3 * jax.random.normal(ks[2], shape[2:]) if bias else None
    dy = jax.random.normal(ks[3], shape).astype(dtype)
    return x, w, b, dy


def _grads(fn, x, w, b, dy):
    """The output and every cotangent, float32."""
    o, vjp = jax.vjp(fn, x, w, b)
    named = dict(zip(("o", "dx", "dw", "db"), (o, *vjp(dy))))
    return {
        n: np.asarray(v, np.float32) for n, v in named.items()
        if v is not None
    }


def _rel(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# a float32 sum in another order; the last bit of a bfloat16
LIMIT = {"float32": 1e-5, "bfloat16": 2.0**-7}


@pytest.mark.parametrize("what", ["o", "dx", "dw", "db"])
@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no_bias"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernels_give_what_the_plain_statement_gives(dtype, bias, what):
    if what == "db" and not bias:
        pytest.skip("a convolution without a bias has no such cotangent")
    args = _inputs(jnp.dtype(dtype), bias)
    assert conv_kernels.fits(args[0], args[1])
    got = _grads(conv_kernels.conv_silu, *args)
    want = _grads(plain, *args)
    assert set(got) == set(want)
    assert got[what].shape == want[what].shape
    assert _rel(got[what], want[what]) <= LIMIT[dtype]
    if what in ("o", "dx") and dtype == "bfloat16":
        # rounded once: where a bfloat16 differs it is by its last bit
        # (beside a float32 difference where the taps cancel)
        slack = 1e-5 * np.max(np.abs(want[what]))
        assert np.all(
            np.abs(got[what] - want[what])
            <= 2.0**-7 * np.abs(want[what]) + slack
        )


@pytest.mark.parametrize("steps", [32, 64, 1024])
def test_a_sequence_of_one_time_block(steps):
    """One sub-block and no loop; two; and one block of the largest kind."""
    args = _inputs(F32, True, seed=steps, shape=(2, steps, 128))
    assert conv_kernels._blocks(args[0])[0] == min(steps, 512)
    got = _grads(conv_kernels.conv_silu, *args)
    want = _grads(plain, *args)
    for n in want:
        assert _rel(got[n], want[n]) <= 1e-5, n


@pytest.mark.parametrize("taps", [2, 4, 8])
def test_other_tap_counts_up_to_the_halo(taps):
    args = _inputs(F32, True, seed=taps, shape=(1, 128, 128), taps=taps)
    assert conv_kernels.fits(args[0], args[1])
    got = _grads(conv_kernels.conv_silu, *args)
    want = _grads(plain, *args)
    for n in want:
        assert _rel(got[n], want[n]) <= 1e-5, n


def test_a_halo_crosses_a_time_block_boundary():
    """One step before a block's end reaches K - 1 steps into the next
    block forward, and a cotangent at a block's first step reaches K - 1
    steps into the block before backward."""
    bt = conv_kernels._blocks(jnp.zeros((1, T, C)))[0]
    assert bt == 512 and T // bt == 3
    x = jnp.zeros((1, T, C)).at[0, bt - 1].set(1.0).at[0, 2 * bt - 2].set(-2.0)
    w = jnp.arange(1.0, K + 1)[:, None] * jnp.ones((K, C))
    o = np.asarray(conv_kernels.conv_silu(x, w))
    np.testing.assert_allclose(o, np.asarray(plain(x, w)), atol=1e-6)
    # step bt - 1 is read through taps K-1 .. 0 at steps bt - 1 .. bt + K - 2
    pre = np.zeros(2 * K)
    pre[K - 1:2 * K - 1] = np.arange(K, 0, -1)
    seen = o[0, bt - K:bt + K, 0]
    np.testing.assert_allclose(seen, pre / (1 + np.exp(-pre)), atol=1e-6)
    dy = jnp.zeros((1, T, C)).at[0, bt].set(1.0).at[0, 2 * bt + 1].set(0.5)
    b = jnp.full((C,), 0.25)
    got = _grads(conv_kernels.conv_silu, x, w, b, dy)
    want = _grads(plain, x, w, b, dy)
    assert np.any(want["dx"][0, bt - K + 1:bt] != 0.0)
    assert np.all(want["dx"][0, :bt - K + 1] == 0.0)
    for n in want:
        np.testing.assert_allclose(got[n], want[n], atol=1e-6, err_msg=n)


def test_zeros_stand_before_a_rows_start():
    """The first K - 1 steps of every row of the batch read zeros, not
    the end of the row (or of the channel block) before."""
    x, w, b, dy = _inputs(F32, True, seed=3)
    x = x.at[:, -K:].set(100.0)  # what a wrapped roll would bring in
    o = np.asarray(conv_kernels.conv_silu(x, w, b))
    for t in range(K):
        pre = b + sum(
            w[K - 1 - s] * x[:, t - s] for s in range(t + 1)
        )
        np.testing.assert_allclose(
            o[:, t], np.asarray(jax.nn.silu(pre)), rtol=1e-5, atol=1e-6
        )
    # and a cotangent at the row's last steps reads zeros after its end
    dy = dy.at[:, :K].set(100.0)
    got = _grads(conv_kernels.conv_silu, x, w, b, dy)
    want = _grads(plain, x, w, b, dy)
    assert _rel(got["dx"][:, -K:], want["dx"][:, -K:]) <= 1e-5


def test_the_rows_of_a_batch_do_not_see_each_other():
    x, w, b, dy = _inputs(F32, True, seed=4)
    both = _grads(conv_kernels.conv_silu, x, w, b, dy)
    alone = [
        _grads(conv_kernels.conv_silu, x[i:i + 1], w, b, dy[i:i + 1])
        for i in range(B)
    ]
    for n in ("o", "dx"):
        np.testing.assert_array_equal(
            both[n], np.concatenate([a[n] for a in alone])
        )
    for n in ("dw", "db"):  # summed over the batch outside the kernel
        np.testing.assert_allclose(
            both[n], sum(a[n] for a in alone), rtol=1e-6, atol=1e-6
        )


def test_the_weights_cotangents_take_the_weights_dtype():
    x, w, b, dy = _inputs(jnp.bfloat16, True)
    _, vjp = jax.vjp(
        conv_kernels.conv_silu, x, w.astype(jnp.bfloat16), b
    )
    dx, dw, db = vjp(dy)
    assert (dx.dtype, dw.dtype, db.dtype) == (jnp.bfloat16, jnp.bfloat16, F32)


# -- the rule -----------------------------------------------------------------

REFUSED = {
    "channels_not_whole_lane_tiles": ((1, 128, 200), K),
    "channels_of_toy_width": ((1, 128, 48), K),
    "a_ragged_sequence": ((1, 1000, 256), K),
    "a_sequence_shorter_than_a_block": ((1, 16, 256), K),
    "one_step_of_cached_decoding": ((1, 1, 256), K),
    "more_taps_than_the_halo": ((1, 128, 256), 9),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_fits_refuses(case):
    shape, taps = REFUSED[case]
    x, w = jnp.zeros(shape, jnp.bfloat16), jnp.zeros((taps, shape[2]))
    assert not conv_kernels.fits(x, w)
    before = trace_counts.snapshot()
    text = str(jax.make_jaxpr(conv_silu)(x, w))
    assert "pallas_call" not in text
    assert added(before, CONV) == (1, 0)


@pytest.mark.parametrize("dtype", ["float16", "int8", "float64"])
def test_fits_refuses_other_dtypes(dtype):
    x = jax.ShapeDtypeStruct((1, 128, 256), jnp.dtype(dtype))
    assert not conv_kernels.fits(x, jnp.zeros((K, 256)))


@pytest.mark.parametrize("shape", [(1, 8192, 6144), (1, 8192, 8192),
                                   (1, 8192, 12288), (2, 1024, 128),
                                   (1, 32, 128)], ids=str)
def test_fits_takes_the_cells_shapes(shape):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    assert conv_kernels.fits(x, jnp.zeros((K, shape[2])))
    bt, bc = conv_kernels._blocks(x)
    assert shape[1] % bt == 0 and bt % conv_kernels._ROWS == 0
    assert shape[2] % bc == 0 and bc % conv_kernels._LANES == 0


def test_fits_refuses_a_program_on_a_mesh_of_several_devices():
    x, w = jnp.zeros((2, 128, 256), jnp.bfloat16), jnp.zeros((K, 256))
    one = build_mesh(MeshConfig(), jax.devices()[:1])
    many = build_mesh(MeshConfig(dp=2), jax.devices()[:2])
    assert conv_kernels.fits(x, w, one)
    assert not conv_kernels.fits(x, w, many)
    # no mesh handed down: a region that leaves an axis to GSPMD keeps
    # the plain statement, one that names every axis owns its shard
    assert conv_kernels.fits(x, w, None)
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    seen = {}

    def inside(x):
        seen["fits"] = conv_kernels.fits(x, w, None)
        return x

    jax.make_jaxpr(shard_map(
        inside, mesh=many, in_specs=P("dp"), out_specs=P("dp"),
        axis_names=frozenset({"dp"}),
    ))(x)
    assert seen["fits"] is False  # fsdp, tp, ... are still GSPMD's


def _hybrid(**over):
    """Two mixers whose convolutions are one lane tile wide: a Mamba-2
    layer of 4 x 16 + 2 x 2 x 16 channels, a Gated DeltaNet layer of
    2 x 16 + 2 x 16 + 4 x 16."""
    cfg = TransformerConfig(
        vocab_size=64, num_layers=3, layer_pattern="MG-", model_dim=32,
        num_heads=2, mlp_dim=32, dense_mlp_dim=32, max_seq_len=64,
        positions="none", rmsnorm=True, tie_embeddings=False,
        ssm_heads=4, ssm_head_dim=16, ssm_state=16, ssm_groups=2,
        ssm_chunk=16, gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=16,
        gdn_value_dim=16, gdn_chunk=16, dtype="float32",
        param_dtype="float32",
    )
    return replace(cfg, **over)


def _step(cfg, mesh):
    tx = build_optimizer("adamw", lr=1e-3)
    params = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)
    )
    state = jax.eval_shape(lambda p: TrainState(
        step=jnp.zeros((), jnp.int32), params=p, opt_state=tx.init(p),
    ), params)
    x = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    return build_train_step(cfg, mesh, tx, donate=False).lower(state, x, x)


def test_the_mixers_take_the_kernels_where_the_widths_allow():
    cfg = _hybrid()
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    x = rng.integers(0, 64, (2, 64)).astype(np.int32)
    before = trace_counts.snapshot()
    text = str(jax.make_jaxpr(lambda p: loss_fn(p, x, x, cfg, None))(params))
    assert added(before, CONV) == (2, 2)
    assert "conv_silu_fwd" in text
    # against the same model with the rule switched off
    loss = jax.value_and_grad(lambda p: loss_fn(p, x, x, cfg, None))
    a, ga = loss(params)
    took = conv_kernels.fits
    try:
        conv_kernels.fits = lambda *a: False
        b, gb = jax.value_and_grad(
            lambda p: loss_fn(p, x, x, cfg, None)
        )(params)
    finally:
        conv_kernels.fits = took
    assert float(a) == pytest.approx(float(b), rel=1e-6)
    flat_a = jax.tree_util.tree_leaves_with_path(ga)
    flat_b = jax.tree_util.tree_leaves(gb)
    for (path, u), v in zip(flat_a, flat_b):
        if np.any(np.asarray(v)):
            assert _rel(np.asarray(u), np.asarray(v)) <= 1e-4, (
                jax.tree_util.keystr(path)
            )


def test_the_mixers_lower_to_the_plain_statement_at_toy_widths():
    narrow = _hybrid(ssm_head_dim=8, gdn_key_dim=8)
    before = trace_counts.snapshot()
    text = _step(narrow, build_mesh(MeshConfig(), jax.devices()[:1])).as_text()
    assert added(before, CONV) == (2, 0)
    assert "conv_silu" not in text


def test_the_mixers_lower_to_the_plain_statement_on_a_mesh():
    cfg = _hybrid()
    before = trace_counts.snapshot()
    _step(cfg, build_mesh(MeshConfig(dp=2), jax.devices()[:2]))
    sites, in_kernels = added(before, CONV)
    assert sites >= 2 and in_kernels == 0


def test_under_checkpoint_both_counts_see_the_same_traces():
    """A layer under ``jax.checkpoint`` is traced once as the primal and
    once more for the backward pass: both counters are kept at the one
    place that sees both, so the share reads N of N (how the trainer
    folds what a step's build traced: ``test_trace_counts.py``)."""
    cfg = _hybrid(remat=True)
    mesh = build_mesh(MeshConfig(), jax.devices()[:1])
    before = trace_counts.snapshot()
    _step(cfg, mesh)
    sites, in_kernels = added(before, CONV)
    assert sites == in_kernels >= 2
    # without recomputation every mixer is one site
    before = trace_counts.snapshot()
    _step(replace(cfg, remat=False), mesh)
    assert added(before, CONV) == (2, 2)
    # a model without such a layer never moves it
    dense = TransformerConfig(
        vocab_size=64, num_layers=1, model_dim=32, num_heads=2, mlp_dim=32,
        max_seq_len=64,
    )
    before = trace_counts.snapshot()
    _step(dense, mesh)
    assert added(before, CONV) == (0, 0)


def test_the_delta_mixer_hands_its_mesh_to_the_rule():
    """``gated_delta_mixer(mesh=...)``: the convolution of a mixer on a
    mesh of several devices stays the plain statement."""
    cfg = _hybrid()
    many = build_mesh(MeshConfig(dp=2), jax.devices()[:2])
    p = init_params(jax.random.PRNGKey(0), cfg)["layers"][1]["gdn"]
    u = jnp.zeros((2, 64, 32))
    for mesh, want in ((None, (1, 1)), (many, (1, 0))):
        before = trace_counts.snapshot()
        jax.make_jaxpr(lambda u: gated_delta_mixer(u, p, cfg, 1e-5, mesh))(u)
        assert added(before, CONV) == want
    before = trace_counts.snapshot()
    ssm = init_params(jax.random.PRNGKey(0), cfg)["layers"][0]["ssm"]
    jax.make_jaxpr(lambda u: mamba2.mamba2_mixer(u, ssm, cfg, 1e-5, many))(u)
    assert added(before, CONV) == (1, 0)
