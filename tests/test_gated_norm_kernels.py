"""The gated norm after a scan as kernels (``ops/gated_norm_kernels.py``),
interpreted on the CPU, held to the three plain statements
(``head_gated_rmsnorm`` and both orders of ``gated_group_rmsnorm``) and to
``jax.grad`` of them; the rule that chooses between them (``fits``), and
the counters the mixers keep of which way each site went
(``ops/mamba2.gated_norm``)."""

import functools
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.common import trace_counts
from dlrover_tpu.models.config import TransformerConfig
from dlrover_tpu.models.train import TrainState, build_train_step
from dlrover_tpu.models.transformer import init_params
from dlrover_tpu.ops import gated_norm_kernels, mamba2
from dlrover_tpu.ops.gated_delta import gated_delta_mixer, head_gated_rmsnorm
from dlrover_tpu.ops.mamba2 import gated_group_rmsnorm, gated_norm
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.trainer.elastic.trainer import build_optimizer
from trace_counted import GATE, added

F32 = jnp.float32
EPS = 1e-5
# three row blocks of 512 and two rows of a batch; four groups of a lane
# tile, or two of two tiles where the gate is inside the norm
B, T, C = 2, 1536, 512
# the form -> the group's width
FORMS = {"head": 128, "outside": 128, "inside": 256, "outside_192": 192}
# a head of a tile and a half (Olmo-Hybrid's value head): four groups, two
# spans of three tiles
SHAPES = {"outside_192": (B, T, 768)}


def statement(form, width):
    """The plain statement of a form, rounded as the mixers round it; the
    weight is one group's in the head-wise form, as the mixer holds it."""
    def plain(o, z, w):
        if form == "head":
            y = head_gated_rmsnorm(o, z, w, EPS)
        else:
            y = gated_group_rmsnorm(
                o, z, w, o.shape[-1] // width, EPS,
                norm_before_gate=form.startswith("outside"),
            )
        return y.astype(o.dtype)

    return plain


def _two_spans(form):
    """Channels of the short cases: 256, or two groups of 192."""
    return 384 if FORMS[form] == 192 else 256


def _inputs(form, dtype, seed=0, shape=None):
    width = FORMS[form]
    shape = shape or SHAPES.get(form, (B, T, C))
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    o = (2.0 * jax.random.normal(ks[0], shape)).astype(dtype)
    gates = (*shape[:2], shape[2] // width) if form == "head" else shape
    z = jax.random.normal(ks[1], gates).astype(dtype)
    w = 1.0 + 0.3 * jax.random.normal(
        ks[2], (width if form == "head" else shape[2],)
    )
    dy = jax.random.normal(ks[3], shape).astype(dtype)
    return o, z, w, dy


def _grads(fn, o, z, w, dy):
    """The output and every cotangent, float32."""
    y, vjp = jax.vjp(fn, o, z, w)
    return {
        n: np.asarray(v, np.float32)
        for n, v in zip(("y", "do", "dz", "dw"), (y, *vjp(dy)))
    }


def kernel(form):
    width = FORMS[form]

    def fn(o, z, w):
        every = jnp.tile(w, o.shape[-1] // w.shape[0])
        return gated_norm_kernels.gated_norm(
            o, z, every, width, EPS, form == "inside"
        )

    return fn


@functools.lru_cache(maxsize=None)
def _both(form, dtype):
    args = _inputs(form, jnp.dtype(dtype))
    assert gated_norm_kernels.fits(args[0], args[1], FORMS[form])
    assert gated_norm_kernels._row_block(args[0]) == 512
    assert gated_norm_kernels._span(FORMS[form]) == (
        384 if form == "outside_192" else FORMS[form]
    )
    return (
        _grads(kernel(form), *args),
        _grads(statement(form, FORMS[form]), *args),
    )


def _rel(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# a float32 sum in another order; the last bit of a bfloat16
LIMIT = {"float32": 1e-5, "bfloat16": 2.0**-7}


@pytest.mark.parametrize("what", ["y", "do", "dz", "dw"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_kernels_give_what_the_plain_statement_gives(form, dtype, what):
    got, want = _both(form, dtype)
    assert got[what].shape == want[what].shape
    assert _rel(got[what], want[what]) <= LIMIT[dtype]
    if what != "dw" and dtype == "bfloat16":
        # rounded once: where a bfloat16 differs it is by its last bit
        slack = 1e-5 * np.max(np.abs(want[what]))
        assert np.all(
            np.abs(got[what] - want[what])
            <= 2.0**-7 * np.abs(want[what]) + slack
        )


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("rows", [32, 64, 96])
def test_a_sequence_of_one_row_block(form, rows):
    """One sub-block; two; and three blocks of one (96 is whole blocks of
    32 alone)."""
    args = _inputs(form, F32, seed=rows, shape=(1, rows, _two_spans(form)))
    assert gated_norm_kernels._row_block(args[0]) == (64 if rows == 64 else 32)
    got = _grads(kernel(form), *args)
    want = _grads(statement(form, FORMS[form]), *args)
    for n in want:
        assert _rel(got[n], want[n]) <= 1e-5, n


@pytest.mark.parametrize("form", sorted(FORMS))
def test_the_rows_of_a_batch_do_not_see_each_other(form):
    o, z, w, dy = _inputs(form, F32, seed=4, shape=(2, 64, _two_spans(form)))
    both = _grads(kernel(form), o, z, w, dy)
    alone = [
        _grads(kernel(form), o[i:i + 1], z[i:i + 1], w, dy[i:i + 1])
        for i in range(2)
    ]
    for n in ("y", "do", "dz"):
        apart = np.concatenate([a[n] for a in alone])
        if FORMS[form] % 128:
            # a masked sum's order is the interpreter's to choose a program
            np.testing.assert_allclose(both[n], apart, rtol=2e-6, atol=2e-6)
        else:
            np.testing.assert_array_equal(both[n], apart)
    # summed over the batch outside the kernel
    np.testing.assert_allclose(
        both["dw"], sum(a["dw"] for a in alone), rtol=1e-5, atol=1e-5
    )


def test_a_group_sees_its_own_lanes_and_its_own_gate():
    """A group that is all zeros but one lane, beside groups of large
    values: its mean square is its own, and a gate a group reaches the
    lanes of its group alone."""
    o = jnp.full((1, 32, 512), 50.0).at[:, :, 128:256].set(0.0)
    o = o.at[:, :, 130].set(2.0)
    z = jnp.zeros((1, 32, 4)).at[:, :, 1].set(30.0).at[:, :, 2].set(-30.0)
    w = jnp.ones((128,))
    y = np.asarray(kernel("head")(o, z, w))
    np.testing.assert_allclose(
        y, np.asarray(statement("head", 128)(o, z, w)), rtol=1e-6
    )
    alone = 2.0 / np.sqrt(2.0**2 / 128 + EPS)
    np.testing.assert_allclose(y[0, :, 130], alone, rtol=1e-5)
    assert np.all(np.abs(y[0, :, 256:384]) < 1e-10)  # 50 x sigmoid(-30)
    np.testing.assert_allclose(y[0, :, :128], 0.5, rtol=1e-5)


def test_the_weights_cotangent_takes_the_weights_dtype():
    o, z, w, dy = _inputs("outside", jnp.bfloat16, shape=(1, 32, 256))
    _, vjp = jax.vjp(kernel("outside"), o, z, w.astype(jnp.bfloat16))
    do, dz, dw = vjp(dy)
    assert (do.dtype, dz.dtype, dw.dtype) == (jnp.bfloat16,) * 3
    _, vjp = jax.vjp(kernel("head"), *_inputs("head", jnp.bfloat16)[:3])
    do, dz, dw = vjp(_inputs("head", jnp.bfloat16)[3])
    assert dz.shape == (B, T, C // 128) and dw.shape == (128,)
    assert dw.dtype == F32


def test_a_program_lowers_the_kernel_once_a_shape_and_form():
    """Six sites of one shape are one ``jax.jit``: one private function a
    kernel in the lowered module, called from every site."""
    o, z, w, _ = _inputs("head", jnp.bfloat16, shape=(1, 64, 256))

    def six(o, z, w):
        for _ in range(6):
            o = kernel("head")(o, z, w)
        return o

    text = jax.jit(six).lower(o, z, w).as_text()
    assert text.count("func.func private @_fwd_call") == 1
    assert text.count("call @_fwd_call") == 6


# -- the rule -----------------------------------------------------------------

REFUSED = {
    "a_group_of_no_whole_lane_tiles": ((1, 128, 256), 64, None),
    "a_gate_a_group_of_a_tile_and_a_half": (
        (1, 128, 384), 192, (1, 128, 2)
    ),
    "a_tile_and_a_half_in_channels_of_no_whole_spans": (
        (1, 128, 576), 192, None
    ),
    "channels_that_are_no_whole_groups": ((1, 128, 384), 256, None),
    "channels_of_toy_width": ((1, 128, 48), 16, None),
    "rows_that_are_no_whole_blocks": ((1, 1000, 256), 128, None),
    "a_sequence_shorter_than_a_block": ((1, 16, 256), 128, None),
    "one_step_of_cached_decoding": ((1, 1, 256), 128, None),
    "a_gate_of_another_shape": ((1, 128, 256), 128, (1, 128, 1)),
    "channels_wider_than_a_block_holds": ((1, 128, 1 << 16), 128, None),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_fits_refuses(case):
    shape, width, gates = REFUSED[case]
    o = jnp.zeros(shape, jnp.bfloat16)
    z = jnp.zeros(gates or shape, jnp.bfloat16)
    w = jnp.ones((shape[2],))
    assert not gated_norm_kernels.fits(o, z, width)
    if gates or shape[2] > 4096:
        return
    before = trace_counts.snapshot()
    text = str(jax.make_jaxpr(lambda o, z, w: gated_norm(
        statement("outside", width), o, z, w, width, EPS
    ))(o, z, w))
    assert "pallas_call" not in text
    assert added(before, GATE) == (1, 0)


@pytest.mark.parametrize("dtype", ["float16", "int8", "float64"])
def test_fits_refuses_other_dtypes(dtype):
    o = jax.ShapeDtypeStruct((1, 128, 256), jnp.dtype(dtype))
    assert not gated_norm_kernels.fits(o, o, 128)


def test_fits_refuses_a_gate_of_another_dtype():
    o = jax.ShapeDtypeStruct((1, 128, 256), jnp.bfloat16)
    assert not gated_norm_kernels.fits(
        o, jax.ShapeDtypeStruct(o.shape, F32), 128
    )


CELLS = {
    "ling": ((1, 8192, 4096), 128, (1, 8192, 32)),
    "qwen3_next": ((1, 8192, 4096), 128, None),
    "nemotron": ((1, 8192, 4096), 512, None),
    "a_batch_of_short_rows": ((2, 1024, 128), 128, None),
    "one_sub_block": ((1, 32, 128), 128, (1, 32, 1)),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_fits_takes_the_cells_shapes(cell, dtype):
    shape, width, gates = CELLS[cell]
    o = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
    z = jax.ShapeDtypeStruct(gates or shape, jnp.dtype(dtype))
    assert gated_norm_kernels.fits(o, z, width)
    bt = gated_norm_kernels._row_block(o)
    assert shape[1] % bt == 0 and bt % min(bt, gated_norm_kernels._ROWS) == 0
    held = bt * shape[2] * o.dtype.itemsize
    assert held <= gated_norm_kernels._BLOCK_BYTES
    # the backward kernel holds five such blocks twice
    assert 10 * held < gated_norm_kernels._VMEM_BYTES


def test_fits_refuses_a_program_on_a_mesh_of_several_devices():
    o = jnp.zeros((2, 128, 256), jnp.bfloat16)
    one = build_mesh(MeshConfig(), jax.devices()[:1])
    many = build_mesh(MeshConfig(dp=2), jax.devices()[:2])
    assert gated_norm_kernels.fits(o, o, 128, one)
    assert not gated_norm_kernels.fits(o, o, 128, many)
    # no mesh handed down: a region that leaves an axis to GSPMD keeps
    # the plain statement, one that names every axis owns its shard
    assert gated_norm_kernels.fits(o, o, 128, None)
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    seen = {}

    def inside(o):
        seen["fits"] = gated_norm_kernels.fits(o, o, 128, None)
        return o

    jax.make_jaxpr(shard_map(
        inside, mesh=many, in_specs=P("dp"), out_specs=P("dp"),
        axis_names=frozenset({"dp"}),
    ))(o)
    assert seen["fits"] is False  # fsdp, tp, ... are still GSPMD's


# -- the mixers ---------------------------------------------------------------


def _hybrid(**over):
    """Two mixers whose scans' outputs are two lane tiles wide: a Mamba-2
    layer of 4 heads of 64 in two groups of 128, a Gated DeltaNet layer
    of 2 value heads of 128."""
    cfg = TransformerConfig(
        vocab_size=64, num_layers=3, layer_pattern="MG-", model_dim=32,
        num_heads=2, mlp_dim=32, dense_mlp_dim=32, max_seq_len=64,
        positions="none", rmsnorm=True, tie_embeddings=False,
        ssm_heads=4, ssm_head_dim=64, ssm_state=16, ssm_groups=2,
        ssm_chunk=16, gdn_key_heads=2, gdn_value_heads=2, gdn_key_dim=16,
        gdn_value_dim=128, gdn_chunk=16, dtype="float32",
        param_dtype="float32",
    )
    return replace(cfg, **over)


MIXERS = {
    "mamba2": ("ssm", {}),
    "delta_silu_a_channel": ("gdn", {}),
    "delta_sigmoid_a_head": ("gdn", dict(gdn_gate="head_sigmoid")),
}


@pytest.mark.parametrize("name", sorted(MIXERS))
def test_a_mixers_gradient_through_the_kernels_is_the_plain_paths(
    name, monkeypatch
):
    kind, over = MIXERS[name]
    cfg = _hybrid(**over)
    layers = init_params(jax.random.PRNGKey(0), cfg)["layers"]
    p = layers[0 if kind == "ssm" else 1][kind]
    # a norm weight that is not all ones
    p = dict(p, norm=1.0 + 0.2 * jnp.cos(jnp.arange(p["norm"].shape[0])))
    mixer = mamba2.mamba2_mixer if kind == "ssm" else gated_delta_mixer
    u = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 32))

    def loss(p, u):
        return jnp.sum(jnp.sin(mixer(u, p, cfg, EPS)))

    before = trace_counts.snapshot()
    text = str(jax.make_jaxpr(loss)(p, u))
    assert added(before, GATE) == (1, 1)
    assert "gated_norm_fwd" in text
    a, ga = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(p, u)
    monkeypatch.setattr(gated_norm_kernels, "fits", lambda *a: False)
    before = trace_counts.snapshot()
    b, gb = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(p, u)
    assert added(before, GATE) == (1, 0)
    assert float(a) == pytest.approx(float(b), rel=1e-4)
    flat_a = jax.tree_util.tree_leaves_with_path(ga)
    flat_b = jax.tree_util.tree_leaves(gb)
    for (path, x), y in zip(flat_a, flat_b):
        if np.any(np.asarray(y)):
            assert _rel(np.asarray(x), np.asarray(y)) <= 1e-4, (
                jax.tree_util.keystr(path)
            )


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


def test_the_mixers_lower_to_the_plain_statement_at_toy_widths():
    narrow = _hybrid(ssm_head_dim=16, gdn_value_dim=16)
    before = trace_counts.snapshot()
    text = _step(narrow, build_mesh(MeshConfig(), jax.devices()[:1])).as_text()
    assert added(before, GATE) == (2, 0)
    assert "gated_norm" not in text


def test_the_mixers_lower_to_the_plain_statement_on_a_mesh():
    before = trace_counts.snapshot()
    _step(_hybrid(), build_mesh(MeshConfig(dp=2), jax.devices()[:2]))
    sites, in_kernels = added(before, GATE)
    assert sites >= 2 and in_kernels == 0


def test_under_checkpoint_both_counts_see_the_same_traces():
    """A layer under ``jax.checkpoint`` is traced once as the primal and
    once more for the backward pass: both counters are kept at the one
    place that sees both, so the share reads N of N."""
    cfg = _hybrid(remat=True)
    mesh = build_mesh(MeshConfig(), jax.devices()[:1])
    before = trace_counts.snapshot()
    _step(cfg, mesh)
    sites, in_kernels = added(before, GATE)
    assert sites == in_kernels >= 2
    # without recomputation every mixer is one site
    before = trace_counts.snapshot()
    _step(replace(cfg, remat=False), mesh)
    assert added(before, GATE) == (2, 2)
    # a model without such a layer never moves it
    dense = TransformerConfig(
        vocab_size=64, num_layers=1, model_dim=32, num_heads=2, mlp_dim=32,
        max_seq_len=64,
    )
    before = trace_counts.snapshot()
    _step(dense, mesh)
    assert added(before, GATE) == (0, 0)


def test_the_mixers_hand_their_mesh_to_the_rule():
    cfg = _hybrid()
    many = build_mesh(MeshConfig(dp=2), jax.devices()[:2])
    layers = init_params(jax.random.PRNGKey(0), cfg)["layers"]
    u = jnp.zeros((2, 64, 32))
    for mixer, p in (
        (gated_delta_mixer, layers[1]["gdn"]),
        (mamba2.mamba2_mixer, layers[0]["ssm"]),
    ):
        for mesh, want in ((None, (1, 1)), (many, (1, 0))):
            before = trace_counts.snapshot()
            jax.make_jaxpr(lambda u: mixer(u, p, cfg, 1e-5, mesh))(u)
            assert added(before, GATE) == want
