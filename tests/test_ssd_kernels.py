"""The Mamba-2 chunked scan as kernels (``ops/ssd_kernels.py``), interpreted
on the CPU, held to the plain statement (``ops/mamba2.ssd_chunked`` with the
``D`` skip the mixer adds), to the recurrence itself one step at a time and
to ``jax.grad`` of the statement; the rule that chooses between the two ways
(``fits``), and the counters the mixer keeps of which way each site went."""

import functools
import importlib.util
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.common import trace_counts
from dlrover_tpu.models.config import TransformerConfig
from dlrover_tpu.models.train import TrainState, build_train_step
from dlrover_tpu.models.transformer import init_params
from dlrover_tpu.ops import mamba2, ssd_kernels
from dlrover_tpu.ops.mamba2 import ssd_chunked
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.trainer.elastic.trainer import build_optimizer
from trace_counted import SSD, added

F32 = jnp.float32
Q, N = 128, 128
# the smallest shapes ``fits`` takes: two and five chunks (the states in
# the scratch cross programs), one and two groups of two heads of 64 (a
# lane tile holds both), a group of one head of a whole tile, two rows of
# a batch; (B, T, G, rep, P) and the activation dtype
CASES = {
    "two_chunks_one_group": ((1, 256, 1, 2, 64), "float32"),
    "five_chunks_two_groups": ((2, 640, 2, 2, 64), "float32"),
    "two_chunks_two_groups_bf16": ((1, 256, 2, 2, 64), "bfloat16"),
    "a_head_of_a_whole_tile": ((1, 256, 2, 1, 128), "float32"),
}
NAMES = ("y", "dx", "ddt", "da", "dB", "dC", "dD")


def _inputs(shape, dtype, seed=0):
    B, T, G, rep, P = shape
    H = G * rep
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(k[0], (B, T, H, P)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, T, H)) - 2.0)
    a = -jnp.exp(jax.random.normal(k[2], (H,)))
    Bm = (jax.random.normal(k[3], (B, T, G, N)) * N**-0.5).astype(dtype)
    Cm = jax.random.normal(k[4], (B, T, G, N)).astype(dtype)
    D = 1.0 + 0.3 * jax.random.normal(k[5], (H,))
    dy = jax.random.normal(k[6], (B, T, H, P)).astype(dtype)
    return (x, dt, a, Bm, Cm, D), dy


def statement(x, dt, a, Bm, Cm, D):
    """The plain statement as ``mamba2_mixer`` rounds it."""
    y = ssd_chunked(x, dt, a, Bm, Cm, Q)
    return (y + D[:, None] * x.astype(F32)).astype(x.dtype)


def recurrence(x, dt, a, Bm, Cm, D):
    """The recurrence itself, one step at a time, float32."""
    H, G = x.shape[2], Bm.shape[2]

    def step(S, inp):
        x_t, dt_t, B_t, C_t = inp
        B_h = jnp.repeat(B_t, H // G, axis=1)
        C_h = jnp.repeat(C_t, H // G, axis=1)
        S = jnp.exp(dt_t * a)[..., None, None] * S + (
            (dt_t[..., None] * x_t)[..., None] * B_h[:, :, None]
        )
        return S, jnp.einsum("bhpn,bhn->bhp", S, C_h) + D[:, None] * x_t

    S0 = jnp.zeros((x.shape[0], H, x.shape[3], N), F32)
    xs = tuple(jnp.moveaxis(t.astype(F32), 1, 0) for t in (x, dt, Bm, Cm))
    return jnp.moveaxis(jax.lax.scan(step, S0, xs)[1], 0, 1)


def kernel(x, dt, a, Bm, Cm, D):
    return ssd_kernels.ssd(x, dt, a, Bm, Cm, D, Q)


def _grads(fn, args, dy):
    """The output and every cotangent, float32."""
    def both(*args):
        y, vjp = jax.vjp(fn, *args)
        return (y, *vjp(dy.astype(y.dtype)))

    return {
        n: np.asarray(v, np.float32)
        for n, v in zip(NAMES, jax.jit(both)(*args))
    }


@functools.lru_cache(maxsize=None)
def _three(case):
    shape, dtype = CASES[case]
    args, dy = _inputs(shape, jnp.dtype(dtype))
    x, dt, _, Bm, Cm, _ = args
    assert ssd_kernels.fits(x, dt, Bm, Cm, Q)
    return (
        _grads(kernel, args, dy), _grads(statement, args, dy),
        np.asarray(jax.jit(recurrence)(*args), np.float32),
    )


def _rel(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# float32: sums in another order, within 2e-4 of a leaf's largest entry;
# bfloat16: a few last bits of the operands the cotangents are rounded to
LIMIT = {"float32": 2e-4, "bfloat16": 2.0**-6}


@pytest.mark.parametrize("what", NAMES)
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernels_give_what_the_plain_statement_gives(case, what):
    got, want, _ = _three(case)
    assert got[what].shape == want[what].shape
    assert np.max(np.abs(want[what])) > 0
    assert _rel(got[what], want[what]) <= LIMIT[CASES[case][1]]


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernels_scan_is_the_recurrence(case):
    got, _, want = _three(case)
    limit = 2e-5 if CASES[case][1] == "float32" else 2.0**-6
    assert _rel(got["y"], want) <= limit


def test_the_rows_of_a_batch_and_the_groups_do_not_see_each_other():
    """A program's scratch starts at zero at its first chunk: the second
    row of a batch is the scan of that row alone."""
    args, _ = _inputs((2, 256, 2, 2, 64), F32)
    whole = jax.jit(kernel)(*args)
    x, dt, a, Bm, Cm, D = args
    alone = jax.jit(kernel)(x[1:], dt[1:], a, Bm[1:], Cm[1:], D)
    np.testing.assert_array_equal(np.asarray(whole[1:]), np.asarray(alone))


def test_the_cotangents_take_their_primals_dtypes():
    args, dy = _inputs((1, 256, 1, 2, 64), jnp.bfloat16)
    y, vjp = jax.vjp(kernel, *args)
    assert y.dtype == jnp.bfloat16 and y.shape == args[0].shape
    for got, arg in zip(vjp(dy), args):
        assert got.dtype == arg.dtype and got.shape == arg.shape


def test_a_program_lowers_each_kernel_once_a_shape():
    """Four sites of one shape are one ``jax.jit`` a direction: one
    private function a kernel in the lowered module, called from every
    site."""
    args, _ = _inputs((1, 256, 1, 2, 64), jnp.bfloat16)

    def four(x, *rest):
        for _ in range(4):
            x = kernel(x, *rest)
        return jnp.sum(x.astype(F32))

    text = jax.jit(jax.value_and_grad(four)).lower(*args).as_text()
    assert text.count("func.func private @_fwd_call") == 1
    assert text.count("func.func private @_bwd_call") == 1
    assert text.count("call @_fwd_call") == 4
    assert text.count("call @_bwd_call") == 4


# -- the rule -----------------------------------------------------------------

# (B, T, H, P, G, N, chunk)
REFUSED = {
    "a_chunk_of_no_whole_lane_tiles": (1, 256, 4, 64, 2, 128, 64),
    "a_sequence_of_no_whole_chunks": (1, 320, 4, 64, 2, 128, 128),
    "a_state_of_no_whole_lane_tiles": (1, 256, 4, 64, 2, 64, 128),
    "a_group_of_no_whole_lane_tiles": (1, 256, 2, 64, 2, 128, 128),
    "heads_that_are_no_whole_groups": (1, 256, 6, 64, 4, 128, 128),
    "a_chunk_longer_than_a_program_holds": (1, 1024, 4, 64, 2, 128, 512),
    "a_group_wider_than_a_program_holds": (1, 256, 32, 64, 1, 128, 128),
    "toy_widths": (2, 48, 4, 8, 2, 16, 16),
    "heads_of_a_sixteenth_of_a_tile": (1, 256, 32, 8, 2, 128, 128),
    "heads_of_a_tile_and_a_half": (1, 256, 4, 192, 2, 128, 256),
    "a_head_longer_than_a_chunk": (1, 256, 2, 256, 2, 128, 128),
    "one_step_of_cached_decoding": (1, 1, 4, 64, 2, 128, 1),
}


def _shapes(B, T, H, P, G, N, dtype=jnp.bfloat16):
    sds = jax.ShapeDtypeStruct
    return (
        sds((B, T, H, P), dtype), sds((B, T, H), F32),
        sds((B, T, G, N), dtype), sds((B, T, G, N), dtype),
    )


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_fits_refuses(case):
    *shape, chunk = REFUSED[case]
    assert not ssd_kernels.fits(*_shapes(*shape), chunk)


@pytest.mark.parametrize("dtype", ["float16", "int8", "float64"])
def test_fits_refuses_other_dtypes(dtype):
    assert not ssd_kernels.fits(
        *_shapes(1, 256, 4, 64, 2, 128, jnp.dtype(dtype)), 128
    )


def test_fits_refuses_operands_of_two_dtypes():
    x, dt, Bm, Cm = _shapes(1, 256, 4, 64, 2, 128)
    sds = jax.ShapeDtypeStruct
    assert not ssd_kernels.fits(x, dt, sds(Bm.shape, F32), Cm, 128)
    assert not ssd_kernels.fits(x, sds(dt.shape, jnp.bfloat16), Bm, Cm, 128)


CELLS = {
    "nemotron": (1, 8192, 64, 64, 8, 128, 128),
    "the_fingerprints_row": (1, 1024, 64, 64, 8, 128, 128),
    "two_chunks_of_two_heads": (1, 256, 2, 64, 1, 128, 128),
    "heads_of_a_lane_tile": (2, 512, 8, 128, 2, 256, 256),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_fits_takes_the_cells_shapes(cell, dtype):
    *shape, chunk = CELLS[cell]
    assert ssd_kernels.fits(*_shapes(*shape, jnp.dtype(dtype)), chunk)


def test_fits_refuses_a_program_on_a_mesh_of_several_devices():
    args = _shapes(2, 256, 4, 64, 2, 128)
    one = build_mesh(MeshConfig(), jax.devices()[:1])
    many = build_mesh(MeshConfig(dp=2), jax.devices()[:2])
    assert ssd_kernels.fits(*args, 128, one)
    assert not ssd_kernels.fits(*args, 128, many)
    assert ssd_kernels.fits(*args, 128, None)


# -- the mixer ----------------------------------------------------------------


def _model(**over):
    """A Mamba-2 layer whose scan fits the kernels (4 heads of 64 in two
    groups, a state of 128, chunks of 128) and a dense layer."""
    cfg = TransformerConfig(
        vocab_size=64, num_layers=2, layer_pattern="M-", model_dim=32,
        num_heads=2, mlp_dim=32, dense_mlp_dim=32, max_seq_len=256,
        positions="none", rmsnorm=True, tie_embeddings=False,
        ssm_heads=4, ssm_head_dim=64, ssm_state=128, ssm_groups=2,
        ssm_chunk=128, dtype="float32", param_dtype="float32",
    )
    return replace(cfg, **over)


def test_the_mixers_gradient_through_the_kernels_is_the_plain_ways(
    monkeypatch
):
    cfg = _model()
    p = init_params(jax.random.PRNGKey(0), cfg)["layers"][0]["ssm"]
    p = dict(p, D=1.0 + 0.2 * jnp.cos(jnp.arange(p["D"].shape[0])))
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 256, 32))

    def loss(p, u):
        return jnp.sum(jnp.sin(mamba2.mamba2_mixer(u, p, cfg, 1e-5)))

    before = trace_counts.snapshot()
    text = str(jax.make_jaxpr(jax.grad(loss))(p, u))
    assert added(before, SSD) == (1, 1)
    assert "ssd_scan_fwd" in text and "ssd_scan_bwd" in text
    a, ga = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(p, u)
    monkeypatch.setattr(ssd_kernels, "fits", lambda *a: False)
    before = trace_counts.snapshot()
    b, gb = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(p, u)
    assert added(before, SSD) == (1, 0)
    assert float(a) == pytest.approx(float(b), rel=1e-5)
    flat_a = jax.tree_util.tree_leaves_with_path(ga)
    for (path, x), y in zip(flat_a, jax.tree_util.tree_leaves(gb)):
        assert np.any(np.asarray(y)), jax.tree_util.keystr(path)
        assert _rel(np.asarray(x), np.asarray(y)) <= 2e-4, (
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
    x = jax.ShapeDtypeStruct((2, 256), jnp.int32)
    return build_train_step(cfg, mesh, tx, donate=False).trace(state, x, x)


REFUSED_STEPS = {
    "toy_widths": (dict(ssm_head_dim=16, ssm_state=16, ssm_chunk=16), 1),
    "a_mesh_of_two_devices": ({}, 2),
}


@pytest.mark.parametrize("case", sorted(REFUSED_STEPS))
def test_a_step_the_rule_refuses_lowers_to_the_plain_statement(case):
    """No Mosaic call and no interpreted kernel of this module is in the
    step: the scan is ``ssd_chunked`` under its ``jax.checkpoint``."""
    over, devices = REFUSED_STEPS[case]
    mesh = build_mesh(MeshConfig(dp=devices), jax.devices()[:devices])
    before = trace_counts.snapshot()
    traced = _step(_model(**over), mesh)
    sites, in_kernels = added(before, SSD)
    assert sites >= 1 and in_kernels == 0
    assert "ssd_scan" not in str(traced.jaxpr)
    assert "tpu_custom_call" not in traced.lower().as_text()


def test_a_step_that_fits_holds_both_kernels_and_counts_its_site():
    mesh = build_mesh(MeshConfig(), jax.devices()[:1])
    before = trace_counts.snapshot()
    text = str(_step(_model(), mesh).jaxpr)
    assert added(before, SSD) == (1, 1)
    assert "ssd_scan_fwd" in text and "ssd_scan_bwd" in text
    # under a recomputed layer both counts see the same traces
    before = trace_counts.snapshot()
    _step(_model(remat=True), mesh)
    sites, in_kernels = added(before, SSD)
    assert sites == in_kernels >= 1
    # a model without such a layer never moves them
    dense = TransformerConfig(
        vocab_size=64, num_layers=1, model_dim=32, num_heads=2, mlp_dim=32,
        max_seq_len=256,
    )
    before = trace_counts.snapshot()
    _step(dense, mesh)
    assert added(before, SSD) == (0, 0)


def test_the_reference_holds_the_scans_kernels_too():
    """``test_nemotron_h.py``'s model at the smallest widths the scan's
    kernels take (heads of 64, a state of 128, two chunks of 128): loss and
    every gradient leaf against the reference's scan, one step at a time,
    as ``test_loss_and_every_gradient_leaf_match_the_reference`` holds the
    plain way."""
    from test_nemotron_h import (
        GRAD_RTOL, ROOT, RTOL, _cfg, _ref_loss, _weights, loss_fn,
    )

    spec = importlib.util.spec_from_file_location(
        "nemotron_h_ref",
        os.path.join(ROOT, "benchmark", "references", "nemotron_h.py"),
    )
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    cfg = _cfg(
        ssm_head_dim=64, ssm_state=128, ssm_chunk=128, max_seq_len=256,
        layer_pattern="ME*", num_layers=3,
    )
    params = _weights(cfg)
    rng = np.random.default_rng(0)
    data = rng.integers(0, cfg.vocab_size, (1, 257)).astype(np.int32)
    x, y = data[:, :-1], data[:, 1:]
    before = trace_counts.snapshot()
    got, g_got = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, x, y, cfg, None)
    ))(params)
    assert added(before, SSD) == (1, 1)
    want, g_want = jax.jit(jax.value_and_grad(
        lambda p: _ref_loss(ref, p, x, y)
    ))(params)
    assert abs(float(got) - float(want)) <= RTOL * abs(float(want))
    got_leaves = jax.tree_util.tree_leaves_with_path(g_got)
    want_leaves = jax.tree_util.tree_leaves(g_want)
    assert len(got_leaves) == len(want_leaves) == 3 + 11 + 5 + 7
    for (path, a), b in zip(got_leaves, want_leaves):
        name = jax.tree_util.keystr(path)
        if name.endswith(".bias"):
            continue
        assert float(jnp.max(jnp.abs(b))) > 0, name
        assert _rel(a, b) <= GRAD_RTOL, name
