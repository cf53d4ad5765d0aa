"""The Mamba-1 selective scan (``ops/selective_scan.py``): the chunked
plain statement and the ``sscan_*`` kernels (interpreted on the CPU) held
to the recurrence itself, one step at a time; decays from -1e-3 to -16 a
step; rows that are no whole chunks; the rule that picks the way; the
mixer's tree and its sites."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.common import trace_counts
from dlrover_tpu.models.config import TransformerConfig
from dlrover_tpu.ops import selective_scan as ss

SSCAN = ("sscan_sites", "sscan_kernel_sites", "sscan_serial_steps")


def recurrence(x, dt, a, bm, cm, d):
    """S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t; y_t = C_t S_t + D x_t."""
    B, T, C = x.shape

    def step(S, at):
        xt, dtt, bt, ct = at
        S = jnp.exp(dtt[..., None] * a) * S + (
            (dtt * xt)[..., None] * bt[:, None, :]
        )
        return S, jnp.sum(S * ct[:, None, :], -1)

    _, ys = jax.lax.scan(
        step, jnp.zeros((B, C, a.shape[1])),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, bm, cm)),
    )
    return jnp.moveaxis(ys, 0, 1) + d * x


def inputs(T, C=128, N=16, B=2, seed=0, decay=(-3.0, 3.0)):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    lo, hi = decay
    return (
        jax.random.normal(k[0], (B, T, C)),
        jax.nn.softplus(jax.random.normal(k[1], (B, T, C)) - 2.0),
        -jnp.exp(jax.random.uniform(k[2], (C, N), minval=lo, maxval=hi)),
        jax.random.normal(k[3], (B, T, N)),
        jax.random.normal(k[4], (B, T, N)),
        jax.random.normal(k[5], (C,)),
    ), jax.random.normal(k[6], (B, T, C))


def value_and_grads(fn, args, w):
    return jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a) * w), argnums=tuple(range(6))
    ))(*args)


def rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("T,chunk", [(96, 32), (100, 32), (37, 64), (7, 1)])
def test_plain_statement_is_the_recurrence(T, chunk):
    args, w = inputs(T, C=24, N=8)
    want, g_want = value_and_grads(recurrence, args, w)
    got, g_got = value_and_grads(
        lambda *a: ss.selective_scan_plain(*a, chunk), args, w
    )
    assert abs(float(got - want)) <= 1e-5 * abs(float(want))
    for a, b in zip(g_got, g_want):
        assert rel(a, b) <= 2e-5


@pytest.mark.parametrize("C,N", [(128, 16), (384, 8), (512, 16)])
def test_kernels_are_the_recurrence(C, N):
    """Interpreted: two time blocks, so the state crosses a block's edge
    forward and its gradient backward; one, three and (at 512 lanes) four
    lane groups a channel block."""
    args, w = inputs(256, C=C, N=N, B=1 if C > 128 else 2)
    assert ss.fits(args[0], args[2])
    want, g_want = value_and_grads(recurrence, args, w)
    got, g_got = value_and_grads(ss.selective_scan_kernels, args, w)
    assert abs(float(got - want)) <= 1e-5 * abs(float(want))
    for name, a, b in zip("x dt a b c d".split(), g_got, g_want):
        assert rel(a, b) <= 2e-5, name


@pytest.mark.parametrize("way", ["plain", "kernels"])
def test_decays_from_a_thousandth_to_sixteen_a_step(way):
    """``dt A`` from -1e-3 (a state that forgets nothing over the row) to
    -16 (one that forgets all in a step): no overflow, no NaN, and the
    recurrence's numbers."""
    args, w = inputs(128, C=128, N=16, B=1)
    C, N = args[2].shape
    x, _, _, bm, cm, d = args
    per_step = -jnp.exp(jnp.linspace(np.log(1e-3), np.log(16.0), C * N))
    a = per_step.reshape(C, N)
    dt = jnp.ones_like(args[1])
    args = (x, dt, a, bm, cm, d)
    fn = ss.selective_scan_kernels if way == "kernels" else (
        lambda *a: ss.selective_scan_plain(*a, 48)
    )
    want, g_want = value_and_grads(recurrence, args, w)
    got, g_got = value_and_grads(fn, args, w)
    assert np.isfinite(float(got))
    assert abs(float(got - want)) <= 1e-5 * abs(float(want))
    for g, gw in zip(g_got, g_want):
        assert bool(jnp.all(jnp.isfinite(g))) and rel(g, gw) <= 2e-5


def test_kernels_round_once_in_bfloat16():
    args, w = inputs(128, B=1)
    x, dt, a, bm, cm, d = args
    low = (x.astype(jnp.bfloat16), dt, a, bm.astype(jnp.bfloat16),
           cm.astype(jnp.bfloat16), d)
    got = ss.selective_scan_kernels(*low)
    plain = ss.selective_scan_plain(*low, 32)
    assert got.dtype == plain.dtype == jnp.bfloat16
    assert rel(got.astype(jnp.float32), plain.astype(jnp.float32)) <= 1e-2
    grads = jax.grad(
        lambda *a: jnp.sum(ss.selective_scan_kernels(*a).astype(jnp.float32)),
        argnums=(0, 1, 3),
    )(*low)
    assert [g.dtype for g in grads] == [jnp.bfloat16, jnp.float32,
                                        jnp.bfloat16]


@pytest.mark.parametrize("shape,states,dtype,fit", [
    ((1, 256, 256), 16, jnp.bfloat16, True),
    ((2, 128, 128), 8, jnp.float32, True),
    ((1, 16384, 5120), 16, jnp.bfloat16, True),  # the cell's
    ((1, 200, 256), 16, jnp.bfloat16, False),  # no whole time blocks
    ((1, 256, 192), 16, jnp.bfloat16, False),  # no whole lane tiles
    ((1, 256, 256), 12, jnp.bfloat16, False),  # no whole sublane tiles
    ((1, 256, 256), 64, jnp.bfloat16, False),
    ((1, 256, 256), 16, jnp.float16, False),
])
def test_the_rule_reads_the_input(shape, states, dtype, fit):
    x = jax.ShapeDtypeStruct(shape, dtype)
    a = jax.ShapeDtypeStruct((shape[2], states), jnp.float32)
    assert ss.fits(x, a) is fit


def _cfg(**over):
    base = dict(
        vocab_size=64, num_layers=2, layer_pattern="S-", model_dim=32,
        num_heads=4, sscan_inner=128, sscan_state=8, sscan_dt_rank=4,
        sscan_chunk=16, dense_mlp_dim=32, swiglu=True, positions="none",
        dtype="float32",
    )
    return TransformerConfig(**dict(base, **over))


@pytest.mark.parametrize("T,sites", [(128, (1, 1, 3 * 128)), (40, (1, 0, 120))])
def test_a_mixer_is_a_site_and_counts_its_steps(T, sites):
    cfg = _cfg()
    p = ss.init_selective_scan_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    assert set(p) == set(ss.selective_scan_logical_axes())
    assert p["A_log"].shape == (128, 8) and p["w_xproj"].shape == (128, 20)
    assert np.allclose(np.exp(p["A_log"][5]), np.arange(1, 9))
    u = jax.random.normal(jax.random.PRNGKey(1), (1, T, 32))
    before = trace_counts.snapshot()
    grads = jax.grad(
        lambda p: jnp.sum(ss.selective_scan_mixer(u, p, cfg)[0] ** 2)
    )(p)
    got = trace_counts.since(before)
    assert tuple(got[n] for n in SSCAN) == sites
    for name, g in grads.items():
        assert bool(jnp.any(g != 0)), name


def test_the_mixer_goes_the_same_both_ways():
    cfg = _cfg()
    p = ss.init_selective_scan_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(1), (1, 128, 32))
    (out, y) = ss.selective_scan_mixer(u, p, cfg)
    assert y.shape == (1, 128, 128)
    # a mesh of two devices' worth: the plain statement
    short = ss.selective_scan_mixer(u[:, :100], p, cfg)
    assert rel(short[0], out[:, :100]) <= 1e-5  # causal, either way
    assert rel(short[1], y[:, :100]) <= 1e-5
