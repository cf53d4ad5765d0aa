"""The Mamba-1 layer (selective state spaces, arXiv:2312.00752): the
selective scan, plainly and as Pallas kernels, and the mixer around it.

The recurrence, for one channel ``c`` with a state of ``N`` numbers, a
decay matrix ``A[c, n] = -exp(A_log[c, n])`` and a step of its own
``dt_t[c] = softplus(delta_t W_dt + b_dt)[c]``; ``B_t`` and ``C_t`` ``[N]``
are shared by all channels:

    S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c]
    y_t[c]    = sum_n C_t[n] S_t[c, n] + D[c] x_t[c]          (S_0 = 0)

The decay is a matrix times a step a channel, not one scalar a head, so
none of the matmuls of the SSD form (``ops/mamba2.ssd_chunked``) applies:
every ``(t, c, n)`` has a decay of its own, and the work is element-wise.

``selective_scan_plain`` states it in chunks: one ``lax.scan`` over the
chunks whose body, under ``jax.checkpoint``, walks the chunk's steps one
after the other in float32. What a backward pass keeps is the state at
each chunk's start, ``[T / chunk, C, N]``, and never ``[T, C, N]``; every
``exp`` is of ``dt A <= 0``, no running sum is ever exponentiated.

Where ``fits`` takes the input (as ``ops/conv_kernels.fits``: read from the
shapes and from who owns the program), forward and backward are the
``sscan_fwd`` / ``sscan_bwd`` kernels under one ``jax.custom_vjp``. A
program is a batch element, a block of ``_STEPS`` time steps and a block of
channels (whole 128-lane tiles), the channels innermost and time
sequential: the state ``[N, channels]`` (states on the sublanes, channels
on the lanes) lives in a VMEM scratch from one time block to the next, and
a block's steps are walked in order, sixteen at a time out of whole tiles.
``B_t`` and ``C_t`` arrive transposed, ``[N, steps]``, and are spread over
the lanes once a time block (``_expand``). The forward writes the state
that enters each time block (``[T / _STEPS, N, C]`` float32, 42 MB at
16384 x 5120 x 16); the backward walks the time blocks from the end, makes
a block's states again from that boundary into VMEM, then walks the block
backwards with ``dS`` carried in a second scratch. It writes ``dx`` and
``d dt`` whole, ``dA`` as one partial a time block (summed by XLA), and
``dB``, ``dC`` as sums over the channels still spread over 128 lanes
(summed by XLA). Nothing of ``[T, C, N]`` is ever an HBM array. The
kernels do no matmul: they are bound by the vector unit (and the ``exp``
of every ``(t, c, n)``), which ``kernel.sscan_roofline`` reads as a low
share of an HBM roofline.

The precision is the plain statement's: ``x``, ``B``, ``C`` in the
activation dtype, ``dt``, ``A``, ``D``, the state and every product in
float32, ``y`` and ``dx`` rounded once.

The spans of a layer: ``scope/layer/sscan/{in_proj,conv,x_proj,scan,gate,
out_proj}``; a gated memory unit's: ``scope/layer/gmu/{in_proj,gate,
out_proj}``.
"""

from __future__ import annotations

import functools
import importlib
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.common import trace_counts
from dlrover_tpu.ops import conv_kernels
from dlrover_tpu.ops.mamba2 import conv_silu

# ``dlrover_tpu.ops.flash_attention`` the attribute is the function
_flash = importlib.import_module("dlrover_tpu.ops.flash_attention")

_F32 = jnp.float32
_LANES = 128
# steps a time block (one lane tile of the transposed B and C) and steps
# walked out of one load (whole sublane tiles of bfloat16 and float32)
_STEPS = 128
_ROWS = 16
_CHANNEL_BLOCKS = (4 * _LANES, 2 * _LANES, _LANES)
_VMEM_LIMIT = 64 * 1024 * 1024


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
def init_selective_scan_params(key, cfg, dtype):
    """One layer's parameters. The in-projection is stored as its two
    column blocks ``[x | z]`` (``w_x``, ``w_z``); ``w_xproj`` is ``[delta |
    B | C]`` wide; ``dt_bias`` is the inverse softplus of a step drawn
    log-uniformly from ``[ssm_dt_min, ssm_dt_max]``, ``w_dt`` uniform in
    ``+- rank^-0.5``, ``A_log = log(1 .. N)`` every channel, ``D = 1``
    (Mamba-1's initial values)."""
    d, d_in, N = cfg.model_dim, cfg.sscan_inner, cfg.sscan_state
    R, K = cfg.sscan_dt_rank, cfg.sscan_conv
    kx, kz, kc, kp, kt, kd, ko = jax.random.split(key, 7)

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape) * fan_in**-0.5).astype(dtype)

    dt = jnp.exp(
        jax.random.uniform(kd, (d_in,))
        * (math.log(cfg.ssm_dt_max) - math.log(cfg.ssm_dt_min))
        + math.log(cfg.ssm_dt_min)
    )
    dt = jnp.maximum(dt, cfg.ssm_dt_floor)
    return {
        "w_x": dense(kx, (d, d_in), d),
        "w_z": dense(kz, (d, d_in), d),
        "conv_w": dense(kc, (K, d_in), K),
        "conv_b": jnp.zeros((d_in,), dtype),
        "w_xproj": dense(kp, (d_in, R + 2 * N), d_in),
        "w_dt": jax.random.uniform(
            kt, (R, d_in), minval=-(R**-0.5), maxval=R**-0.5
        ).astype(dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)), (d_in, N)
        ).astype(dtype),
        "D": jnp.ones((d_in,), dtype),
        "w_out": dense(ko, (d_in, d), d_in),
    }


def selective_scan_logical_axes():
    return {
        "w_x": ("embed", "ssm_inner"),
        "w_z": ("embed", "ssm_inner"),
        "conv_w": (None, None),
        "conv_b": (None,),
        "w_xproj": ("ssm_inner", None),
        "w_dt": (None, "ssm_inner"),
        "dt_bias": (None,),
        "A_log": (None, None),
        "D": (None,),
        "w_out": ("ssm_inner", "embed"),
    }


def init_memory_unit_params(key, cfg, dtype):
    """A gated memory unit's two matrices, as wide as the scan it reads."""
    d, d_in = cfg.model_dim, cfg.sscan_inner
    ki, ko = jax.random.split(key)
    return {
        "w_in": (jax.random.normal(ki, (d, d_in)) * d**-0.5).astype(dtype),
        "w_out": (
            jax.random.normal(ko, (d_in, d)) * d_in**-0.5
        ).astype(dtype),
    }


def memory_unit_logical_axes():
    return {"w_in": ("embed", "ssm_inner"), "w_out": ("ssm_inner", "embed")}


# ---------------------------------------------------------------------------
# the plain statement
# ---------------------------------------------------------------------------
def selective_scan_plain(x, dt, a, bm, cm, d, chunk: int):
    """x [B, T, C] (activation dtype), dt [B, T, C] float32 (after
    softplus), a [C, N] float32 (negative), bm, cm [B, T, N], d [C] ->
    y [B, T, C] in ``x``'s dtype. Any T: the last chunk is filled with
    steps of ``dt = 0``, which leave the state as it is."""
    B, T, C = x.shape
    Q = max(1, min(chunk, T))
    pad = -T % Q

    def chunks(t):  # [B, T, .] -> [nc, Q, B, .] float32, time leading
        t = jnp.pad(t.astype(_F32), ((0, 0), (0, pad), (0, 0)))
        return jnp.moveaxis(t.reshape(B, -1, Q, t.shape[-1]), 0, 2)

    a = a.astype(_F32)

    def step(S, at):  # S [B, C, N]
        xt, dtt, bt, ct = at
        S = jnp.exp(dtt[..., None] * a) * S + (
            (dtt * xt)[..., None] * bt[:, None, :]
        )
        return S, jnp.sum(S * ct[:, None, :], axis=-1)

    @jax.checkpoint  # keeps the state at the chunk's start alone
    def one_chunk(S, chunk_inputs):
        return lax.scan(step, S, chunk_inputs)

    _, ys = lax.scan(
        one_chunk, jnp.zeros((B, C, a.shape[1]), _F32),
        tuple(chunks(t) for t in (x, dt, bm, cm)),
    )
    y = jnp.moveaxis(ys, 2, 0).reshape(B, T + pad, C)[:, :T]
    return (y + d.astype(_F32) * x.astype(_F32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------
def _channel_block(C: int) -> int:
    return next((s for s in _CHANNEL_BLOCKS if C % s == 0), 0)


def fits(x, a, mesh=None) -> bool:
    """THE rule for which way the scan is executed, read from its input:
    the kernels where the channels are whole 128-lane tiles, the sequence
    whole blocks of ``_STEPS`` steps, the states whole sublane tiles (8,
    16, 24 or 32 of them) and one device owns the program (GSPMD refuses
    to partition a Mosaic call); the plain statement everywhere else."""
    return (
        x.ndim == 3
        and jnp.dtype(x.dtype) in (jnp.dtype(jnp.bfloat16), jnp.dtype(_F32))
        and x.shape[1] % _STEPS == 0
        and bool(_channel_block(x.shape[2]))
        and a.shape[1] % 8 == 0 and a.shape[1] <= 32
        and conv_kernels._one_device(x, mesh)
    )


def _expand(src_ref, dst_ref):
    """``src`` [1, N, steps] (a step a lane) -> ``dst`` [steps, N, 128]:
    step ``t``'s column spread over the lanes, so that a step reads its
    ``B_t`` (``C_t``) as whole vector registers with the states on the
    sublanes."""
    tile = src_ref[0]
    N = tile.shape[0]
    for t in range(tile.shape[1]):
        dst_ref[t] = jnp.broadcast_to(tile[:, t:t + 1], (N, _LANES))


def _lanes(g: int):
    return slice(g * _LANES, (g + 1) * _LANES)


def _rows_of(tile_rows: int):
    return lax.broadcasted_iota(jnp.int32, (tile_rows, _LANES), 0)


def _fwd_kernel(x_ref, dt_ref, a_ref, bt_ref, ct_ref, d_ref, y_ref, sin_ref,
                s_ref, bexp, cexp):
    j, c = pl.program_id(1), pl.program_id(2)
    steps, bc = x_ref.shape[1], x_ref.shape[2]
    groups = bc // _LANES

    @pl.when(c == 0)
    def _():
        _expand(bt_ref, bexp)
        _expand(ct_ref, cexp)

    @pl.when(j == 0)
    def _():  # S_0 = 0
        s_ref[c] = jnp.zeros(s_ref.shape[1:], _F32)

    sin_ref[0, 0] = s_ref[c]
    a = a_ref[...]
    skip = d_ref[...]
    rows = _rows_of(_ROWS)

    def some_steps(i, s):
        r0 = pl.multiple_of(i * _ROWS, _ROWS)
        dts = [dt_ref[0, pl.ds(r0, _ROWS), _lanes(g)] for g in range(groups)]
        xs = [
            x_ref[0, pl.ds(r0, _ROWS), _lanes(g)].astype(_F32)
            for g in range(groups)
        ]
        ys = [jnp.zeros((_ROWS, _LANES), _F32)] * groups
        s = list(s)
        for r in range(_ROWS):
            b, cc = bexp[r0 + r], cexp[r0 + r]
            for g in range(groups):
                dl, xv = dts[g][r:r + 1], xs[g][r:r + 1]
                s[g] = jnp.exp(dl * a[:, _lanes(g)]) * s[g] + (dl * xv) * b
                y = jnp.sum(s[g] * cc, axis=0, keepdims=True)
                y = y + skip[:, _lanes(g)] * xv
                ys[g] = jnp.where(rows == r, y, ys[g])
        for g in range(groups):
            y_ref[0, pl.ds(r0, _ROWS), _lanes(g)] = ys[g].astype(y_ref.dtype)
        return tuple(s)

    s = lax.fori_loop(
        0, steps // _ROWS, some_steps,
        tuple(s_ref[c, :, _lanes(g)] for g in range(groups)),
    )
    for g in range(groups):
        s_ref[c, :, _lanes(g)] = s[g]


def _bwd_kernel(x_ref, dt_ref, a_ref, bt_ref, ct_ref, dy_ref, sin_ref,
                dx_ref, ddt_ref, da_ref, db_ref, dc_ref,
                ds_ref, st_ref, bexp, cexp):
    j, c = pl.program_id(1), pl.program_id(2)  # time blocks from the end
    steps, bc = x_ref.shape[1], x_ref.shape[2]
    groups = bc // _LANES
    n_sub = steps // _ROWS

    @pl.when(c == 0)
    def _():
        _expand(bt_ref, bexp)
        _expand(ct_ref, cexp)
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    @pl.when(j == 0)
    def _():  # nothing reads the state after the row's end
        ds_ref[c] = jnp.zeros(ds_ref.shape[1:], _F32)

    a = a_ref[...]
    rows = _rows_of(_ROWS)

    def loads(ref, r0):
        return [
            ref[0, pl.ds(r0, _ROWS), _lanes(g)].astype(_F32)
            for g in range(groups)
        ]

    # the block's states again from the state that entered it: step t's
    # row of ``st_ref`` is S_{t-1}
    def remake(i, s):
        r0 = pl.multiple_of(i * _ROWS, _ROWS)
        dts, xs = loads(dt_ref, r0), loads(x_ref, r0)
        s = list(s)
        for r in range(_ROWS):
            b = bexp[r0 + r]
            for g in range(groups):
                st_ref[r0 + r, :, _lanes(g)] = s[g]
                dl, xv = dts[g][r:r + 1], xs[g][r:r + 1]
                s[g] = jnp.exp(dl * a[:, _lanes(g)]) * s[g] + (dl * xv) * b
        return tuple(s)

    lax.fori_loop(
        0, n_sub, remake,
        tuple(sin_ref[0, 0, :, _lanes(g)] for g in range(groups)),
    )

    def back(i, carry):
        ds, da = (list(t) for t in carry)
        r0 = pl.multiple_of((n_sub - 1 - i) * _ROWS, _ROWS)
        dts, xs, dys = loads(dt_ref, r0), loads(x_ref, r0), loads(dy_ref, r0)
        dxs = [jnp.zeros((_ROWS, _LANES), _F32)] * groups
        ddts = [jnp.zeros((_ROWS, _LANES), _F32)] * groups
        for r in reversed(range(_ROWS)):
            t = r0 + r
            b, cc = bexp[t], cexp[t]
            db_t = dc_t = None
            for g in range(groups):
                ag = a[:, _lanes(g)]
                dl, xv, dyv = (
                    dts[g][r:r + 1], xs[g][r:r + 1], dys[g][r:r + 1]
                )
                before = st_ref[t, :, _lanes(g)]
                decay = jnp.exp(dl * ag)
                dlx = dl * xv
                now = decay * before + dlx * b  # S_t
                grad = ds[g] + dyv * cc  # dL / dS_t
                part_c, part_b = dyv * now, grad * dlx
                dc_t = part_c if dc_t is None else dc_t + part_c
                db_t = part_b if db_t is None else db_t + part_b
                from_b = jnp.sum(grad * b, axis=0, keepdims=True)
                ds[g] = grad * decay  # dL / dS_{t-1}
                through = ds[g] * before  # dL / d(dt A)
                da[g] = da[g] + through * dl
                ddt = jnp.sum(through * ag, axis=0, keepdims=True)
                ddts[g] = jnp.where(rows == r, ddt + from_b * xv, ddts[g])
                dxs[g] = jnp.where(rows == r, from_b * dl, dxs[g])
            db_ref[0, t] += db_t
            dc_ref[0, t] += dc_t
        for g in range(groups):
            dx_ref[0, pl.ds(r0, _ROWS), _lanes(g)] = dxs[g].astype(
                dx_ref.dtype
            )
            ddt_ref[0, pl.ds(r0, _ROWS), _lanes(g)] = ddts[g]
        return tuple(ds), tuple(da)

    zeros = tuple(
        jnp.zeros((a.shape[0], _LANES), _F32) for _ in range(groups)
    )
    ds, da = lax.fori_loop(
        0, n_sub, back,
        (tuple(ds_ref[c, :, _lanes(g)] for g in range(groups)), zeros),
    )
    for g in range(groups):
        ds_ref[c, :, _lanes(g)] = ds[g]
        da_ref[0, 0, :, _lanes(g)] = da[g]


def _params():
    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=_flash._interpret_default(),
    )


def _transposed(m):
    """[B, T, N] -> [B, N, T] float32 (a few hundred kB)."""
    return jnp.swapaxes(m.astype(_F32), 1, 2)


def _fwd_call(x, dt, a, bm, cm, d):
    B, T, C = x.shape
    N = a.shape[1]
    bc, nb = _channel_block(C), T // _STEPS
    trace_counts.count("sscan_serial_steps", T)
    tokens = pl.BlockSpec((1, _STEPS, bc), lambda i, j, c: (i, j, c))
    matrix = pl.BlockSpec((N, bc), lambda i, j, c: (0, c))
    shared = pl.BlockSpec((1, N, _STEPS), lambda i, j, c: (i, 0, j))
    row = pl.BlockSpec((1, bc), lambda i, j, c: (0, c))
    boundary = pl.BlockSpec((1, 1, N, bc), lambda i, j, c: (i, j, 0, c))
    return pl.pallas_call(
        _fwd_kernel,
        name="sscan_fwd",
        grid=(B, nb, C // bc),
        in_specs=[tokens, tokens, matrix, shared, shared, row],
        out_specs=[tokens, boundary],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((B, nb, N, C), _F32),
        ],
        scratch_shapes=[
            pltpu.VMEM((C // bc, N, bc), _F32),
            pltpu.VMEM((_STEPS, N, _LANES), _F32),
            pltpu.VMEM((_STEPS, N, _LANES), _F32),
        ],
        **_params(),
    )(
        x, dt.astype(_F32), a.astype(_F32).T, _transposed(bm),
        _transposed(cm), d.astype(_F32).reshape(1, C),
    )


def _bwd_call(x, dt, a, bm, cm, d, sin, dy):
    B, T, C = x.shape
    N = a.shape[1]
    bc, nb = _channel_block(C), T // _STEPS
    # a block's states made again, then walked backwards
    trace_counts.count("sscan_serial_steps", 2 * T)
    tokens = pl.BlockSpec(
        (1, _STEPS, bc), lambda i, j, c: (i, nb - 1 - j, c)
    )
    matrix = pl.BlockSpec((N, bc), lambda i, j, c: (0, c))
    shared = pl.BlockSpec((1, N, _STEPS), lambda i, j, c: (i, 0, nb - 1 - j))
    boundary = pl.BlockSpec(
        (1, 1, N, bc), lambda i, j, c: (i, nb - 1 - j, 0, c)
    )
    spread = pl.BlockSpec(
        (1, _STEPS, N, _LANES), lambda i, j, c: (i, nb - 1 - j, 0, 0)
    )
    dx, ddt, da, db, dc = pl.pallas_call(
        _bwd_kernel,
        name="sscan_bwd",
        grid=(B, nb, C // bc),
        in_specs=[tokens, tokens, matrix, shared, shared, tokens, boundary],
        out_specs=[tokens, tokens, boundary, spread, spread],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(x.shape, _F32),
            jax.ShapeDtypeStruct((B, nb, N, C), _F32),
            jax.ShapeDtypeStruct((B, T, N, _LANES), _F32),
            jax.ShapeDtypeStruct((B, T, N, _LANES), _F32),
        ],
        scratch_shapes=[
            pltpu.VMEM((C // bc, N, bc), _F32),
            pltpu.VMEM((_STEPS, N, bc), _F32),
            pltpu.VMEM((_STEPS, N, _LANES), _F32),
            pltpu.VMEM((_STEPS, N, _LANES), _F32),
        ],
        **_params(),
    )(
        x, dt.astype(_F32), a.astype(_F32).T, _transposed(bm),
        _transposed(cm), dy, sin,
    )
    # the skip ``D x`` is the forward kernel's; its two gradients are
    # element-wise over arrays the backward kernel reads anyway
    dyf, xf = dy.astype(_F32), x.astype(_F32)
    dx = (dx.astype(_F32) + d.astype(_F32) * dyf).astype(x.dtype)
    return (
        dx, ddt.astype(dt.dtype),
        da.sum(axis=(0, 1)).T.astype(a.dtype),
        db.sum(axis=-1).astype(bm.dtype), dc.sum(axis=-1).astype(cm.dtype),
        jnp.sum(dyf * xf, axis=(0, 1)).astype(d.dtype),
    )


@jax.custom_vjp
def selective_scan_kernels(x, dt, a, bm, cm, d):
    """The recurrence at shapes ``fits`` takes (arguments as
    ``selective_scan_plain``'s)."""
    return _fwd_call(x, dt, a, bm, cm, d)[0]


def _scan_fwd(x, dt, a, bm, cm, d):
    y, sin = _fwd_call(x, dt, a, bm, cm, d)
    return y, (x, dt, a, bm, cm, d, sin)


def _scan_bwd(res, dy):
    return _bwd_call(*res, dy)


selective_scan_kernels.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(x, dt, a, bm, cm, d, chunk: int = 128, mesh=None):
    """One site of the recurrence (``common/trace_counts``:
    ``sscan_sites``, ``sscan_kernel_sites`` where the kernels take it, and
    ``sscan_serial_steps``: the steps the site's passes walk in order, T
    forward and 2 T backward, a chunk's or block's states made again and
    then walked from the end)."""
    in_kernels = fits(x, a, mesh)
    trace_counts.count("sscan_sites")
    trace_counts.count("sscan_kernel_sites", in_kernels)
    if in_kernels:
        return selective_scan_kernels(x, dt, a, bm, cm, d)
    trace_counts.count("sscan_serial_steps", 3 * x.shape[1])
    return selective_scan_plain(x, dt, a, bm, cm, d, chunk)


# ---------------------------------------------------------------------------
# the mixers
# ---------------------------------------------------------------------------
def selective_scan_mixer(u, p, cfg, mesh=None):
    """u [B, T, d] (already normed) -> (out [B, T, d], y [B, T, d_in]):
    the layer's output, and the scan's output before the gate, which a
    gated memory unit further up reads. ``mesh``: the mesh the step is
    sharded over, or None inside a region that names its own axes."""
    R, N = cfg.sscan_dt_rank, cfg.sscan_state
    act = u.dtype
    with jax.named_scope("scope/layer/sscan/in_proj"):
        x = u @ p["w_x"].astype(act)
        z = u @ p["w_z"].astype(act)
    with jax.named_scope("scope/layer/sscan/conv"):
        x = conv_silu(x, p["conv_w"], p["conv_b"], mesh)
    with jax.named_scope("scope/layer/sscan/x_proj"):
        dbc = x @ p["w_xproj"].astype(act)
        dt = jnp.dot(
            dbc[..., :R], p["w_dt"].astype(act),
            preferred_element_type=_F32,
        )
        dt = jax.nn.softplus(dt + p["dt_bias"].astype(_F32))
    with jax.named_scope("scope/layer/sscan/scan"):
        y = selective_scan(
            x, dt, -jnp.exp(p["A_log"].astype(_F32)), dbc[..., R:R + N],
            dbc[..., R + N:], p["D"], cfg.sscan_chunk, mesh,
        )
    with jax.named_scope("scope/layer/sscan/gate"):
        gated = jax.checkpoint(
            lambda y, z: (
                y.astype(_F32) * jax.nn.silu(z.astype(_F32))
            ).astype(act)
        )(y, z)
    with jax.named_scope("scope/layer/sscan/out_proj"):
        return gated @ p["w_out"].astype(act), y


def memory_unit_mixer(u, p, memory):
    """A gated memory unit (SambaY, arXiv:2507.06607): ``(silu(u W_in) *
    m) W_out`` with ``m`` a scan layer's output before its gate."""
    act = u.dtype
    with jax.named_scope("scope/layer/gmu/in_proj"):
        g = u @ p["w_in"].astype(act)
    with jax.named_scope("scope/layer/gmu/gate"):
        gated = jax.checkpoint(
            lambda g, m: (
                jax.nn.silu(g.astype(_F32)) * m.astype(_F32)
            ).astype(act)
        )(g, memory)
    with jax.named_scope("scope/layer/gmu/out_proj"):
        return gated @ p["w_out"].astype(act)
