"""The gated norm after every scan as Pallas kernels: ``weight *
RMSNorm_group(o) * gate(z)`` rounded once to the activation dtype, and its
whole backward (``ops/gated_delta.head_gated_rmsnorm`` and both orders of
``ops/mamba2.gated_group_rmsnorm`` state it and are the oracle). One
``jax.custom_vjp`` whose residuals are its inputs, what the plain
statement's ``jax.checkpoint`` keeps.

The three forms are one algorithm with three parameters that a call's own
arguments state: the group's width (a head's 128 lanes in the two
delta-rule forms, 512 in the Mamba-2 layer's; a head of 192 lanes, no
whole tiles, is walked two groups a span of three tiles, each group's mean
square a sum under a mask of its lanes: ``fits`` is the one place that
says which shapes run here), ``z``'s shape (``[B, T, C]``:
``silu(z)`` a channel; ``[B, T, C / group]``: ``sigmoid(z)`` a group) and
whether the gate is inside the norm (``RMSNorm(o * gate) * weight``) or
outside it (``RMSNorm(o) * weight * gate``).

A program is a batch element and a block of rows with every channel. The
kernel walks the block in sub-blocks of ``_ROWS`` rows and a sub-block
``_TILES`` lane tiles at a time (four heads of 128, or one group of 512),
both as loops, so that what it holds stays near the vector registers and
the body is a few groups of one sub-block. A block of ``o`` and of ``z``
comes in once, in the activation dtype. A gate a group is spread
over its group's lanes by the matrix unit, exactly (``z`` times a matrix of
zeros and ones). No float32 copy of the stretch, no broadcast of the gate
or the weight and no ``[..., H, d]`` array is ever an HBM array.

The backward kernel reads ``o``, ``z`` and ``dy`` once, makes the normed
rows again in VMEM and writes ``do`` and ``dz`` once (a gate a group: the
sum over its group's lanes, ``[B, T, C / group]``). The weight's gradient
adds up in float32 over the row blocks in the block of the output that
holds it, eight partial rows a batch element, and is written once; the sum
over those rows and the batch is left to XLA (a few hundred kB).

The precision is the plain statement's: float32 inside, the same ``eps``,
one rounding to the activation dtype at the output, ``do`` and ``dz``; the
weight's gradient float32. Only the order of the float32 sums over a group
and over rows differs.
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

from dlrover_tpu.ops.conv_kernels import _one_device

# ``dlrover_tpu.ops.flash_attention`` the attribute is the function
_flash = importlib.import_module("dlrover_tpu.ops.flash_attention")

_LANES = 128
_SUBLANES = 8
_F32 = jnp.float32
# rows a sub-block (a block of 32 rows is one): whole sublane tiles of
# float32 and of bfloat16
_ROWS = 64
# lane tiles of a sub-block the inner loop's body holds at once: four heads
# of 128 are four independent chains of a lane reduction, an ``rsqrt`` and a
# gate for the scheduler to overlap (PERF.md §6, PR 63: 32 rows of one head
# a trip took 2.5 times as long)
_TILES = 4
# the widest span of groups that are no whole tiles (``_span``)
_SPAN_TILES = 4
_ROW_BLOCKS = (512, 256, 128, 64, 32)
# the largest block of one array a program holds (twice: it comes in
# while the one before is worked on); the backward kernel holds five
_BLOCK_BYTES = 2 << 20
_VMEM_BYTES = 48 << 20


def _row_block(o) -> int:
    """The rows of a program's block of ``o`` [B, T, C]: the largest that
    divides T and stays under ``_BLOCK_BYTES``; 0 where none does."""
    _, T, C = o.shape
    room = _BLOCK_BYTES // (C * jnp.dtype(o.dtype).itemsize)
    return next((s for s in _ROW_BLOCKS if T % s == 0 and s <= room), 0)


def _span(group: int) -> int:
    """The lanes the walk takes at once: the fewest whole groups that are
    whole 128-lane tiles (a group of whole tiles: itself; 192: two groups,
    three tiles)."""
    return math.lcm(group, _LANES)


def fits(o, z, group: int, mesh=None) -> bool:
    """THE rule for which way the stretch is executed, read from its
    input: the kernels where the channels are whole spans (``_span``: a
    group of whole 128-lane tiles, or a group wider than a tile with the
    few that make whole tiles together, at most ``_SPAN_TILES`` of them;
    such a group only with a gate a channel), the rows are whole row
    blocks, ``z`` is a gate a channel or a gate a group in ``o``'s dtype,
    bfloat16 or float32, and one device owns the program (GSPMD refuses to
    partition a Mosaic call); the plain statement everywhere else."""
    if o.ndim != 3 or group <= 0 or o.shape[2] % group:
        return False
    per_group = (*o.shape[:2], o.shape[2] // group)
    whole = group % _LANES == 0
    return (
        jnp.dtype(o.dtype) in (jnp.dtype(jnp.bfloat16), jnp.dtype(_F32))
        and z.dtype == o.dtype
        and z.shape in ((o.shape, per_group) if whole else (o.shape,))
        and (whole or _LANES < group <= _span(group) <= _SPAN_TILES * _LANES)
        and o.shape[2] % _span(group) == 0
        and _row_block(o) > 0
        and _one_device(o, mesh)
    )


def _sub_block(bt: int) -> int:
    """The rows of a sub-block of a block of ``bt`` rows."""
    return min(_ROWS, bt)


def _spread(zs, g):
    """A gate a group -> the group's lanes: ``zs`` [rows, H] in the
    activation dtype times the [H, 128] matrix that is one in row ``g``,
    float32 and exact (a bfloat16 times one; a float32 in as many
    bfloat16 pieces as hold it)."""
    H = zs.shape[1]
    pick = (
        lax.broadcasted_iota(jnp.int32, (H, _LANES), 0) == g
    ).astype(zs.dtype)
    return jnp.dot(
        zs, pick, preferred_element_type=_F32,
        precision=lax.Precision.HIGHEST if zs.dtype == _F32 else None,
    )


def _group_mean(x, group: int):
    """The mean over each group's lanes of ``x`` [rows, span] float32:
    [rows, 1] where the span is one group, else [rows, span], a lane
    holding its own group's (sums under a mask of the group's lanes)."""
    span = x.shape[-1]
    if group == span:
        return jnp.mean(x, axis=-1, keepdims=True)
    lane = lax.broadcasted_iota(jnp.int32, (1, span), 1)
    mean = jnp.zeros_like(x)
    for u in range(span // group):
        inside = (lane >= u * group) & (lane < (u + 1) * group)
        total = jnp.sum(jnp.where(inside, x, 0.0), axis=-1, keepdims=True)
        mean = jnp.where(inside, total / group, mean)
    return mean


def _rsqrt_mean_square(x, eps: float, group: int):
    """[rows, span] float32 -> [rows, 1] (``_group_mean``'s shape)."""
    return lax.rsqrt(_group_mean(x * x, group) + eps)


def _group(o_ref, z_ref, w_ref, rows, lanes, g, inside: bool):
    """One group of a sub-block, float32: ``o``, the gate and its
    derivative in ``z`` (the sigmoid a group where ``z`` holds one gate a
    group, SiLU a channel), the weight's lanes, and what the norm is taken
    of."""
    o = o_ref[0, rows, lanes].astype(_F32)
    if z_ref.shape[2] != o_ref.shape[2]:
        s = jax.nn.sigmoid(_spread(z_ref[0, rows, :], g))
        gate, dgate_dz = s, s * (1.0 - s)
    else:
        z = z_ref[0, rows, lanes].astype(_F32)
        s = jax.nn.sigmoid(z)
        gate, dgate_dz = z * s, s * (1.0 + z * (1.0 - s))
    return o, gate, dgate_dz, w_ref[:, lanes], o * gate if inside else o


def _walk(bt: int, groups: int, width: int, body, carry0=0, done=None):
    """``body(rows, lanes, g, carry) -> carry`` over every group of every
    sub-block of a block of ``bt`` rows: the sub-block's rows, the group's
    lanes and the group. ``carry0`` starts every sub-block and ``done(rows,
    carry)`` ends it. A trip of the inner loop holds ``_TILES`` lane tiles,
    whole groups written out one after the other (the TPU's lowering
    unrolls a loop whole or not at all)."""
    held = math.gcd(max(_TILES * _LANES // width, 1), groups)
    sub = _sub_block(bt)

    def sub_block(i, _):
        rows = pl.ds(pl.multiple_of(i * sub, sub), sub)

        def trip(j, carry):
            for u in range(held):
                g = j * held + u
                lanes = pl.ds(pl.multiple_of(g * width, _LANES), width)
                carry = body(rows, lanes, g, carry)
            return carry

        carry = lax.fori_loop(0, groups // held, trip, carry0)
        if done is not None:
            done(rows, carry)
        return 0

    lax.fori_loop(0, bt // sub, sub_block, 0)


def _fwd_kernel(o_ref, z_ref, w_ref, y_ref, *, width, group, eps, inside):
    bt, C = o_ref.shape[1:]

    def body(rows, lanes, g, carry):
        _, gate, _, w, x = _group(o_ref, z_ref, w_ref, rows, lanes, g, inside)
        y = x * _rsqrt_mean_square(x, eps, group) * w
        if not inside:
            y = y * gate
        y_ref[0, rows, lanes] = y.astype(y_ref.dtype)
        return carry

    _walk(bt, C // width, width, body)


def _bwd_kernel(
    o_ref, z_ref, w_ref, dy_ref, do_ref, dz_ref, dw_ref, *, width, group,
    eps, inside,
):
    bt, C = o_ref.shape[1:]
    sub = _sub_block(bt)
    H = z_ref.shape[2]
    a_group = H != C

    @pl.when(pl.program_id(1) == 0)
    def _():  # the sums' start
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def body(rows, lanes, g, dz_rows):
        o, gate, dgate_dz, w, x = _group(
            o_ref, z_ref, w_ref, rows, lanes, g, inside
        )
        dy = dy_ref[0, rows, lanes].astype(_F32)
        r = _rsqrt_mean_square(x, eps, group)
        n = x * r
        if inside:
            dw, dn = dy * n, dy * w
        else:
            dw, dn = dy * n * gate, dy * w * gate
        dw_ref[0, :, lanes] += dw.reshape(
            sub // _SUBLANES, _SUBLANES, width
        ).sum(axis=0)
        dx = r * (dn - n * _group_mean(dn * n, group))
        if inside:
            do, dz = dx * gate, dx * o * dgate_dz
        else:
            do, dz = dx, dy * n * w * dgate_dz
        do_ref[0, rows, lanes] = do.astype(do_ref.dtype)
        if not a_group:
            dz_ref[0, rows, lanes] = dz.astype(dz_ref.dtype)
            return dz_rows
        # a gate a group: the sum over its lanes lands in column ``g``
        column = lax.broadcasted_iota(jnp.int32, (sub, H), 1) == g
        return jnp.where(
            column, jnp.sum(dz, axis=-1, keepdims=True), dz_rows
        )

    def done(rows, dz_rows):
        dz_ref[0, rows, :] = dz_rows.astype(dz_ref.dtype)

    if a_group:
        _walk(
            bt, C // width, width, body, jnp.zeros((sub, H), _F32), done
        )
    else:
        _walk(bt, C // width, width, body)


def _specs(o, z):
    B, T, C = o.shape
    bt = _row_block(o)
    tokens = pl.BlockSpec((1, bt, C), lambda i, j: (i, j, 0))
    gates = pl.BlockSpec((1, bt, z.shape[2]), lambda i, j: (i, j, 0))
    row = pl.BlockSpec((1, C), lambda i, j: (0, 0))
    return (B, T // bt), tokens, gates, row


def _params(interpret: bool):
    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES,
        ),
        interpret=interpret,
    )


_STATIC = ("width", "eps", "inside", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _fwd_call(o, z, w, *, width, eps, inside, interpret):
    """One jit for every call site: a program lowers the kernel once a
    (shape, form) and calls it once a site."""
    grid, tokens, gates, row = _specs(o, z)
    return pl.pallas_call(
        functools.partial(
            _fwd_kernel, width=_span(width), group=width, eps=eps,
            inside=inside,
        ),
        name="gated_norm_fwd",
        grid=grid,
        in_specs=[tokens, gates, row],
        out_specs=tokens,
        out_shape=jax.ShapeDtypeStruct(o.shape, o.dtype),
        **_params(interpret),
    )(o, z, w.astype(_F32).reshape(1, -1))


@functools.partial(jax.jit, static_argnames=_STATIC)
def _bwd_call(o, z, w, dy, *, width, eps, inside, interpret):
    B, _, C = o.shape
    grid, tokens, gates, row = _specs(o, z)
    do, dz, dw = pl.pallas_call(
        functools.partial(
            _bwd_kernel, width=_span(width), group=width, eps=eps,
            inside=inside,
        ),
        name="gated_norm_bwd",
        grid=grid,
        in_specs=[tokens, gates, row, tokens],
        out_specs=[
            tokens, gates,
            pl.BlockSpec((1, _SUBLANES, C), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(o.shape, o.dtype),
            jax.ShapeDtypeStruct(z.shape, z.dtype),
            jax.ShapeDtypeStruct((B, _SUBLANES, C), _F32),
        ],
        **_params(interpret),
    )(o, z, w.astype(_F32).reshape(1, -1), dy)
    return do, dz, dw.sum(axis=(0, 1)).astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def gated_norm(o, z, w, width: int, eps: float, inside: bool):
    """``RMSNorm_group(o) * w * gate(z)`` (``inside``: ``RMSNorm_group(o *
    gate(z)) * w``) in ``o``'s dtype, the mean square over each ``width``
    lanes of the last axis: o [B, T, C] in the activation dtype, w [C], z
    [B, T, C] (the gate is ``silu(z)``) or [B, T, C / width] (``sigmoid(z)``
    a group), at shapes ``fits`` takes."""
    return _fwd_call(
        o, z, w, width=width, eps=eps, inside=inside,
        interpret=_flash._interpret_default(),
    )


def _gated_norm_fwd(o, z, w, width, eps, inside):
    return gated_norm(o, z, w, width, eps, inside), (o, z, w)


def _gated_norm_bwd(width, eps, inside, res, dy):
    return _bwd_call(
        *res, dy, width=width, eps=eps, inside=inside,
        interpret=_flash._interpret_default(),
    )


gated_norm.defvjp(_gated_norm_fwd, _gated_norm_bwd)
