"""The convolution stretch before every scan as Pallas kernels:
``silu(causal_conv1d(x, w, b))`` rounded once to the activation dtype, and
its whole backward (``ops/mamba2.causal_conv1d`` states the convolution and
is the oracle). One ``jax.custom_vjp`` whose residuals are its inputs, what
the plain statement's ``jax.checkpoint`` keeps.

A program is a batch element, a block of channels (whole 128-lane tiles)
and a block of time steps; time is the innermost, sequential grid axis, and
inside a block the kernel walks sub-blocks of ``_ROWS`` steps so that what
it holds stays near the vector registers. A block of ``x`` comes in once,
in the activation dtype. A tap's operand is the float32 block rolled down
the sublanes, with the rows before the block spliced on top: the last rows
of the block before (kept in a VMEM scratch from one grid step to the next;
zeros at a row's start). No float32 copy of ``x``, no padded array and no
shifted slice is ever an HBM array.

The backward kernel walks time from the end. It makes ``pre`` again (the
rows before a block come as a second, 16-row block of ``x``), ``dpre = dy *
silu'(pre)``, and ``dx_t = sum_k w[k] dpre_{t + (K-1) - k}`` with the rows
AFTER the block carried from the grid step before (zeros at a row's end).
``dw[k] = sum_t x_{t-(K-1)+k} dpre_t`` and ``db = sum_t dpre_t`` add up in
float32 in a VMEM scratch over the time blocks and are written once a
batch element and channel block; the sum over the batch is left to XLA (a
few hundred kB).

The precision is the plain statement's: float32 inside, one rounding to
the activation dtype at the output and at ``dx``, ``dw`` and ``db``
float32. Only the order of the float32 sums over time differs.
"""

from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# ``dlrover_tpu.ops.flash_attention`` the attribute is the function
_flash = importlib.import_module("dlrover_tpu.ops.flash_attention")

_LANES = 128
_F32 = jnp.float32
# rows a sub-block: whole sublane tiles of float32 and of bfloat16
_ROWS = 32
# what the rows before (after) a sub-block are read in: one bfloat16 tile,
# of which the float32 halo keeps the last (first) ``_HALO`` rows
_TILE, _HALO = 16, 8
_TIME_BLOCKS = (512, 256, 128, 64, 32)
_CHANNEL_BLOCKS = (4 * _LANES, 2 * _LANES, _LANES)


def _blocks(x):
    """The time block and the channel block of ``x`` [B, T, C]: the largest
    that divides; 0 where none does."""
    def largest(n, sizes):
        return next((s for s in sizes if n % s == 0), 0)

    _, T, C = x.shape
    return largest(T, _TIME_BLOCKS), largest(C, _CHANNEL_BLOCKS)


def _one_device(x, mesh) -> bool:
    """Whether the program being traced belongs to one device: a mesh of
    one, or no mesh and no axis left to GSPMD around us (a region whose
    types track varying axes keeps the plain statement too)."""
    if mesh is not None:
        return mesh.size == 1
    auto = jax.sharding.get_abstract_mesh().auto_axes
    return not auto and not jax.typeof(x).vma


def fits(x, w, mesh=None) -> bool:
    """THE rule for which way the stretch is executed, read from its
    input: the kernels where the channels are whole 128-lane tiles, the
    sequence is whole time blocks, the taps are at most 8 (the halo is one
    float32 sublane tile) and one device owns the program (GSPMD refuses
    to partition a Mosaic call); the plain statement everywhere else."""
    return (
        x.ndim == 3
        and jnp.dtype(x.dtype) in (jnp.dtype(jnp.bfloat16), jnp.dtype(_F32))
        and w.shape[0] <= _HALO
        and all(_blocks(x))
        and _one_device(x, mesh)
    )


def _taps(ext, K: int):
    """``x_{t-(K-1)+k}`` for k < K over a sub-block's steps, from ``ext`` =
    the ``_HALO`` rows before it and the sub-block, float32."""
    return [
        (pltpu.roll(ext, K - 1 - k, 0) if k < K - 1 else ext)[_HALO:]
        for k in range(K)
    ]


def _pre(taps, w, b):
    pre = taps[0] * w[0:1]
    for k in range(1, len(taps)):
        pre = pre + taps[k] * w[k:k + 1]
    return pre if b is None else pre + b


def _with_rows_before(before, cur):
    """[``_TILE`` rows before | the sub-block] in the activation dtype ->
    float32 with ``_HALO`` rows before."""
    both = jnp.concatenate([before, cur], axis=0).astype(_F32)
    return both[_TILE - _HALO:]


def _fwd_kernel(*refs, K: int, bias: bool):
    if bias:
        x_ref, w_ref, b_ref, o_ref, last_ref = refs
        b = b_ref[...]
    else:
        x_ref, w_ref, o_ref, last_ref = refs
        b = None
    bt = x_ref.shape[1]
    w = w_ref[...]

    @pl.when(pl.program_id(2) == 0)
    def _():  # zeros before a row's start
        last_ref[...] = jnp.zeros_like(last_ref)

    def sub(ext, r0):
        y = _pre(_taps(ext, K), w, b)
        o_ref[0, pl.ds(r0, _ROWS), :] = (y * jax.nn.sigmoid(y)).astype(
            o_ref.dtype
        )

    sub(_with_rows_before(last_ref[...], x_ref[0, 0:_ROWS, :]), 0)

    def body(i, _):
        r0 = pl.multiple_of(i * _ROWS, _ROWS)
        ext = x_ref[0, pl.ds(r0 - _TILE, _ROWS + _TILE), :].astype(_F32)
        sub(ext[_TILE - _HALO:], r0)
        return 0

    if bt > _ROWS:  # (a loop of no trips is still traced)
        lax.fori_loop(1, bt // _ROWS, body, 0)
    last_ref[...] = x_ref[0, bt - _TILE:bt, :]


def _bwd_kernel(*refs, K: int, bias: bool):
    if bias:
        (x_ref, before_ref, dy_ref, w_ref, b_ref, dx_ref, dw_ref, db_ref,
         after_ref, acc_ref) = refs
        b = b_ref[...]
    else:
        (x_ref, before_ref, dy_ref, w_ref, dx_ref, dw_ref, after_ref,
         acc_ref) = refs
        b = None
    bt = x_ref.shape[1]
    w = w_ref[...]
    j = pl.program_id(2)  # time blocks from the row's end
    last = pl.num_programs(2) - 1

    @pl.when(j == 0)
    def _():  # zeros after a row's end, and the sums' start
        after_ref[...] = jnp.zeros_like(after_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def fold(k, prod):  # [rows, bc] -> the tap's [_HALO, bc] partial sums
        acc_ref[k] += prod.reshape(_ROWS // _HALO, _HALO, -1).sum(axis=0)

    def sub(ext, r0, after):
        """One sub-block; ``after`` = ``dpre`` of the ``_HALO`` rows that
        follow it. Returns its own first ``_HALO`` rows of ``dpre``."""
        taps = _taps(ext, K)
        y = _pre(taps, w, b)
        s = jax.nn.sigmoid(y)
        dpre = dy_ref[0, pl.ds(r0, _ROWS), :].astype(_F32) * (
            s * (1.0 + y * (1.0 - s))
        )
        for k in range(K):
            fold(k, taps[k] * dpre)
        if bias:
            fold(K, dpre)
        both = jnp.concatenate([dpre, after], axis=0)
        n = both.shape[0]
        dx = dpre * w[K - 1:K]
        for k in range(K - 1):  # row t takes dpre of row t + (K-1) - k
            dx = dx + pltpu.roll(both, n - (K - 1 - k), 0)[:_ROWS] * w[k:k + 1]
        dx_ref[0, pl.ds(r0, _ROWS), :] = dx.astype(dx_ref.dtype)
        return dpre[:_HALO]

    def body(i, after):
        r0 = pl.multiple_of(bt - (i + 1) * _ROWS, _ROWS)
        ext = x_ref[0, pl.ds(r0 - _TILE, _ROWS + _TILE), :].astype(_F32)
        return sub(ext[_TILE - _HALO:], r0, after)

    after = after_ref[...]
    if bt > _ROWS:
        after = lax.fori_loop(0, bt // _ROWS - 1, body, after)
    # the block's first sub-block: the rows before it are the block
    # before's (zeros before a row's start)
    before = before_ref[0]
    before = jnp.where(j == last, jnp.zeros_like(before), before)
    after_ref[...] = sub(
        _with_rows_before(before, x_ref[0, 0:_ROWS, :]), 0, after
    )

    @pl.when(j == last)
    def _():
        for k in range(K):
            dw_ref[0, k:k + 1, :] = acc_ref[k].sum(axis=0, keepdims=True)
        if bias:
            db_ref[0] = acc_ref[K].sum(axis=0, keepdims=True)


def _params():
    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=_flash._interpret_default(),
    )


def _fwd_call(x, w, b):
    B, T, C = x.shape
    K = w.shape[0]
    bt, bc = _blocks(x)
    tokens = pl.BlockSpec((1, bt, bc), lambda i, c, j: (i, j, c))
    taps = pl.BlockSpec((K, bc), lambda i, c, j: (0, c))
    row = pl.BlockSpec((1, bc), lambda i, c, j: (0, c))
    ins = [x, w.astype(_F32)]
    if b is not None:
        ins.append(b.astype(_F32).reshape(1, C))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, K=K, bias=b is not None),
        name="conv_silu_fwd",
        grid=(B, C // bc, T // bt),
        in_specs=[tokens, taps] + [row] * (b is not None),
        out_specs=tokens,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((_TILE, bc), x.dtype)],
        **_params(),
    )(*ins)


def _bwd_call(x, w, b, dy):
    B, T, C = x.shape
    K = w.shape[0]
    bt, bc = _blocks(x)
    nt, tiles = T // bt, bt // _TILE
    tokens = pl.BlockSpec((1, bt, bc), lambda i, c, j: (i, nt - 1 - j, c))
    # the tile of ``x`` before the block (the first block reads its own
    # first tile and the kernel puts zeros in its place)
    before = pl.BlockSpec(
        (1, _TILE, bc),
        lambda i, c, j: (i, jnp.maximum((nt - 1 - j) * tiles - 1, 0), c),
    )
    taps = pl.BlockSpec((K, bc), lambda i, c, j: (0, c))
    row = pl.BlockSpec((1, bc), lambda i, c, j: (0, c))
    sums = [pl.BlockSpec((1, K, bc), lambda i, c, j: (i, 0, c))]
    shapes = [jax.ShapeDtypeStruct((B, K, C), _F32)]
    ins = [x, x, dy, w.astype(_F32)]
    if b is not None:
        ins.append(b.astype(_F32).reshape(1, C))
        sums.append(pl.BlockSpec((1, 1, bc), lambda i, c, j: (i, 0, c)))
        shapes.append(jax.ShapeDtypeStruct((B, 1, C), _F32))
    dx, *dwb = pl.pallas_call(
        functools.partial(_bwd_kernel, K=K, bias=b is not None),
        name="conv_silu_bwd",
        grid=(B, C // bc, nt),
        in_specs=[tokens, before, tokens, taps] + [row] * (b is not None),
        out_specs=[tokens] + sums,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)] + shapes,
        scratch_shapes=[
            pltpu.VMEM((_HALO, bc), _F32),
            pltpu.VMEM((K + (b is not None), _HALO, bc), _F32),
        ],
        **_params(),
    )(*ins)
    dw = dwb[0].sum(axis=0).astype(w.dtype)
    if b is None:
        return dx, dw, None
    return dx, dw, dwb[1].sum(axis=(0, 1)).astype(b.dtype)


@jax.custom_vjp
def conv_silu(x, w, b=None):
    """``silu(causal_conv1d(x, w, b))`` in ``x``'s dtype: x [B, T, C] in
    the activation dtype, w [K, C], b [C] or None, at shapes ``fits``
    takes."""
    return _fwd_call(x, w, b)


def _conv_silu_fwd(x, w, b):
    return _fwd_call(x, w, b), (x, w, b)


def _conv_silu_bwd(res, dy):
    return _bwd_call(*res, dy)


conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)
