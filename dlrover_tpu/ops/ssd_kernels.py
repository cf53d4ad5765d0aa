"""The Mamba-2 chunked scan as two Pallas kernels: ``ops/mamba2.ssd_chunked``
with the ``D`` skip and the rounding to the activation dtype that follow it
in the mixer, and its whole backward (``ssd_chunked`` states the scan and is
the oracle). One ``jax.custom_vjp``: what it keeps is its operands and the
float32 state that entered each chunk (134 MB a layer at the Nemotron
cell's shape: 0.33 ms to write and read back at the HBM's peak, against a
second sweep of ``x`` and ``B`` through the states' products inside the
backward call, which was not built).

A program is one chunk of one ``B`` / ``C`` group of one batch element; the
chunks are the grid's last, serial dimension. It reads the group's ``x``
``[Q, rep P]``, ``B`` and ``C`` ``[Q, N]`` token-major, as blocks of the
row-major ``[B, T, .]`` arrays the convolution wrote, and writes ``y``
``[Q, rep P]`` the same way: no head-major copy of ``x`` or ``y`` is ever an
HBM array. ``C B^T`` is made once a group. A head's decay square ``L =
exp(cum_i - cum_j)`` under the lower triangle and ``M = L * C B^T`` rounded
to the activation dtype live in VMEM only; the float32 states of the
group's ``rep`` heads stay in a VMEM scratch from the first chunk to the
last (``S <- exp(cum_Q) S + (to_end * dt x)^T B``: the statement's closed
form over the chunk states is this recurrence summed in another order).

**Heads share lane tiles.** A head of 64 channels is half a 128-lane tile,
and every token-shaped float32 array of one head would be half-empty vector
registers. The kernels walk a group one lane tile at a time (``_tile``): the
heads that fill it (two of 64; one of 128 or more) side by side in one
``[Q, W]`` array whose lanes know their head, read and written as whole
tiles with no lane shift. Only a head's squares are its own: ``M_h`` times
the tile's ``dt x`` gives head ``h``'s columns of ``y`` on head ``h``'s
lanes and is dropped on the others (a select; the matrix unit's pass is as
long for 64 columns as for 128). What a head's state adds, ``C S^T``, and
the state's update, ``w^T B``, are ONE product a tile, the heads' states
stacked along the rows; so are ``dC``, ``dB`` and the states' cotangent,
whose sums over a tile's heads the product's contraction makes (a head at a
time takes 1.86 / 6.70 ms a call forward / forward + backward at the
Nemotron cell's shape where this takes 0.90 / 3.22: PERF.md §7).

A head's step ``dt`` and in-chunk cumulative log-decay are the two small
float32 arrays XLA makes outside as the statement does (``cum``: the
``highest`` einsum); a program reads ``cum`` twice, as rows over the chunk's
positions (head-major ``[rep, Q]``: a position is a lane) and, with ``dt``
and the chunk's whole sum beside it, as columns (token-major ``[Q, 3 rep]``:
a position is a sublane), so that no kernel turns a row into a column.

The reversed kernel walks the same grid from the last chunk with the
cotangent of the states in the scratch. From a chunk's ``x``, ``B``, ``C``,
the decays, the entered states and ``dy`` it makes ``L``, ``M`` and ``dt x``
again in VMEM and writes ``dx``, ``dB`` and ``dC`` (summed over the group's
heads in the program) token-major, and the small float32 cotangents a
position of ``cum`` and of ``dt`` as columns, and of ``D`` a channel summed
over the chunk. **The decay's cotangent is read off the tokens, not off the
squares**: everything a position reads scales with ``exp(cum_i)`` and
everything it writes with ``exp(-cum_i)``, so ``dcum_i = <dy_i, y_i> -
<d(dt x)_i, (dt x)_i>``, one float32 sum over a head's lanes (what a chunk's
own square adds to the two cancels over the chunk term by term, as the row
and column sums of ``dL * L`` do), and the chunk's last position takes what
the leaving state's two factors add. The reverse cumulative sum to ``d(dt
a)``, ``da`` and the sum to ``dD`` are XLA's, over ``[H, T]`` float32.

The precision is the statement's: operands in the activation dtype,
float32 accumulation, decays, their sums and the carried state float32,
every rounding where the statement rounds (``dt x``, ``M``, the entered
state as a matmul operand, ``to_end``); a float32 cotangent is rounded to
the activation dtype where it is a matmul's operand, as the XLA transpose
rounds it at default precision. ``dB`` and ``dC`` add up in float32 and are
rounded once.
"""

from __future__ import annotations

import functools
import importlib
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.conv_kernels import _one_device

# ``dlrover_tpu.ops.flash_attention`` the attribute is the function
_flash = importlib.import_module("dlrover_tpu.ops.flash_attention")

_LANES = 128
_F32 = jnp.float32
_HI = lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_TN = (((0,), (0,)), ((), ()))  # a^T @ b
# the longest chunk (its float32 squares are [Q, Q]), the widest group
# (``rep P`` lanes: a tile's body is written out once a tile) and the
# narrowest head (four a tile, four squares at once) a program takes
_MAX_CHUNK = 256
_MAX_GROUP_LANES = 8 * _LANES
_MIN_HEAD = _LANES // 4
_VMEM_BYTES = 64 << 20


def fits(x, dt, Bm, Cm, chunk: int, mesh=None) -> bool:
    """THE rule for which way the scan is executed, read from its operands
    (x [B, T, H, P], dt [B, T, H], Bm, Cm [B, T, G, N]): the kernels where
    the activations are bfloat16 or float32 and ``dt`` float32, a chunk is
    whole 128-lane tiles (its positions are the lanes of a decay square) and
    the sequence whole chunks, the state's width ``N`` and a group's
    ``rep P`` channels are whole lane tiles (a group's blocks of the
    token-major arrays), a head is a quarter, a half or whole tiles (the
    heads of a tile are worked on side by side) and no longer than a chunk,
    and one device owns the program (GSPMD refuses to partition a Mosaic
    call); the plain statement everywhere else."""
    if x.ndim != 4 or Bm.ndim != 4 or Bm.shape != Cm.shape:
        return False
    _, T, H, P = x.shape
    G, N = Bm.shape[2:]
    if dt.shape != x.shape[:3] or Bm.shape[:2] != x.shape[:2] or H % G:
        return False
    group = H // G * P
    return (
        jnp.dtype(x.dtype) in (jnp.dtype(jnp.bfloat16), jnp.dtype(_F32))
        and Bm.dtype == Cm.dtype == x.dtype
        and dt.dtype == _F32
        and chunk % _LANES == 0
        and chunk <= _MAX_CHUNK
        and T % chunk == 0
        and N % _LANES == 0
        and group % _LANES == 0
        and group <= _MAX_GROUP_LANES
        and 2 * (H // G) <= _LANES
        and (P % _LANES == 0 or (_LANES % P == 0 and P >= _MIN_HEAD))
        and P <= chunk
        and _one_device(x, mesh)
    )


def _dot(a, b, dims=_NN):
    return lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _lower(Q: int):
    """``i >= j`` over a chunk's square (row i, column j)."""
    row = lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    return row >= col


def _heads_a_tile(P: int) -> int:
    """The heads a program works on side by side: as many as fill a
    128-lane tile (two of 64), or one of whole tiles."""
    return max(1, _LANES // P)


class _Tile(NamedTuple):
    """What both kernels make of one lane tile of a program's group, ``t``
    heads of ``P`` channels side by side: the statement's values of one
    chunk, in VMEM. A head's squares are a list's entries; what is
    token-shaped is one ``[Q, t P]`` array whose lanes know their head."""

    lanes: slice  # the tile's channels in the group's block
    heads: range
    segs: tuple  # a head's lanes, [1, W] masks (None: one head)
    L: list  # [Q, Q] float32, zero above the diagonal
    M: list  # L * C B^T in the activation dtype
    x: jax.Array  # [Q, W] float32
    dt: jax.Array
    xdt: jax.Array  # activation dtype
    from_start: jax.Array  # exp(cum), float32
    to_end: jax.Array  # exp(cum_Q - cum) in the activation dtype, float32
    w: jax.Array  # to_end * xdt, activation dtype
    total: jax.Array  # [W, 1]: exp(cum_Q) of the head a state's row is of
    skip: jax.Array  # [1, W]


def _tile(x_ref, rows_ref, cols_ref, d_ref, k: int, lower, cb) -> _Tile:
    rep, Q = rows_ref.shape
    P = x_ref.shape[1] // rep
    t = _heads_a_tile(P)
    W = t * P
    act = x_ref.dtype
    lanes = slice(k * W, (k + 1) * W)
    heads = range(k * t, (k + 1) * t)
    lane = lax.broadcasted_iota(jnp.int32, (1, W), 1)
    row = lax.broadcasted_iota(jnp.int32, (W, 1), 0)

    def column(which, j):  # [Q, 1]
        return cols_ref[:, which * rep + j:which * rep + j + 1]

    def spread(which):
        """A head's column over its head's lanes: [Q, W]."""
        out = jnp.broadcast_to(column(which, heads[0]), (Q, W))
        for i, j in enumerate(heads[1:], 1):
            out = jnp.where(lane >= i * P, column(which, j), out)
        return out

    dt, lam, last = spread(0), spread(1), spread(2)
    L = [
        jnp.exp(jnp.where(
            lower, column(1, j) - rows_ref[j:j + 1, :], -jnp.inf
        ))
        for j in heads
    ]
    x = x_ref[:, lanes].astype(_F32)
    xdt = (x * dt).astype(act)
    to_end = jnp.exp(last - lam).astype(act)
    total = jnp.exp(column(2, heads[0])[:W])
    for i, j in enumerate(heads[1:], 1):
        total = jnp.where(row >= i * P, jnp.exp(column(2, j)[:W]), total)
    return _Tile(
        lanes, heads,
        tuple(
            (lane >= i * P) & (lane < (i + 1) * P) if t > 1 else None
            for i in range(t)
        ),
        L, [(sq * cb).astype(act) for sq in L], x, dt, xdt, jnp.exp(lam),
        to_end.astype(_F32), to_end * xdt, total, d_ref[:, lanes],
    )


def _tiles(x_ref, rows_ref) -> range:
    """The lane tiles of a program's group."""
    P = x_ref.shape[1] // rows_ref.shape[0]
    return range(x_ref.shape[1] // (_heads_a_tile(P) * P))


def _by_head(tile: _Tile, made):
    """``made(i)`` [Q, W] for each head of the tile -> the array that holds
    head ``i``'s on head ``i``'s lanes."""
    out = made(0)
    for i in range(1, len(tile.heads)):
        out = jnp.where(tile.segs[i], made(i), out)
    return out


def _of_head(tile: _Tile, i: int, x):
    """``x`` [., W] on head ``i``'s lanes, zero on the others."""
    return x if tile.segs[i] is None else jnp.where(tile.segs[i], x, 0.0)


def _fwd_kernel(x_ref, b_ref, c_ref, rows_ref, cols_ref, d_ref, y_ref,
                *rest, keep: bool):
    sin_ref, s_ref = rest if keep else (None, *rest)
    Q = rows_ref.shape[1]
    act = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    Bc, Cc = b_ref[...], c_ref[...]
    cb = _dot(Cc, Bc, _NT)
    lower = _lower(Q)
    for k in _tiles(x_ref, rows_ref):
        h = _tile(x_ref, rows_ref, cols_ref, d_ref, k, lower, cb)
        S = s_ref[h.lanes, :]  # [W, N]: the tile's heads' states
        if keep:
            sin_ref[h.lanes, :] = S
        y = (
            _by_head(h, lambda i: _dot(h.M[i], h.xdt))
            + h.from_start * _dot(Cc, S.astype(act), _NT)
            + h.skip * h.x
        )
        y_ref[:, h.lanes] = y.astype(act)
        s_ref[h.lanes, :] = h.total * S + _dot(h.w, Bc, _TN)


def _bwd_kernel(x_ref, b_ref, c_ref, rows_ref, cols_ref, d_ref, sin_ref,
                dy_ref, dx_ref, db_ref, dc_ref, dcols_ref, dskip_ref,
                ds_ref):
    rep, Q = rows_ref.shape
    act = x_ref.dtype
    P = x_ref.shape[1] // rep

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    Bc, Cc = b_ref[...], c_ref[...]
    cb = _dot(Cc, Bc, _NT)
    lower = _lower(Q)
    column = lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    at_last = lax.broadcasted_iota(jnp.int32, (Q, 1), 0) == Q - 1
    dcb = jnp.zeros((Q, Q), _F32)
    dB = jnp.zeros(Bc.shape, _F32)
    dC = jnp.zeros(Cc.shape, _F32)
    # a position's cotangents of ``cum`` and ``dt``, a head a lane
    cols = jnp.zeros((Q, _LANES), _F32)

    def over_lanes(x):
        return jnp.sum(x, axis=1, keepdims=True)

    def over_rows(x):
        return jnp.sum(x, axis=0, keepdims=True)

    for k in _tiles(x_ref, rows_ref):
        h = _tile(x_ref, rows_ref, cols_ref, d_ref, k, lower, cb)
        dyf = dy_ref[:, h.lanes].astype(_F32)
        dy = [_of_head(h, i, dyf).astype(act) for i in range(len(h.heads))]
        S = sin_ref[h.lanes, :]  # the states that entered, float32
        Sb = S.astype(act)
        dS = ds_ref[h.lanes, :]  # of the states that LEFT this chunk
        dSb = dS.astype(act)
        # inside the chunk: y += M (dt x)
        for i in range(len(h.heads)):
            dcb = dcb + _dot(dy[i], h.xdt, _NT) * h.L[i]
        dxdt = _by_head(h, lambda i: _dot(h.M[i], dy[i], _TN))
        # the entered state: y += exp(cum) (C S^T)
        ys = _by_head(h, lambda i: _dot(h.M[i], h.xdt)) + (
            h.from_start * _dot(Cc, Sb, _NT)
        )
        dz = (h.from_start * dyf).astype(act)
        dC = dC + _dot(dz, Sb)
        # the state that leaves: S' = exp(cum_Q) S + w^T B
        dw = _dot(Bc, dSb, _NT)
        dB = dB + _dot(h.w, dSb)
        dxdt = dxdt + dw * h.to_end
        decayed = h.total * dS
        ds_ref[h.lanes, :] = decayed + _dot(dz, Cc, _TN)
        # the decays. Everything a position reads scales with exp(cum_i)
        # and everything it writes with exp(-cum_i): the cotangent of cum_i
        # is <dy_i, y_i> less <d(dt x)_i, (dt x)_i> (one sum: what a chunk's
        # square adds to the two cancels over the chunk term by term), and
        # the chunk's last position takes what the leaving state's two
        # factors add
        both = dyf * ys - dxdt * h.xdt.astype(_F32)
        writes = dxdt * h.x
        kept = decayed * S  # [W, N]
        sent = over_rows(dw * h.w.astype(_F32))  # [1, W]
        for i, j in enumerate(h.heads):
            at_end = over_lanes(_of_head(h, i, sent)) + over_lanes(
                over_rows(kept[i * P:(i + 1) * P])
            )
            dlam = over_lanes(_of_head(h, i, both)) + jnp.where(
                at_last, at_end, 0.0
            )
            cols = jnp.where(column == j, dlam, cols)
            cols = jnp.where(
                column == rep + j, over_lanes(_of_head(h, i, writes)), cols
            )
        dskip_ref[:, h.lanes] = over_rows(dyf * h.x)
        dx_ref[:, h.lanes] = (dxdt * h.dt + h.skip * dyf).astype(act)
    dcb = dcb.astype(act)
    db_ref[...] = (dB + _dot(dcb, Cc, _TN)).astype(act)
    dc_ref[...] = (dC + _dot(dcb, Bc)).astype(act)
    dcols_ref[...] = cols[:, :2 * rep]


class _Dims(NamedTuple):
    B: int
    T: int
    H: int
    P: int
    G: int
    N: int
    nc: int
    Q: int
    rep: int


def _dims(x, Bm, cum) -> _Dims:
    B, T, H, P = x.shape
    G, N = Bm.shape[2:]
    return _Dims(B, T, H, P, G, N, cum.shape[1], cum.shape[3], H // G)


def _layouts(x, Bm, Cm, dt, cum, D):
    """The operands as the kernels read them: tokens as they lie, the
    decays' rows ``[B, nc, G, rep, Q]`` and columns ``[B, G, T, 3 rep]``
    (``dt | cum | cum`` at its chunk's end), ``D`` spread over its head's
    lanes."""
    d = _dims(x, Bm, cum)
    last = jnp.broadcast_to(cum[..., -1:], cum.shape)
    cols = jnp.concatenate(
        [dt.reshape(d.B, d.T, d.G, d.rep)] + [
            jnp.swapaxes(rows, 2, 3).reshape(d.B, d.T, d.G, d.rep)
            for rows in (cum, last)
        ], axis=-1,
    )
    return (
        x.reshape(d.B, d.T, d.H * d.P), Bm.reshape(d.B, d.T, d.G * d.N),
        Cm.reshape(d.B, d.T, d.G * d.N),
        cum.reshape(d.B, d.nc, d.G, d.rep, d.Q), jnp.moveaxis(cols, 2, 1),
        jnp.repeat(D.astype(_F32), d.P).reshape(d.G, 1, d.rep * d.P),
    )


class _Specs(NamedTuple):
    grid: tuple
    tokens: Callable  # (width) -> a group's columns of a [B, T, .] array
    cols: Callable  # (arrays side by side) -> of a [B, G, T, .] array
    operands: list  # of ``_layouts``' six
    states: pl.BlockSpec  # [B, nc, G, rep P, N]: a head's rows of a group's
    sums: pl.BlockSpec  # [B, nc, G, 1, rep P]: a chunk's sum a channel


def _specs(d: _Dims, reverse: bool) -> _Specs:
    """A program's blocks: chunk ``c`` of group ``g`` of batch element
    ``b``, the chunks walked from the last where ``reverse``."""
    def at(c):
        return d.nc - 1 - c if reverse else c

    def tokens(width):
        return pl.BlockSpec(
            (None, d.Q, width), lambda b, g, c: (b, at(c), g)
        )

    def cols(k):
        return pl.BlockSpec(
            (None, None, d.Q, k * d.rep), lambda b, g, c: (b, g, at(c), 0)
        )

    def a_chunk(*tail):  # of a [B, nc, G, *tail] array
        return pl.BlockSpec(
            (None, None, None, *tail), lambda b, g, c: (b, at(c), g, 0, 0)
        )

    group = d.rep * d.P
    return _Specs(
        (d.B, d.G, d.nc), tokens, cols,
        [
            tokens(group), tokens(d.N), tokens(d.N), a_chunk(d.rep, d.Q),
            cols(3), pl.BlockSpec((None, 1, group), lambda b, g, c: (g, 0, 0)),
        ],
        a_chunk(group, d.N), a_chunk(1, group),
    )


def _call(kernel, name, d: _Dims, sp: _Specs, interpret, in_specs, outs):
    """One of the two kernels: ``outs`` its ``(block, shape, dtype)``s."""
    return pl.pallas_call(
        kernel,
        name=name,
        grid=sp.grid,
        in_specs=in_specs,
        out_specs=[spec for spec, _, _ in outs],
        out_shape=[
            jax.ShapeDtypeStruct(shape, dtype) for _, shape, dtype in outs
        ],
        scratch_shapes=[pltpu.VMEM((d.rep * d.P, d.N), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES,
        ),
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("keep", "interpret"))
def _fwd_call(x, Bm, Cm, dt, cum, D, *, keep, interpret):
    """One jit for every call site (``gated_norm_kernels._fwd_call``'s
    reason): a program traces and lowers the kernel once a shape and calls
    it once a layer. ``keep``: the entered states are a result too."""
    d = _dims(x, Bm, cum)
    sp = _specs(d, False)
    group = d.rep * d.P
    outs = [(sp.tokens(group), (d.B, d.T, d.H * d.P), x.dtype)]
    if keep:
        outs.append((sp.states, (d.B, d.nc, d.G, group, d.N), _F32))
    y, *states = _call(
        functools.partial(_fwd_kernel, keep=keep), "ssd_scan_fwd", d, sp,
        interpret, sp.operands, outs,
    )(*_layouts(x, Bm, Cm, dt, cum, D))
    return (y.reshape(x.shape), *states)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _bwd_call(x, Bm, Cm, dt, cum, D, states, dy, *, interpret):
    d = _dims(x, Bm, cum)
    sp = _specs(d, True)
    act, group = x.dtype, d.rep * d.P
    dx, dB, dC, dcols, dskip = _call(
        _bwd_kernel, "ssd_scan_bwd", d, sp, interpret,
        sp.operands + [sp.states, sp.tokens(group)],
        [
            (sp.tokens(group), (d.B, d.T, d.H * d.P), act),
            (sp.tokens(d.N), (d.B, d.T, d.G * d.N), act),
            (sp.tokens(d.N), (d.B, d.T, d.G * d.N), act),
            (sp.cols(2), (d.B, d.G, d.T, 2 * d.rep), _F32),
            (sp.sums, (d.B, d.nc, d.G, 1, group), _F32),
        ],
    )(
        *_layouts(x, Bm, Cm, dt, cum, D), states,
        dy.astype(act).reshape(d.B, d.T, d.H * d.P),
    )
    # a position's two cotangents, token-major [B, T, H] each
    dlam, ddt = jnp.split(
        jnp.moveaxis(dcols, 1, 2).reshape(d.B, d.T, d.G, 2, d.rep), 2,
        axis=3,
    )
    return (
        dx.reshape(x.shape), dB.reshape(Bm.shape), dC.reshape(Cm.shape),
        ddt.reshape(d.B, d.T, d.H),
        jnp.swapaxes(dlam.reshape(d.B, d.nc, d.Q, d.H), 2, 3),
        dskip.reshape(d.B * d.nc, d.H, d.P).sum(axis=(0, 2)).astype(D.dtype),
    )


@jax.custom_vjp
def _scan(x, Bm, Cm, dt, cum, D):
    (y,) = _fwd_call(
        x, Bm, Cm, dt, cum, D, keep=False,
        interpret=_flash._interpret_default(),
    )
    return y


def _scan_fwd(x, Bm, Cm, dt, cum, D):
    y, states = _fwd_call(
        x, Bm, Cm, dt, cum, D, keep=True,
        interpret=_flash._interpret_default(),
    )
    return y, (x, Bm, Cm, dt, cum, D, states)


def _scan_bwd(res, dy):
    return _bwd_call(*res, dy, interpret=_flash._interpret_default())


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd(x, dt, a, Bm, Cm, D, chunk: int):
    """``(ssd_chunked(x, dt, a, Bm, Cm, chunk) + D x)`` rounded once to
    ``x``'s dtype, [B, T, H, P], at shapes ``fits`` takes: x [B, T, H, P],
    Bm, Cm [B, T, G, N] in the activation dtype, dt [B, T, H] (after
    softplus), a [H] (negative) and D [H] float32. The log-decays' running
    sum inside a chunk is the statement's own, made here and differentiated
    by JAX; the kernels take it and ``dt`` as they are."""
    B, T, H, _ = x.shape
    Q = chunk
    la = jnp.swapaxes((dt * a).reshape(B, T // Q, Q, H), 2, 3)  # <= 0
    cum = jnp.einsum(
        "bchj,ji->bchi", la, jnp.triu(jnp.ones((Q, Q), _F32)),
        precision=_HI,
    )
    return _scan(x, Bm, Cm, dt, cum, D)
