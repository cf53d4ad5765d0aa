"""The gated delta rule's chunk-local work as Pallas kernels
(``ops/gated_delta.py`` states the rule and is the oracle): what a chunk
computes before the serial pass (``wy``: decays, ``K K^T``, the unit
triangle's inverse -> ``U, W, delta, a``) and after it (``read_out``: the
masked ``Q K^T`` against the chunk's own ``V'`` plus the entered state),
each with its backward pass written by hand. A program is one
``(batch, key head, run of chunks)``; the ``r`` value heads a key head
serves are stacked along the rows, so a chunk's squares are ONE
``[r C, r C]`` float32 array with a head's ``[C, C]`` on the block
diagonal (``[128, 128]`` at 2 x 64: whole vector registers and one MXU
tile), made, used and dropped in VMEM. A backward kernel makes its squares
again from the inputs the forward had; no square is ever a residual.

Layouts are the neighbours': ``q, k, v`` are read token-major
``[B, T, heads * d]`` in blocks of ``(C, d)`` (a head is a lane tile),
``U, W, K, delta, a`` are written chunk-major as ``chunk_state_pass`` takes
them, ``o`` token-major as the gate does. ``beta`` and ``g`` (and their
cotangents) travel as rows ``[n, B, H_k, 1, r C]``. A head that is no
whole lane tiles (96 / 192) is no lane block Mosaic takes, and comes
head-major instead, ``[B, H_k, T, d_k]`` and ``[B, H_k, r, T, d_v]``, a
head's width the block's whole minor dimension; the chunk-major arrays
hold the stated widths as they are. ``fits`` is the one place that says
which shapes run here and in which layout; the kernels' bodies read either
through ``_head`` and are otherwise one code. The scalar kind's inverse is
the product form, or (``halves``: a write strength scaled past 1) the one
by halves of the second half of this file.

A decay that is a vector over the key's channels (Kimi Delta Attention,
``g [B, T, H d_k]``) has the same two stretches as kernels of its own,
``wy_channel`` and ``read_out_channel`` (``gdn_channel_*``, the second half
of this file): there every key head serves its one value head, the square
stacks two consecutive chunks of a head, the decayed operands of a masked
square are made in VMEM in row blocks of 16 steps, and the inverse is the
one by halves.

The serial pass between the two stretches, either kind's, is two kernels of
its own (``state_pass`` / ``state_pass_rev``, the third part of this file):
a head's float32 state stays in VMEM across a layer's chunks.

The precision is the plain statement's: decays, their sums and the inverse
float32, the inverse's products at ``Precision.HIGHEST``; every other
matmul takes the activation dtype and accumulates in float32 (a float32
cotangent is rounded to it as the XLA transpose rounds it at default
precision). Two exponents are zero by construction, the diagonal of the
read-out's decay square and what is left of a chunk after its last
position; they are constants here too, so no cotangent flows through them
(PERF.md, Findings PR 43).
"""

from __future__ import annotations

import functools
import importlib
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.common import trace_counts

# ``dlrover_tpu.ops.flash_attention`` the attribute is the function
_flash = importlib.import_module("dlrover_tpu.ops.flash_attention")

_LANES = 128
_F32 = jnp.float32
_HI = lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_TN = (((0,), (0,)), ((), ()))  # a^T @ b
# chunks a program: amortises the grid step's ~0.35 us over more work
_CHUNKS_A_PROGRAM = (8, 4, 2, 1)


_QUARTER = _LANES // 4


def whole_tiles(d_k: int, d_v: int) -> bool:
    """A key and a value head are whole 128-lane tiles: the kernels read
    ``[B, T, heads * d]`` as it lies, a head a lane block."""
    return d_k % _LANES == 0 and d_v % _LANES == 0


def head_lanes(d_k: int, d_v: int) -> int:
    """The lanes a key and a value head take in a kernel's blocks: their
    widths in whole tiles, which is what Mosaic makes of a block's minor
    dimension (96 + 192 stated are 128 + 256 held)."""
    return sum(-(-d // _LANES) * _LANES for d in (d_k, d_v))


def fits(d_k: int, d_v: int, chunk: int, T: int, dtype,
         channel: bool = False) -> bool:
    """THE rule for which way the chunk-local work is executed, read from
    the shapes alone: the kernels where a key and a value head are whole
    quarters of a 128-lane tile, a chunk is whole sublane tiles of the
    activation dtype (8 rows of float32, 16 of bfloat16) and the sequence
    is whole chunks; the plain ``jax.numpy`` statement everywhere else.
    Heads of whole tiles (``whole_tiles``) are read token-major, ``[B, T,
    heads * d]`` in lane blocks of a head. Any other width (96 / 192) is
    no lane block Mosaic takes: the caller hands ``q, k, v`` head-major,
    ``[B, H_k, T, d_k]`` and ``[B, H_k, r, T, d_v]``, a head's width the
    block's whole minor dimension, and takes ``o`` and the cotangents
    back so; every array keeps the stated widths in HBM as XLA sees it,
    and a block's lanes past them are Mosaic's own padding in VMEM
    (``head_lanes``). The same shapes serve both kinds of decay, each
    with kernels of its own: one scalar a head and step (``wy``,
    ``read_out``: squares from ``exp(gamma_i - gamma_j)`` of scalars) or
    a vector over the key's channels (``channel``), ``g [B, T, H, d_k]``
    (``wy_channel``, ``read_out_channel``: heads of whole tiles only,
    token-major)."""
    sublanes = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    widths = (
        whole_tiles(d_k, d_v) if channel
        else d_k % _QUARTER == 0 and d_v % _QUARTER == 0
    )
    return widths and chunk % sublanes == 0 and T % chunk == 0


def _dot(a, b, dims=_NN, precision=None):
    return lax.dot_general(
        a, b, dims, precision=precision, preferred_element_type=_F32
    )


class _Geometry(NamedTuple):
    """Masks of the stacked ``[r C, r C]`` square (row i, column j)."""

    eye: jax.Array
    same: jax.Array  # i and j are positions of one value head
    below: jax.Array  # ... and j < i
    upto: jax.Array  # ... and j <= i
    above: jax.Array  # ... and j > i


def _geometry(C: int, r: int) -> _Geometry:
    N = r * C
    row = lax.broadcasted_iota(jnp.int32, (N, N), 0)
    col = lax.broadcasted_iota(jnp.int32, (N, N), 1)

    def within(j):  # both positions are value head j's
        lo, hi = j * C, (j + 1) * C
        return (row >= lo) & (row < hi) & (col >= lo) & (col < hi)

    same = functools.reduce(jnp.logical_or, map(within, range(r)))
    return _Geometry(
        row == col, same, same & (col < row), same & (col <= row),
        same & (col > row),
    )


def _as_col(x_row, geo):
    """[1, N] -> [N, 1], exactly (a sum with zeros)."""
    return jnp.sum(jnp.where(geo.eye, x_row, 0.0), axis=1, keepdims=True)


def _as_row(x_col, geo):
    return jnp.sum(jnp.where(geo.eye, x_col, 0.0), axis=0, keepdims=True)


def _gammas(g_row, geo):
    """The running sum of ``g`` inside its chunk, as a column and as the
    same numbers in a row."""
    gamma_col = jnp.sum(jnp.where(geo.upto, g_row, 0.0), axis=1, keepdims=True)
    return gamma_col, _as_row(gamma_col, geo)


def _decay_below(gamma_col, gamma_row, geo):
    """``exp(gamma_i - gamma_j)`` strictly below a head's diagonal, else 0."""
    return jnp.exp(jnp.where(geo.below, gamma_col - gamma_row, -jnp.inf))


def _unit_lower_inverses(As, geo, C: int):
    """``(I - A)^{-1}`` of each ``A`` as ``gated_delta.unit_lower_inverse``
    forms it (a head's block is nilpotent of order ``C`` and the blocks do
    not mix), the chunks of a program side by side: a chunk's products
    wait for one another, another chunk's do not."""
    eye = geo.eye.astype(_F32)
    Ts, powers, reach = [A + eye for A in As], list(As), 2
    while reach < C:
        powers = [_dot(p, p, precision=_HI) for p in powers]
        Ts = [T + _dot(T, p, precision=_HI) for T, p in zip(Ts, powers)]
        reach *= 2
    return Ts


def _width(ref, r: int) -> int:
    """A value head's width in a block of a key head's ``r`` value heads:
    token-major ``[rows, r d]`` or head-major ``[r, rows, d]``."""
    return ref.shape[-1] if ref.ndim == 3 else ref.shape[-1] // r


def _head(ref, rows, j: int, d: int):
    """Where value head ``j``'s ``rows`` lie in such a block."""
    if ref.ndim == 3:
        return (j, rows, slice(None))
    return (rows, slice(j * d, (j + 1) * d))


def _stack(ref, rows, r: int, d: int):
    """A block's ``r`` value heads -> ``[r C, d]``."""
    return jnp.concatenate(
        [ref[_head(ref, rows, j, d)] for j in range(r)], axis=0
    )


def _fold(x2, r: int, C: int):
    """The sum of the ``r`` row blocks of ``[r C, d]``."""
    return sum(x2[j * C:(j + 1) * C] for j in range(1, r)) + x2[:C]


class _WySquares(NamedTuple):
    k2: jax.Array  # [N, d_k] act, the key head once a value head
    v2: jax.Array  # [N, d_v] float32
    beta_col: jax.Array
    g_col: jax.Array
    gamma_row: jax.Array
    E: jax.Array  # the decay square, strictly lower
    kk: jax.Array
    A: jax.Array
    T: jax.Array
    c_row: jax.Array  # beta * exp(gamma)


def _wy_squares(k_ref, v_ref, beta_ref, g_ref, geo, C, r, m, halves):
    """The squares of each of a program's ``m`` chunks; the inverse by
    halves (``_inverses_by_halves``) where ``halves``."""
    d_v = _width(v_ref, r)
    made = []
    for c in range(m):
        rows = slice(c * C, (c + 1) * C)
        beta_row, g_row = beta_ref[c], g_ref[c]
        k2 = jnp.concatenate([k_ref[rows, :]] * r, axis=0)
        gamma_col, gamma_row = _gammas(g_row, geo)
        E = _decay_below(gamma_col, gamma_row, geo)
        kk = _dot(k2, k2, _NT)
        beta_col = _as_col(beta_row, geo)
        made.append(_WySquares(
            k2, _stack(v_ref, rows, r, d_v).astype(_F32), beta_col,
            _as_col(g_row, geo), gamma_row, E, kk, -((beta_col * kk) * E),
            None, beta_row * jnp.exp(gamma_row),
        ))
    As = [s.A for s in made]
    if halves:
        Ts = _inverses_by_halves(As, _channel_geometry(C, r), C)
    else:
        Ts = _unit_lower_inverses(As, geo, C)
    return [s._replace(T=T) for s, T in zip(made, Ts)]


def _left_and_total(g_col, geo):
    """What is left of the chunk after each position (summed as such: the
    last position's is an empty sum) and the whole chunk's sum, as rows."""
    left = jnp.sum(jnp.where(geo.below, g_col, 0.0), axis=0, keepdims=True)
    total = jnp.sum(jnp.where(geo.same, g_col, 0.0), axis=0, keepdims=True)
    return left, total


def _wy_fwd_kernel(k_ref, v_ref, beta_ref, g_ref,
                   u_ref, w_ref, kc_ref, delta_ref, a_ref,
                   *, C, r, m, halves):
    act = k_ref.dtype
    geo = _geometry(C, r)
    squares = _wy_squares(
        k_ref, v_ref, beta_ref, g_ref, geo, C, r, m, halves
    )
    for c, s in enumerate(squares):
        U = _dot(s.T.astype(act), (s.v2 * s.beta_col).astype(act))
        W = _dot((s.T * s.c_row).astype(act), s.k2).astype(act)
        for j in range(r):
            u_ref[c, j] = U[j * C:(j + 1) * C]
            w_ref[c, j] = W[j * C:(j + 1) * C]
        kc_ref[c] = s.k2[:C]
        left, total = _left_and_total(s.g_col, geo)
        delta_ref[c] = jnp.exp(left)
        a_ref[c] = jnp.exp(total)


def _wy_bwd_kernel(k_ref, v_ref, beta_ref, g_ref,
                   du_ref, dw_ref, dkc_ref, ddelta_ref, da_ref,
                   dk_ref, dv_ref, dbeta_ref, dg_ref, *, C, r, m, halves):
    act = k_ref.dtype
    d_v = _width(v_ref, r)
    geo = _geometry(C, r)
    squares = _wy_squares(
        k_ref, v_ref, beta_ref, g_ref, geo, C, r, m, halves
    )
    for c, s in enumerate(squares):
        rows = slice(c * C, (c + 1) * C)
        dU = jnp.concatenate([du_ref[c, j] for j in range(r)], 0).astype(act)
        dW = jnp.concatenate([dw_ref[c, j] for j in range(r)], 0)
        Tb, M = s.T.astype(act), (s.T * s.c_row).astype(act)
        vb = (s.v2 * s.beta_col).astype(act)
        # U = T vb and W = M k
        dvb = _dot(Tb, dU, _TN)
        dM = _dot(dW, s.k2, _NT)
        dk2 = _dot(M, dW, _TN)
        dT = _dot(dU, vb, _NT) + dM * s.c_row
        dc_row = jnp.sum(dM * s.T, axis=0, keepdims=True)
        # T = (I - A)^{-1}: dA = T^T dT T^T, and A lives below the diagonal
        dA = _dot(s.T, _dot(dT, s.T, _NT, _HI), _TN, _HI)
        dA = jnp.where(geo.below, dA, 0.0)
        # A = -(beta_i kk_ij) E_ij, E = exp(gamma_i - gamma_j)
        dAE = -(dA * s.E)
        dkk = dAE * s.beta_col
        dkk = dkk.astype(act)  # of kk = k k^T: both of its sides
        dk2 = dk2 + _dot(dkk, s.k2) + _dot(dkk, s.k2, _TN)
        P = dA * s.A
        dbeta_col = (
            jnp.sum(dAE * s.kk, axis=1, keepdims=True)
            + jnp.sum(dvb * s.v2, axis=1, keepdims=True)
        )
        dbeta_row = dc_row * jnp.exp(s.gamma_row)
        dgamma_col = jnp.sum(P, axis=1, keepdims=True)
        dgamma_row = dc_row * s.c_row - jnp.sum(P, axis=0, keepdims=True)
        # delta = exp(left), a = exp(total), gamma: all sums of g
        left, total = _left_and_total(s.g_col, geo)
        dleft_col = _as_col(ddelta_ref[c] * jnp.exp(left), geo)
        dgamma_col = dgamma_col + _as_col(dgamma_row, geo)
        dg_row = (
            da_ref[c] * jnp.exp(total)
            + jnp.sum(
                jnp.where(geo.upto, dgamma_col, 0.0), axis=0, keepdims=True
            )
            + jnp.sum(
                jnp.where(geo.above, dleft_col, 0.0), axis=0, keepdims=True
            )
        )
        dk_ref[rows, :] = (
            _fold(dk2, r, C) + dkc_ref[c].astype(_F32)
        ).astype(act)
        dv = (dvb * s.beta_col).astype(act)
        for j in range(r):
            dv_ref[_head(dv_ref, rows, j, d_v)] = dv[j * C:(j + 1) * C]
        dbeta_ref[c] = dbeta_row + _as_row(dbeta_col, geo)
        dg_ref[c] = dg_row


class _ReadSquares(NamedTuple):
    q2: jax.Array
    k2: jax.Array
    Vn2: jax.Array
    qk: jax.Array
    D: jax.Array  # the decay square with its diagonal of ones
    eg_col: jax.Array  # exp(gamma)
    entered: jax.Array  # [N, d_v] float32, before its decay


def _read_squares(q, k, g_row, vn_ref, s_ref, c, geo, r) -> _ReadSquares:
    q2 = jnp.concatenate([q] * r, axis=0)
    k2 = jnp.concatenate([k] * r, axis=0)
    gamma_col, gamma_row = _gammas(g_row, geo)
    D = _decay_below(gamma_col, gamma_row, geo) + geo.eye.astype(_F32)
    return _ReadSquares(
        q2, k2, jnp.concatenate([vn_ref[c, j] for j in range(r)], axis=0),
        _dot(q2, k2, _NT), D, jnp.exp(gamma_col),
        jnp.concatenate([_dot(q, s_ref[c, j]) for j in range(r)], axis=0),
    )


def _read_fwd_kernel(q_ref, k_ref, g_ref, vn_ref, s_ref, o_ref, *, C, r, m):
    act = k_ref.dtype
    d_v = vn_ref.shape[-1]
    geo = _geometry(C, r)
    for c in range(m):
        rows = slice(c * C, (c + 1) * C)
        s = _read_squares(
            q_ref[rows, :], k_ref[rows, :], g_ref[c], vn_ref, s_ref, c, geo, r
        )
        o = _dot((s.qk * s.D).astype(act), s.Vn2) + s.entered * s.eg_col
        o = o.astype(act)
        for j in range(r):
            o_ref[_head(o_ref, rows, j, d_v)] = o[j * C:(j + 1) * C]


def _read_bwd_kernel(q_ref, k_ref, g_ref, vn_ref, s_ref, do_ref,
                     dq_ref, dk_ref, dg_ref, dvn_ref, ds_ref, *, C, r, m):
    act = k_ref.dtype
    d_v = vn_ref.shape[-1]
    geo = _geometry(C, r)
    for c in range(m):
        rows = slice(c * C, (c + 1) * C)
        q = q_ref[rows, :]
        s = _read_squares(
            q, k_ref[rows, :], g_ref[c], vn_ref, s_ref, c, geo, r
        )
        dob = _stack(do_ref, rows, r, d_v)
        do = dob.astype(_F32)
        # own = P V', P = (Q K^T) * D
        dvn = _dot((s.qk * s.D).astype(act), dob, _TN).astype(act)
        dP = _dot(dob, s.Vn2, _NT)
        dqk = (dP * s.D).astype(act)
        dq2 = _dot(dqk, s.k2)
        dk2 = _dot(dqk, s.q2, _TN)
        # D's diagonal is a constant: only what lies below reaches gamma
        R = jnp.where(geo.below, dP * s.qk * s.D, 0.0)
        # entered * exp(gamma), entered = q S
        de = (do * s.eg_col).astype(act)
        dq = _fold(dq2, r, C)
        for j in range(r):
            de_j = de[j * C:(j + 1) * C]
            dq = dq + _dot(de_j, s_ref[c, j], _NT)
            ds_ref[c, j] = _dot(q, de_j, _TN).astype(act)
            dvn_ref[c, j] = dvn[j * C:(j + 1) * C]
        dgamma_col = (
            jnp.sum(R, axis=1, keepdims=True)
            + jnp.sum(do * s.entered, axis=1, keepdims=True) * s.eg_col
            - _as_col(jnp.sum(R, axis=0, keepdims=True), geo)
        )
        dq_ref[rows, :] = dq.astype(act)
        dk_ref[rows, :] = _fold(dk2, r, C).astype(act)
        dg_ref[c] = jnp.sum(
            jnp.where(geo.upto, dgamma_col, 0.0), axis=0, keepdims=True
        )


class _Shape(NamedTuple):
    B: int
    n: int  # chunks
    Hk: int
    r: int
    C: int
    d_k: int
    d_v: int
    m: int  # chunks a program
    # ``q, k`` [B, H_k, T, d_k] and ``v, o`` [B, H_k, r, T, d_v] (heads
    # that are no whole tiles, ``fits``), not [B, T, heads * d]
    head_major: bool = False

    @property
    def grid(self):
        return (self.B, self.Hk, self.n // self.m)

    @property
    def rows(self):
        """A row of ``beta``, ``g``, ``delta`` ...: [n, B, H_k, 1, r C]."""
        return (self.n, self.B, self.Hk, 1, self.r * self.C)


def _shape(k, Hk: int, r: int, C: int, d_v: int) -> _Shape:
    if k.ndim == 4:
        B, _, T, d_k = k.shape
    else:
        B, T, key_lanes = k.shape
        d_k = key_lanes // Hk
    n = T // C
    m = next(m for m in _CHUNKS_A_PROGRAM if n % m == 0)
    return _Shape(B, n, Hk, r, C, d_k, d_v, m, k.ndim == 4)


def _value_width(v, Hk: int, r: int) -> int:
    return v.shape[-1] if v.ndim == 5 else v.shape[-1] // (Hk * r)


def _tokens(sh: _Shape, d: int):
    """A ``[B, T, heads * d]`` array: a program's run of chunks of one
    key head's (or its value heads') lanes."""
    return pl.BlockSpec((None, sh.m * sh.C, d), lambda b, h, i: (b, i, h))


def _keys(sh: _Shape):
    """``q`` or ``k``: a program's run of chunks of its key head."""
    if not sh.head_major:
        return _tokens(sh, sh.d_k)
    return pl.BlockSpec(
        (None, None, sh.m * sh.C, sh.d_k), lambda b, h, i: (b, h, i, 0)
    )


def _values(sh: _Shape):
    """``v`` or ``o``: ... of its key head's ``r`` value heads."""
    if not sh.head_major:
        return _tokens(sh, sh.r * sh.d_v)
    return pl.BlockSpec(
        (None, None, sh.r, sh.m * sh.C, sh.d_v),
        lambda b, h, i: (b, h, 0, i, 0),
    )


def _chunk_major(sh: _Shape, *tail):
    """A ``[n, B, H_k, *tail]`` array: a program's run of chunks, whole."""
    zeros = (0,) * len(tail)
    return pl.BlockSpec(
        (sh.m, None, None) + tail, lambda b, h, i: (i, b, h) + zeros
    )


def _like(*arrays):
    return [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in arrays]


def _call(kernel, name, sh: _Shape, in_specs, out_specs, out_shape, *ins,
          **static):
    return pl.pallas_call(
        functools.partial(kernel, C=sh.C, r=sh.r, m=sh.m, **static),
        name=name,
        grid=sh.grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=_flash._interpret_default(),
    )(*ins)


def _wy_specs(sh: _Shape):
    """The block specs of ``wy``'s arguments and of its results (which are
    its backward's cotangents in)."""
    r, C, row = sh.r, sh.C, _chunk_major(sh, 1, sh.r * sh.C)
    return (
        [_keys(sh), _values(sh), row, row],
        [
            _chunk_major(sh, r, C, sh.d_v), _chunk_major(sh, r, C, sh.d_k),
            _chunk_major(sh, C, sh.d_k), row, row,
        ],
    )


def _wy_call(k, v, beta, g, Hk, r, C, halves):
    sh = _shape(k, Hk, r, C, _value_width(v, Hk, r))
    # once a trace of ``wy``'s forward, the primal and the ``custom_vjp``
    # rule alike: where ``gated_delta._pass_forward`` counts a site
    # (``_channel_wy_call``'s reason), with the lanes a key and a value
    # head take in the blocks and those the model states
    trace_counts.count("gdn_kernel_sites")
    trace_counts.count("gdn_head_lanes", head_lanes(sh.d_k, sh.d_v))
    trace_counts.count("gdn_head_lanes_used", sh.d_k + sh.d_v)
    ins, outs = _wy_specs(sh)
    lead = sh.rows[:3]
    U, W, Kc, delta, a = _call(
        _wy_fwd_kernel, "gdn_chunk_wy_fwd", sh, ins, outs,
        [
            jax.ShapeDtypeStruct(lead + (r, C, sh.d_v), _F32),
            jax.ShapeDtypeStruct(lead + (r, C, sh.d_k), k.dtype),
            jax.ShapeDtypeStruct(lead + (C, sh.d_k), k.dtype),
            jax.ShapeDtypeStruct(sh.rows, _F32),
            jax.ShapeDtypeStruct(sh.rows, _F32),
        ],
        k, v, beta, g, halves=halves,
    )
    return U, W, Kc, delta.reshape(lead + (r, C)), a[..., 0, ::C]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def wy(k, v, beta, g, Hk: int, r: int, C: int, halves: bool = False):
    """What a chunk computes before the pass. ``k`` [B, T, H_k d_k] and
    ``v`` [B, T, H_v d_v] in the activation dtype (head-major where
    ``fits`` says so: [B, H_k, T, d_k] and [B, H_k, r, T, d_v]), ``beta``
    and ``g`` [n, B, H_k, 1, r C] float32 -> ``chunk_state_pass``'s
    arguments ``U, W, K, delta, a``, chunk axis first. ``halves``: the
    unit triangle's inverse by halves and not as a product
    (``gated_delta.unit_lower_inverse_blocked``)."""
    return _wy_call(k, v, beta, g, Hk, r, C, halves)


def _wy_fwd(k, v, beta, g, Hk, r, C, halves):
    return _wy_call(k, v, beta, g, Hk, r, C, halves), (k, v, beta, g)


def _wy_bwd(Hk, r, C, halves, res, cts):
    k, v, beta, g = res
    dU, dW, dKc, ddelta, da = cts
    sh = _shape(k, Hk, r, C, _value_width(v, Hk, r))
    ins, outs = _wy_specs(sh)
    return _call(
        _wy_bwd_kernel, "gdn_chunk_wy_bwd", sh, ins + outs, ins, _like(*res),
        *res, dU, dW, dKc, ddelta.reshape(sh.rows),
        jnp.repeat(da, C, axis=-1).reshape(sh.rows), halves=halves,
    )


wy.defvjp(_wy_fwd, _wy_bwd)


def _read_specs(q, k, g, Vn, S_in):
    """The shape and the block specs of ``read_out``'s arguments."""
    _, _, Hk, r, C, d_v = Vn.shape
    sh = _shape(k, Hk, r, C, d_v)
    keys = _keys(sh)
    return sh, [
        keys, keys, _chunk_major(sh, 1, r * C),
        _chunk_major(sh, r, C, d_v), _chunk_major(sh, r, sh.d_k, d_v),
    ]


def _read_call(q, k, g, Vn, S_in):
    sh, ins = _read_specs(q, k, g, Vn, S_in)
    T = sh.n * sh.C
    shape = (
        (sh.B, sh.Hk, sh.r, T, sh.d_v) if sh.head_major
        else (sh.B, T, sh.Hk * sh.r * sh.d_v)
    )
    return _call(
        _read_fwd_kernel, "gdn_chunk_read_fwd", sh, ins, _values(sh),
        jax.ShapeDtypeStruct(shape, k.dtype), q, k, g, Vn, S_in,
    )


@jax.custom_vjp
def read_out(q, k, g, Vn, S_in):
    """What every position reads. ``q, k`` [B, T, H_k d_k] (head-major: [B,
    H_k, T, d_k]), ``g`` [n, B, H_k, 1, r C], ``V'`` [n, B, H_k, r, C, d_v]
    and the entered states [n, B, H_k, r, d_k, d_v] as the pass returns
    them -> ``o`` [B, T, H_v d_v] (head-major: [B, H_k, r, T, d_v]),
    accumulated in float32 and rounded once to the activation dtype."""
    return _read_call(q, k, g, Vn, S_in)


def _read_fwd(q, k, g, Vn, S_in):
    return _read_call(q, k, g, Vn, S_in), (q, k, g, Vn, S_in)


def _read_bwd(res, do):
    sh, ins = _read_specs(*res)
    return _call(
        _read_bwd_kernel, "gdn_chunk_read_bwd", sh,
        ins + [_values(sh)], ins, _like(*res), *res, do,
    )


read_out.defvjp(_read_fwd, _read_bwd)


# -- a decay that is a vector over the key's channels ------------------------
#
# ``gated_delta._wy_channel`` and ``_read_out_channel`` as kernels. Every
# key head serves its one value head, so a chunk's square is ``[C, C]``: a
# program is one ``(batch, head, run of chunks)`` and stacks ``s`` (two)
# CONSECUTIVE chunks of its head along the rows, which is how they already
# lie in the token-major block, into one block-diagonal ``[s C, s C]``
# square (``_geometry(C, s)``, a chunk where the scalar kind has a value
# head). ``g [B, T, H d_k]`` float32 travels token-major like ``k`` and its
# cotangent leaves the same way; ``beta`` as rows ``[n / s, B, H, 1, s C]``.
# The decay does not factor out of the sums over the key's channels: a
# masked square is made in row blocks of ``SUB_BLOCK`` steps from operands
# decayed in float32 before they are rounded, as ``_decayed_scores`` states
# it (and with its bound on the one exponent above 0), and the triangle's
# inverse is the one by halves (``unit_lower_inverse_blocked``): the product
# form inside diagonal blocks of 8, then ``T <- T + T A_level T`` with ``A``
# masked to the level's off-diagonal blocks, ten ``highest`` products on
# the whole square at a chunk of 64.

SUB_BLOCK = 16  # steps whose decay a vector-decay chunk may divide by
INVERSE_BASE = 8  # diagonal blocks the product form is still good for


class _ChannelGeometry(NamedTuple):
    """``_geometry(C, s)`` of the ``s`` stacked chunks, every row's
    position in its chunk, and the masks of the inverse by halves."""

    geo: _Geometry
    pos: jax.Array  # int32 [N, 1]
    base: jax.Array  # [N, N]: i and j in one diagonal block of the base
    levels: tuple  # of [N, N]: the off-diagonal blocks the level fills


def _channel_geometry(C: int, s: int) -> _ChannelGeometry:
    N = s * C
    geo = _geometry(C, s)

    def position(shape, axis):
        i = lax.broadcasted_iota(jnp.int32, shape, axis)
        return i - C * sum((i >= j * C).astype(jnp.int32) for j in range(1, s))

    prow, pcol = position((N, N), 0), position((N, N), 1)

    def block(p, size):  # size a power of two
        return lax.shift_right_logical(p, size.bit_length() - 1)

    base = min(INVERSE_BASE, C)
    levels, size = [], base
    while size < C:
        levels.append(
            geo.same & (block(prow, 2 * size) == block(pcol, 2 * size))
            & (block(prow, size) & 1 == 1) & (block(pcol, size) & 1 == 0)
        )
        size *= 2
    return _ChannelGeometry(
        geo, position((N, 1), 0),
        geo.below & (block(prow, base) == block(pcol, base)), tuple(levels),
    )


def _sum_inside(x, cg: _ChannelGeometry, C: int, after: bool = False,
                own: bool = True):
    """The running sum of the rows of ``x`` [N, d] inside their chunk, up
    to each position (from it on where ``after``) and with it (without
    where not ``own``): float32 adds of rows rolled by 1, 2, 4 ... and
    masked at the chunk's ends, no matmul. ``gamma`` is the sum up to and
    with, what is left of the chunk the sum after and without (summed as
    such: the last position's is an empty sum), and each is the other's
    cotangent's way back."""
    N = x.shape[0]

    def shifted(x, by):  # row i takes row i - by (i + by where ``after``)
        seen = cg.pos < C - by if after else cg.pos >= by
        return jnp.where(seen, pltpu.roll(x, (N - by) if after else by, 0), 0.0)

    if not own:
        x = shifted(x, 1)
    by = 1
    while by < C:
        x = x + shifted(x, by)
        by *= 2
    return x


def _inverses_by_halves(As, cg: _ChannelGeometry, C: int):
    """``(I - A)^{-1}`` of each ``A`` as ``unit_lower_inverse_blocked``
    forms it, the squares of a program side by side: inside the diagonal
    blocks of the base the product form, then level by level ``T21 = T22
    A21 T11`` for all of a level's blocks in two products of the whole
    square, ``T`` being block-diagonal at the level's size."""
    eye = cg.geo.eye.astype(_F32)
    powers = [jnp.where(cg.base, A, 0.0) for A in As]
    Ts, reach = [p + eye for p in powers], 2
    while reach < min(INVERSE_BASE, C):
        powers = [_dot(p, p, precision=_HI) for p in powers]
        Ts = [T + _dot(T, p, precision=_HI) for T, p in zip(Ts, powers)]
        reach *= 2
    for level in cg.levels:
        halves = [
            _dot(T, jnp.where(level, A, 0.0), precision=_HI)
            for T, A in zip(Ts, As)
        ]
        Ts = [T + _dot(h, T, precision=_HI) for T, h in zip(Ts, halves)]
    return Ts


def _sub_block(C: int) -> int:
    return SUB_BLOCK if C % SUB_BLOCK == 0 else C


def _block_rows(x, a: int, C: int, s: int):
    """Row block ``a`` of each of the ``s`` stacked chunks -> [s sub, ...]."""
    sub = _sub_block(C)
    return jnp.concatenate(
        [x[j * C + a * sub:j * C + (a + 1) * sub] for j in range(s)], axis=0
    )


def _unblock_rows(blocks, C: int, s: int):
    """``_block_rows`` back: the row blocks ``a`` -> [s C, ...]."""
    sub = _sub_block(C)
    return jnp.concatenate([
        blocks[a][j * sub:(j + 1) * sub]
        for j in range(s) for a in range(C // sub)
    ], axis=0)


class _Decayed(NamedTuple):
    """The operands of a masked square ``sum_c x_ic k_jc exp(gamma_ic -
    gamma_jc)``: ``er = exp(gamma_i - gamma_a)`` with ``gamma_a`` the first
    row of ``i``'s own row block, ``xr = x * er`` rounded, and a block
    ``a``: ``ecs[a] = exp(gamma_a - gamma_j)`` up to the block's end and 0
    past it, ``kcs[a] = k * ecs[a]`` rounded."""

    er: jax.Array
    xr: jax.Array
    ecs: list
    kcs: list


def _decayed(xf, kf, gamma, cg, C: int, s: int, act) -> _Decayed:
    sub = _sub_block(C)
    nb, dk = C // sub, gamma.shape[-1]

    def first(j, a, rows):  # gamma at the first row of chunk j's block a
        at = j * C + a * sub
        return jnp.broadcast_to(gamma[at:at + 1], (rows, dk))

    own = jnp.concatenate(
        [first(j, a, sub) for j in range(s) for a in range(nb)], axis=0
    )
    er = jnp.exp(gamma - own)
    ecs = []
    for a in range(nb):
        ref = jnp.concatenate([first(j, a, C) for j in range(s)], axis=0)
        ecs.append(jnp.exp(
            jnp.where(cg.pos < (a + 1) * sub, ref - gamma, -jnp.inf)
        ))
    return _Decayed(
        er, (xf * er).astype(act), ecs, [(kf * e).astype(act) for e in ecs]
    )


def _scores(d: _Decayed, C: int, s: int):
    """The square, before its mask: row block ``a`` of every chunk is one
    matmul against ``kcs[a]``."""
    return _unblock_rows([
        _dot(_block_rows(d.xr, a, C, s), kc, _NT)
        for a, kc in enumerate(d.kcs)
    ], C, s)


def _scores_bwd(dS, d: _Decayed, C: int, s: int):
    """The square's cotangent (float32, masked) -> those of ``xr`` and of
    each ``kcs[a]``."""
    dSb = dS.astype(d.xr.dtype)
    dxs, dkcs = [], []
    for a, kc in enumerate(d.kcs):
        dR = _block_rows(dSb, a, C, s)
        dxs.append(_dot(dR, kc))
        dkcs.append(_dot(dR, _block_rows(d.xr, a, C, s), _TN))
    return _unblock_rows(dxs, C, s), dkcs


def _chunk_rows(x, j: int, C: int):
    return x[j * C:(j + 1) * C]


def _last_rows(gamma, cg, C: int, s: int):
    """``gamma`` at each chunk's last position, [1, d_k] a chunk."""
    last = jnp.where(cg.pos == C - 1, gamma, 0.0)
    return [
        jnp.sum(_chunk_rows(last, j, C), axis=0, keepdims=True)
        for j in range(s)
    ]


class _ChannelWy(NamedTuple):
    kf: jax.Array  # [N, d_k] float32
    vf: jax.Array
    beta_col: jax.Array
    kb: jax.Array  # k * beta, float32
    gamma: jax.Array
    left: jax.Array
    d: _Decayed
    T: jax.Array


def _channel_wy_squares(k_ref, v_ref, beta_ref, g_ref, cg, C, s, m):
    """What ``wy`` makes of each of a program's ``m / s`` squares."""
    act, N = k_ref.dtype, s * C
    made, As = [], []
    for t in range(m // s):
        rows = slice(t * N, (t + 1) * N)
        kf, g2 = k_ref[rows, :].astype(_F32), g_ref[rows, :]
        beta_col = _as_col(beta_ref[t], cg.geo)
        kb = kf * beta_col
        gamma = _sum_inside(g2, cg, C)
        left = _sum_inside(g2, cg, C, after=True, own=False)
        d = _decayed(kb, kf, gamma, cg, C, s, act)
        As.append(-jnp.where(cg.geo.below, _scores(d, C, s), 0.0))
        made.append(_ChannelWy(
            kf, v_ref[rows, :].astype(_F32), beta_col, kb, gamma, left, d,
            None,
        ))
    Ts = _inverses_by_halves(As, cg, C)
    return [x._replace(T=T) for x, T in zip(made, Ts)]


def _channel_wy_fwd_kernel(k_ref, v_ref, beta_ref, g_ref,
                           u_ref, w_ref, kl_ref, a_ref, *, C, r, m):
    act, s = k_ref.dtype, r
    cg = _channel_geometry(C, s)
    squares = _channel_wy_squares(k_ref, v_ref, beta_ref, g_ref, cg, C, s, m)
    for t, x in enumerate(squares):
        Tb = x.T.astype(act)
        U = _dot(Tb, (x.vf * x.beta_col).astype(act))
        W = _dot(Tb, (x.kb * jnp.exp(x.gamma)).astype(act)).astype(act)
        Kl = (x.kf * jnp.exp(x.left)).astype(act)
        lasts = _last_rows(x.gamma, cg, C, s)
        for j in range(s):
            c = t * s + j
            u_ref[c, 0] = _chunk_rows(U, j, C)
            w_ref[c, 0] = _chunk_rows(W, j, C)
            kl_ref[c] = _chunk_rows(Kl, j, C)
            a_ref[c] = jnp.exp(lasts[j])


def _channel_wy_bwd_kernel(k_ref, v_ref, beta_ref, g_ref,
                           du_ref, dw_ref, dkl_ref, da_ref,
                           dk_ref, dv_ref, dbeta_ref, dg_ref, *, C, r, m):
    act, s = k_ref.dtype, r
    N = s * C
    cg = _channel_geometry(C, s)
    squares = _channel_wy_squares(k_ref, v_ref, beta_ref, g_ref, cg, C, s, m)
    for t, x in enumerate(squares):
        rows = slice(t * N, (t + 1) * N)
        chunks = range(t * s, (t + 1) * s)
        dU = jnp.concatenate([du_ref[c, 0] for c in chunks], 0).astype(act)
        dW = jnp.concatenate([dw_ref[c, 0] for c in chunks], 0)
        dKl = jnp.concatenate([dkl_ref[c] for c in chunks], 0).astype(_F32)
        e_gamma, e_left = jnp.exp(x.gamma), jnp.exp(x.left)
        Tb = x.T.astype(act)
        vb = (x.vf * x.beta_col).astype(act)
        kg = x.kb * e_gamma
        # U = T vb and W = T kg
        dvb = _dot(Tb, dU, _TN)
        dkg = _dot(Tb, dW, _TN)
        dT = _dot(dU, vb, _NT) + _dot(dW, kg.astype(act), _NT)
        # T = (I - A)^{-1}: dA = T^T dT T^T; A = -S below the diagonal
        dA = _dot(x.T, _dot(dT, x.T, _NT, _HI), _TN, _HI)
        dxr, dkcs = _scores_bwd(
            jnp.where(cg.geo.below, -dA, 0.0), x.d, C, s
        )
        # xr = kb er, kcs[a] = k ecs[a], kg = kb exp(gamma), Kl = k exp(left)
        dkb = dkg * e_gamma + dxr * x.d.er
        dkc = sum(dk_a * e for dk_a, e in zip(dkcs, x.d.ecs))
        dkf = dkb * x.beta_col + dkc + dKl * e_left
        dgamma = dkg * kg + (dxr * x.d.er) * x.kb - dkc * x.kf
        # a = exp(gamma) at a chunk's last position
        lasts = _last_rows(x.gamma, cg, C, s)
        da = jnp.concatenate([
            jnp.broadcast_to(da_ref[c] * jnp.exp(last), (C, dgamma.shape[1]))
            for c, last in zip(chunks, lasts)
        ], axis=0)
        dgamma = dgamma + jnp.where(cg.pos == C - 1, da, 0.0)
        dleft = (dKl * e_left) * x.kf
        dbeta_col = (
            jnp.sum(dkb * x.kf, axis=1, keepdims=True)
            + jnp.sum(dvb * x.vf, axis=1, keepdims=True)
        )
        dk_ref[rows, :] = dkf.astype(act)
        dv_ref[rows, :] = (dvb * x.beta_col).astype(act)
        dbeta_ref[t] = _as_row(dbeta_col, cg.geo)
        dg_ref[rows, :] = _sum_inside(dgamma, cg, C, after=True) + (
            _sum_inside(dleft, cg, C, own=False)
        )


class _ChannelRead(NamedTuple):
    qf: jax.Array
    kf: jax.Array
    e_gamma: jax.Array
    d: _Decayed
    Pb: jax.Array  # the masked scores with the own step, rounded
    qe: jax.Array  # q * exp(gamma), rounded
    Vn: jax.Array


def _channel_read(q_ref, k_ref, g_ref, vn_ref, t, cg, C, s) -> _ChannelRead:
    act, N = k_ref.dtype, s * C
    rows = slice(t * N, (t + 1) * N)
    qf, kf = q_ref[rows, :].astype(_F32), k_ref[rows, :].astype(_F32)
    gamma = _sum_inside(g_ref[rows, :], cg, C)
    e_gamma = jnp.exp(gamma)
    d = _decayed(qf, kf, gamma, cg, C, s, act)
    # a position's own step enters undecayed: a constant 1, not exp(0)
    own_step = jnp.sum(qf * kf, axis=1, keepdims=True)
    P = jnp.where(cg.geo.below, _scores(d, C, s), 0.0) + jnp.where(
        cg.geo.eye, own_step, 0.0
    )
    Vn = jnp.concatenate(
        [vn_ref[c, 0] for c in range(t * s, (t + 1) * s)], axis=0
    )
    return _ChannelRead(
        qf, kf, e_gamma, d, P.astype(act), (qf * e_gamma).astype(act), Vn
    )


def _channel_read_fwd_kernel(q_ref, k_ref, g_ref, vn_ref, s_ref, o_ref,
                             *, C, r, m):
    s = r
    cg = _channel_geometry(C, s)
    made = [
        _channel_read(q_ref, k_ref, g_ref, vn_ref, t, cg, C, s)
        for t in range(m // s)
    ]
    for t, x in enumerate(made):
        entered = jnp.concatenate([
            _dot(_chunk_rows(x.qe, j, C), s_ref[t * s + j, 0])
            for j in range(s)
        ], axis=0)
        o = _dot(x.Pb, x.Vn) + entered
        o_ref[t * s * C:(t + 1) * s * C, :] = o.astype(o_ref.dtype)


def _channel_read_bwd_kernel(q_ref, k_ref, g_ref, vn_ref, s_ref, do_ref,
                             dq_ref, dk_ref, dg_ref, dvn_ref, ds_ref,
                             *, C, r, m):
    act, s = k_ref.dtype, r
    N = s * C
    cg = _channel_geometry(C, s)
    made = [
        _channel_read(q_ref, k_ref, g_ref, vn_ref, t, cg, C, s)
        for t in range(m // s)
    ]
    for t, x in enumerate(made):
        rows = slice(t * N, (t + 1) * N)
        do = do_ref[rows, :]
        # own = P V', P = the masked scores + the own step on the diagonal
        dvn = _dot(x.Pb, do, _TN).astype(act)
        dP = _dot(do, x.Vn, _NT)
        dxr, dkcs = _scores_bwd(jnp.where(cg.geo.below, dP, 0.0), x.d, C, s)
        down = jnp.sum(jnp.where(cg.geo.eye, dP, 0.0), axis=1, keepdims=True)
        # entered = qe S, qe = q exp(gamma)
        dqe = []
        for j in range(s):
            c, do_j = t * s + j, _chunk_rows(do, j, C)
            dqe.append(_dot(do_j, s_ref[c, 0], _NT))
            ds_ref[c, 0] = _dot(_chunk_rows(x.qe, j, C), do_j, _TN).astype(act)
            dvn_ref[c, 0] = _chunk_rows(dvn, j, C)
        dqe = jnp.concatenate(dqe, axis=0)
        dkc = sum(dk_a * e for dk_a, e in zip(dkcs, x.d.ecs))
        dx = dxr * x.d.er + dqe * x.e_gamma
        dgamma = dx * x.qf - dkc * x.kf
        dq_ref[rows, :] = (dx + down * x.kf).astype(act)
        dk_ref[rows, :] = (dkc + down * x.qf).astype(act)
        dg_ref[rows, :] = _sum_inside(dgamma, cg, C, after=True)


def _channel_shape(k, H: int, C: int, d_v: int) -> _Shape:
    """``r`` is the chunks a square stacks: two where the chunks pair up."""
    B, T, key_lanes = k.shape
    n = T // C
    s = 2 if n % 2 == 0 else 1
    m = next(m for m in _CHUNKS_A_PROGRAM if n % m == 0 and m % s == 0)
    return _Shape(B, n, H, s, C, key_lanes // H, d_v, m)


def _beta_rows(sh: _Shape):
    """``beta`` [n / s, B, H, 1, s C]: a program's squares, whole."""
    return pl.BlockSpec(
        (sh.m // sh.r, None, None, 1, sh.r * sh.C),
        lambda b, h, i: (i, b, h, 0, 0),
    )


def _channel_wy_specs(sh: _Shape):
    C = sh.C
    keys = _tokens(sh, sh.d_k)
    return (
        [keys, _tokens(sh, sh.d_v), _beta_rows(sh), keys],
        [
            _chunk_major(sh, 1, C, sh.d_v), _chunk_major(sh, 1, C, sh.d_k),
            _chunk_major(sh, C, sh.d_k), _chunk_major(sh, 1, sh.d_k),
        ],
    )


def _channel_wy_call(k, v, beta, g, H, C):
    # once a trace of ``wy_channel``'s forward, the primal and the
    # ``custom_vjp`` rule alike: where ``gated_delta._pass_forward`` counts
    # a site, so that the two counts are of the same traces (a layer under
    # ``jax.checkpoint`` is traced as the primal once and through the rule
    # once more)
    trace_counts.count("gdn_kernel_sites")
    sh = _channel_shape(k, H, C, v.shape[-1] // H)
    ins, outs = _channel_wy_specs(sh)
    lead = (sh.n, sh.B, H)
    return _call(
        _channel_wy_fwd_kernel, "gdn_channel_wy_fwd", sh, ins, outs,
        [
            jax.ShapeDtypeStruct(lead + (1, C, sh.d_v), _F32),
            jax.ShapeDtypeStruct(lead + (1, C, sh.d_k), k.dtype),
            jax.ShapeDtypeStruct(lead + (C, sh.d_k), k.dtype),
            jax.ShapeDtypeStruct(lead + (1, sh.d_k), _F32),
        ],
        k, v, beta, g,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _channel_wy(k, v, beta, g, H: int, C: int):
    return _channel_wy_call(k, v, beta, g, H, C)


def _channel_wy_fwd(k, v, beta, g, H, C):
    return _channel_wy_call(k, v, beta, g, H, C), (k, v, beta, g)


def _channel_wy_bwd(H, C, res, cts):
    k, v, beta, g = res
    sh = _channel_shape(k, H, C, v.shape[-1] // H)
    ins, outs = _channel_wy_specs(sh)
    return _call(
        _channel_wy_bwd_kernel, "gdn_channel_wy_bwd", sh, ins + outs, ins,
        _like(*res), *res, *cts,
    )


_channel_wy.defvjp(_channel_wy_fwd, _channel_wy_bwd)


def wy_channel(k, v, beta, g, H: int, C: int):
    """``wy`` for a decay that is a vector over the key's channels: ``k``
    and ``g`` [B, T, H d_k] (``g`` float32), ``v`` [B, T, H d_v], ``beta``
    [B, T, H] float32 -> ``chunk_state_pass``'s arguments for the kind,
    chunk axis first and every key head its one value head: ``U``
    [n, B, H, 1, C, d_v] float32, ``W`` [n, B, H, 1, C, d_k], the keys
    decayed to the chunk's end [n, B, H, C, d_k] and ``a`` [n, B, H, 1,
    d_k] float32."""
    B, T, _ = k.shape
    sh = _channel_shape(k, H, C, v.shape[-1] // H)
    rows = sh.r * C
    beta = jnp.transpose(beta.reshape(B, T // rows, rows, H), (1, 0, 3, 2))
    return _channel_wy(k, v, beta[:, :, :, None], g, H, C)


def _channel_read_specs(q, k, g, Vn, S_in):
    _, _, H, _, C, d_v = Vn.shape
    sh = _channel_shape(k, H, C, d_v)
    keys = _tokens(sh, sh.d_k)
    return sh, [
        keys, keys, keys,
        _chunk_major(sh, 1, C, d_v), _chunk_major(sh, 1, sh.d_k, d_v),
    ]


def _channel_read_call(q, k, g, Vn, S_in):
    sh, ins = _channel_read_specs(q, k, g, Vn, S_in)
    return _call(
        _channel_read_fwd_kernel, "gdn_channel_read_fwd", sh, ins,
        _tokens(sh, sh.d_v),
        jax.ShapeDtypeStruct((sh.B, sh.n * sh.C, sh.Hk * sh.d_v), k.dtype),
        q, k, g, Vn, S_in,
    )


@jax.custom_vjp
def read_out_channel(q, k, g, Vn, S_in):
    """``read_out`` for the vector kind: ``q, k, g`` [B, T, H d_k], ``V'``
    [n, B, H, 1, C, d_v] and the entered states [n, B, H, 1, d_k, d_v] as
    the pass returns them -> ``o`` [B, T, H d_v] in the activation
    dtype."""
    return _channel_read_call(q, k, g, Vn, S_in)


def _channel_read_fwd(q, k, g, Vn, S_in):
    return _channel_read_call(q, k, g, Vn, S_in), (q, k, g, Vn, S_in)


def _channel_read_bwd(res, do):
    sh, ins = _channel_read_specs(*res)
    return _call(
        _channel_read_bwd_kernel, "gdn_channel_read_bwd", sh,
        ins + [_tokens(sh, sh.d_v)], ins, _like(*res), *res, do,
    )


read_out_channel.defvjp(_channel_read_fwd, _channel_read_bwd)


# -- the serial pass over the chunk states -----------------------------------
#
# ``gated_delta.chunk_state_pass`` as two kernels, ``delta_state_pass`` and
# ``delta_state_pass_rev`` (names that no reader of the ``gdn_chunk`` or
# ``gdn_*_fwd`` kernels takes: the pass is in neither side of their
# roofline). A program is one ``(batch, run of key heads, run of chunks)``,
# the chunk runs walked in order (the grid's last dimension is serial) and a
# run's chunks by a ``fori_loop``; the float32 state of each of the
# program's heads stays in a VMEM scratch from a head's first chunk to its
# last, so a step reads and writes what the pass's contract names and
# nothing else. A chunk's two products wait for each other, another head's
# do not: a program holds all ``r`` value heads of a key head and as many
# key heads as ``_pass_block`` says. The reversed kernel walks the same grid
# from the last chunk with the state's cotangent in the scratch, and makes
# ``dU``, ``dW``, ``dK`` and the decays' cotangents in the step that holds
# their operands: the cotangent of a chunk's leaving state and of its
# decayed ``V'`` are never written. Both kinds of decay run the one body,
# told apart as ``chunk_state_pass`` tells them: ``delta`` None and ``a`` a
# row over the key's channels, or ``delta`` a row a value head and ``a`` a
# scalar. The arithmetic is the plain statement's.

_PASS_VMEM = 64 << 20  # what Mosaic may take; the blocks stay well under
_PASS_BLOCK_BYTES = 24 << 20  # a program's blocks, both buffers of each
_PASS_HEADS = (4, 3, 2, 1)  # key heads a program


def _pass_block(n: int, g: int, r: int, C: int, d_k: int, d_v: int,
                itemsize: int):
    """``(key heads, chunks)`` a program of the pass, from the shapes: the
    chunks a program of the chunk kernels takes, and the most key heads
    whose blocks (the reversed kernel's, the larger set; a head's width in
    whole lane tiles, as VMEM holds it) stay inside ``_PASS_BLOCK_BYTES``."""
    m = next(m for m in _CHUNKS_A_PROGRAM if n % m == 0)
    lk, lv = (-(-d // _LANES) * _LANES for d in (d_k, d_v))
    a_head = (
        (2 * r + 2) * C * lk * itemsize  # W, dW, K, dK
        + 2 * r * (C + d_k) * lv * itemsize  # V', dV', the states, theirs
        + r * C * lv * 4  # dU
    )
    fit = _PASS_BLOCK_BYTES // (2 * m * a_head)
    return next((p for p in _PASS_HEADS if g % p == 0 and p <= fit), 1), m


def _turning(w_ref, scalar: bool) -> _Geometry:
    """The masks that turn a decay's row into a column (``_as_col``): a
    chunk's positions for a scalar decay's ``delta``, the key's channels
    where ``a`` is a row over them."""
    return _geometry(w_ref.shape[-2] if scalar else w_ref.shape[-1], 1)


def _pass_fwd_kernel(*refs, r, m, p, scalar):
    if scalar:
        u_ref, w_ref, k_ref, delta_ref, a_ref, vn_ref, sin_ref, s_ref = refs
    else:
        u_ref, w_ref, k_ref, a_ref, vn_ref, sin_ref, s_ref = refs
    act = w_ref.dtype
    geo = _turning(w_ref, scalar)

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    def chunk(c, carry):
        for h in range(p):
            K = k_ref[c, h]
            for j in range(r):
                S = s_ref[h, j]
                Sb = S.astype(act)
                sin_ref[c, h, j] = Sb
                Vn = u_ref[c, h, j] - _dot(w_ref[c, h, j], Sb)
                Vb = Vn.astype(act)
                vn_ref[c, h, j] = Vb
                if scalar:
                    delta = _as_col(delta_ref[c, h, pl.ds(j, 1), :], geo)
                    Vb = (delta * Vn).astype(act)
                    a = a_ref[c, h][:, j:j + 1]
                else:
                    a = _as_col(a_ref[c, h], geo)
                s_ref[h, j] = a * S + _dot(K, Vb, _TN)
        return carry

    lax.fori_loop(0, m, chunk, 0)


def _pass_bwd_kernel(*refs, r, m, p, scalar):
    if scalar:
        (w_ref, k_ref, delta_ref, a_ref, vn_ref, sin_ref, dvn_ref, dsin_ref,
         du_ref, dw_ref, dk_ref, ddelta_ref, da_ref, ds_ref) = refs
    else:
        (w_ref, k_ref, a_ref, vn_ref, sin_ref, dvn_ref, dsin_ref,
         du_ref, dw_ref, dk_ref, da_ref, ds_ref) = refs
    act = w_ref.dtype
    geo = _turning(w_ref, scalar)
    lane = lax.broadcasted_iota(jnp.int32, (1, r), 1)

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    def chunk(t, carry):
        c = m - 1 - t
        for h in range(p):
            K = k_ref[c, h]
            dK, da_row = None, jnp.zeros((1, r), _F32)
            for j in range(r):
                dS = ds_ref[h, j]  # of the state that LEFT this chunk
                dSb = dS.astype(act)
                S_in, Vb = sin_ref[c, h, j], vn_ref[c, h, j]
                dVd = _dot(K, dSb)
                dU = dvn_ref[c, h, j].astype(_F32)
                if scalar:
                    delta = _as_col(delta_ref[c, h, pl.ds(j, 1), :], geo)
                    Vf = Vb.astype(_F32)
                    dU = dU + delta * dVd
                    Vb = (delta * Vf).astype(act)
                    ddelta_ref[c, h, pl.ds(j, 1), :] = _as_row(
                        jnp.sum(dVd * Vf, axis=1, keepdims=True), geo
                    )
                else:
                    dU = dU + dVd
                du_ref[c, h, j] = dU
                dUb = dU.astype(act)
                dw_ref[c, h, j] = (-_dot(dUb, S_in, _NT)).astype(act)
                dK_j = _dot(Vb, dSb, _NT)
                dK = dK_j if dK is None else dK + dK_j
                # the state's decay: against the state that entered
                over_v = jnp.sum(
                    dSb.astype(_F32) * S_in.astype(_F32), axis=1,
                    keepdims=True,
                )
                if scalar:
                    da_row = jnp.where(
                        lane == j, jnp.sum(over_v, axis=0, keepdims=True),
                        da_row,
                    )
                    a = a_ref[c, h][:, j:j + 1]
                else:
                    da_ref[c, h] = _as_row(over_v, geo)
                    a = _as_col(a_ref[c, h], geo)
                ds_ref[h, j] = (
                    a * dS + dsin_ref[c, h, j].astype(_F32)
                    - _dot(w_ref[c, h, j], dUb, _TN)
                )
            dk_ref[c, h] = dK.astype(act)
            if scalar:
                da_ref[c, h] = da_row
        return carry

    lax.fori_loop(0, m, chunk, 0)


def _pass_call(kernel, name, W, block, interpret: bool, reverse: bool,
               scalar: bool, ins, tails, out_tails, out_dtypes, d_v: int):
    """One of the pass's two kernels over chunk-major arrays ``[n, b, g,
    *tail]``: every block a program's run of ``m`` chunks of its run of
    ``p`` key heads (``block``), the runs walked from the last where
    ``reverse``."""
    n, b, g, r, _, d_k = W.shape
    p, m = block
    last = n // m - 1

    def spec(tail):
        zeros = (0,) * len(tail)
        if reverse:
            return pl.BlockSpec(
                (m, None, p) + tail, lambda b, h, i: (last - i, b, h) + zeros
            )
        return pl.BlockSpec(
            (m, None, p) + tail, lambda b, h, i: (i, b, h) + zeros
        )

    return pl.pallas_call(
        functools.partial(kernel, r=r, m=m, p=p, scalar=scalar),
        name=name,
        grid=(b, g // p, n // m),
        in_specs=[spec(t) for t in tails],
        out_specs=[spec(t) for t in out_tails],
        out_shape=[
            jax.ShapeDtypeStruct((n, b, g) + t, dt)
            for t, dt in zip(out_tails, out_dtypes)
        ],
        scratch_shapes=[pltpu.VMEM((p, r, d_k, d_v), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_PASS_VMEM,
        ),
        interpret=interpret,
    )(*ins)


def _decay_blocks(delta, a):
    """The decays as the pass's kernels take them, and their blocks'
    tails: ``delta`` [n, b, g, r, C] as it is and ``a`` [n, b, g, r] as a
    row a key head, or (``delta`` None) ``a`` [n, b, g, 1, d_k] alone."""
    if delta is None:
        return [a], [a.shape[3:]]
    return [delta, a[:, :, :, None]], [delta.shape[3:], (1, a.shape[3])]


_PASS_STATIC = ("block", "interpret")


@functools.partial(jax.jit, static_argnames=_PASS_STATIC)
def _state_pass(U, W, K, delta, a, *, block, interpret):
    """One jit for every call site (``gated_norm_kernels._fwd_call``'s
    reason): a program traces and lowers the kernel once a shape and calls
    it once a layer."""
    r, C, d_k = W.shape[3:]
    d_v, act = U.shape[-1], W.dtype
    decays, tails = _decay_blocks(delta, a)
    return _pass_call(
        _pass_fwd_kernel, "delta_state_pass", W, block, interpret, False,
        delta is not None, [U, W, K, *decays],
        [(r, C, d_v), (r, C, d_k), (C, d_k), *tails],
        [(r, C, d_v), (r, d_k, d_v)], [act, act], d_v,
    )


@functools.partial(jax.jit, static_argnames=_PASS_STATIC)
def _state_pass_rev(W, K, delta, a, Vn, S_in, dVn, dS_in, *, block,
                    interpret):
    r, C, d_k = W.shape[3:]
    d_v, act = Vn.shape[-1], W.dtype
    decays, tails = _decay_blocks(delta, a)
    rows, states = (r, C, d_v), (r, d_k, d_v)
    dU, dW, dK, *ddecays = _pass_call(
        _pass_bwd_kernel, "delta_state_pass_rev", W, block, interpret, True,
        delta is not None, [W, K, *decays, Vn, S_in, dVn, dS_in],
        [(r, C, d_k), (C, d_k), *tails, rows, states, rows, states],
        [rows, (r, C, d_k), (C, d_k), *tails],
        [_F32, act, act] + [_F32] * len(tails), d_v,
    )
    if delta is None:
        return dU, dW, dK, None, ddecays[0]
    return dU, dW, dK, ddecays[0], ddecays[1][:, :, :, 0]


def _pass_static(W, d_v: int):
    """What the pass's jits are keyed by beside their arguments' shapes."""
    n, _, g, r, C, d_k = W.shape
    return dict(
        block=_pass_block(n, g, r, C, d_k, d_v, W.dtype.itemsize),
        interpret=_flash._interpret_default(),
    )


def state_pass(U, W, K, delta, a):
    """``gated_delta.chunk_state_pass``'s forward, its arguments and its
    two results, with the state in VMEM across a head's chunks."""
    return _state_pass(U, W, K, delta, a, **_pass_static(W, U.shape[-1]))


def state_pass_rev(W, K, delta, a, Vn, S_in, dVn, dS_in):
    """The reversed pass: from what the forward read and returned and the
    cotangents of its two results, the cotangents of ``U, W, K, delta, a``
    in ``gated_delta._pass_scan_bwd``'s dtypes (``delta``'s None where
    ``delta`` is)."""
    return _state_pass_rev(
        W, K, delta, a, Vn, S_in, dVn, dS_in,
        **_pass_static(W, Vn.shape[-1]),
    )
