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
cotangents) travel as rows ``[n, B, H_k, 1, r C]``.

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


def fits(d_k: int, d_v: int, chunk: int, T: int, dtype,
         vector_decay: bool = False) -> bool:
    """THE rule for which way the chunk-local work is executed, read from
    the shapes alone: the kernels where a key and a value head are whole
    128-lane tiles, a chunk is whole sublane tiles of the activation dtype
    (8 rows of float32, 16 of bfloat16), the sequence is whole chunks and
    the decay is one scalar a head and step (the kernels build their
    squares from ``exp(gamma_i - gamma_j)`` of scalars; a decay that is a
    vector over the key's channels, ``g [B, T, H, d_k]``, they do not
    take); the plain ``jax.numpy`` statement everywhere else."""
    sublanes = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    return (
        not vector_decay
        and d_k % _LANES == 0 and d_v % _LANES == 0
        and chunk % sublanes == 0 and T % chunk == 0
    )


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


def _stack(ref, rows, r: int, d: int):
    """A token-major block's ``r`` heads ``[C, r d]`` -> ``[r C, d]``."""
    return jnp.concatenate(
        [ref[rows, j * d:(j + 1) * d] for j in range(r)], axis=0
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


def _wy_squares(k_ref, v_ref, beta_ref, g_ref, geo, C, r, m):
    """The squares of each of a program's ``m`` chunks."""
    d_v = v_ref.shape[-1] // r
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
    Ts = _unit_lower_inverses([s.A for s in made], geo, C)
    return [s._replace(T=T) for s, T in zip(made, Ts)]


def _left_and_total(g_col, geo):
    """What is left of the chunk after each position (summed as such: the
    last position's is an empty sum) and the whole chunk's sum, as rows."""
    left = jnp.sum(jnp.where(geo.below, g_col, 0.0), axis=0, keepdims=True)
    total = jnp.sum(jnp.where(geo.same, g_col, 0.0), axis=0, keepdims=True)
    return left, total


def _wy_fwd_kernel(k_ref, v_ref, beta_ref, g_ref,
                   u_ref, w_ref, kc_ref, delta_ref, a_ref, *, C, r, m):
    act = k_ref.dtype
    geo = _geometry(C, r)
    squares = _wy_squares(k_ref, v_ref, beta_ref, g_ref, geo, C, r, m)
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
                   dk_ref, dv_ref, dbeta_ref, dg_ref, *, C, r, m):
    act = k_ref.dtype
    d_v = v_ref.shape[-1] // r
    geo = _geometry(C, r)
    squares = _wy_squares(k_ref, v_ref, beta_ref, g_ref, geo, C, r, m)
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
            dv_ref[rows, j * d_v:(j + 1) * d_v] = dv[j * C:(j + 1) * C]
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
            o_ref[rows, j * d_v:(j + 1) * d_v] = o[j * C:(j + 1) * C]


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

    @property
    def grid(self):
        return (self.B, self.Hk, self.n // self.m)

    @property
    def rows(self):
        """A row of ``beta``, ``g``, ``delta`` ...: [n, B, H_k, 1, r C]."""
        return (self.n, self.B, self.Hk, 1, self.r * self.C)


def _shape(k, Hk: int, r: int, C: int, d_v: int) -> _Shape:
    B, T, key_lanes = k.shape
    n = T // C
    m = next(m for m in _CHUNKS_A_PROGRAM if n % m == 0)
    return _Shape(B, n, Hk, r, C, key_lanes // Hk, d_v, m)


def _tokens(sh: _Shape, d: int):
    """A ``[B, T, heads * d]`` array: a program's run of chunks of one
    key head's (or its value heads') lanes."""
    return pl.BlockSpec((None, sh.m * sh.C, d), lambda b, h, i: (b, i, h))


def _chunk_major(sh: _Shape, *tail):
    """A ``[n, B, H_k, *tail]`` array: a program's run of chunks, whole."""
    zeros = (0,) * len(tail)
    return pl.BlockSpec(
        (sh.m, None, None) + tail, lambda b, h, i: (i, b, h) + zeros
    )


def _like(*arrays):
    return [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in arrays]


def _call(kernel, name, sh: _Shape, in_specs, out_specs, out_shape, *ins):
    return pl.pallas_call(
        functools.partial(kernel, C=sh.C, r=sh.r, m=sh.m),
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
        [_tokens(sh, sh.d_k), _tokens(sh, r * sh.d_v), row, row],
        [
            _chunk_major(sh, r, C, sh.d_v), _chunk_major(sh, r, C, sh.d_k),
            _chunk_major(sh, C, sh.d_k), row, row,
        ],
    )


def _wy_call(k, v, beta, g, Hk, r, C):
    sh = _shape(k, Hk, r, C, v.shape[-1] // (Hk * r))
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
        k, v, beta, g,
    )
    return U, W, Kc, delta.reshape(lead + (r, C)), a[..., 0, ::C]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def wy(k, v, beta, g, Hk: int, r: int, C: int):
    """What a chunk computes before the pass. ``k`` [B, T, H_k d_k] and
    ``v`` [B, T, H_v d_v] in the activation dtype, ``beta`` and ``g``
    [n, B, H_k, 1, r C] float32 -> ``chunk_state_pass``'s arguments ``U,
    W, K, delta, a``, chunk axis first."""
    return _wy_call(k, v, beta, g, Hk, r, C)


def _wy_fwd(k, v, beta, g, Hk, r, C):
    return _wy_call(k, v, beta, g, Hk, r, C), (k, v, beta, g)


def _wy_bwd(Hk, r, C, res, cts):
    k, v, beta, g = res
    dU, dW, dKc, ddelta, da = cts
    sh = _shape(k, Hk, r, C, v.shape[-1] // (Hk * r))
    ins, outs = _wy_specs(sh)
    return _call(
        _wy_bwd_kernel, "gdn_chunk_wy_bwd", sh, ins + outs, ins, _like(*res),
        *res, dU, dW, dKc, ddelta.reshape(sh.rows),
        jnp.repeat(da, C, axis=-1).reshape(sh.rows),
    )


wy.defvjp(_wy_fwd, _wy_bwd)


def _read_specs(q, k, g, Vn, S_in):
    """The shape and the block specs of ``read_out``'s arguments."""
    _, _, Hk, r, C, d_v = Vn.shape
    sh = _shape(k, Hk, r, C, d_v)
    keys = _tokens(sh, sh.d_k)
    return sh, [
        keys, keys, _chunk_major(sh, 1, r * C),
        _chunk_major(sh, r, C, d_v), _chunk_major(sh, r, sh.d_k, d_v),
    ]


def _read_call(q, k, g, Vn, S_in):
    sh, ins = _read_specs(q, k, g, Vn, S_in)
    lanes = sh.Hk * sh.r * sh.d_v
    return _call(
        _read_fwd_kernel, "gdn_chunk_read_fwd", sh, ins,
        _tokens(sh, sh.r * sh.d_v),
        jax.ShapeDtypeStruct((sh.B, sh.n * sh.C, lanes), k.dtype),
        q, k, g, Vn, S_in,
    )


@jax.custom_vjp
def read_out(q, k, g, Vn, S_in):
    """What every position reads. ``q, k`` [B, T, H_k d_k], ``g``
    [n, B, H_k, 1, r C], ``V'`` [n, B, H_k, r, C, d_v] and the entered
    states [n, B, H_k, r, d_k, d_v] as the pass returns them -> ``o``
    [B, T, H_v d_v], accumulated in float32 and rounded once to the
    activation dtype."""
    return _read_call(q, k, g, Vn, S_in)


def _read_fwd(q, k, g, Vn, S_in):
    return _read_call(q, k, g, Vn, S_in), (q, k, g, Vn, S_in)


def _read_bwd(res, do):
    sh, ins = _read_specs(*res)
    return _call(
        _read_bwd_kernel, "gdn_chunk_read_bwd", sh,
        ins + [_tokens(sh, sh.r * sh.d_v)], ins, _like(*res), *res, do,
    )


read_out.defvjp(_read_fwd, _read_bwd)
