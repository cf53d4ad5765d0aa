"""8-bit (blockwise-quantized state) AdamW for TPU.

Parity: ATorch's low-bit optimizer — python driver
atorch/atorch/optimizers/low_bit/functional.py (vectorwise/blockwise
quantization, linear + nonlinear qmaps) backed by the CUDA kernels in
atorch/atorch/ops/csrc/{quantize.cu,dequantize.cu,quantization_optimizer.cu}.

TPU-native design: optimizer moments are stored as int8 codes + one f32
scale per 128-element block: 128 consecutive elements of one row of the
leaf, the same in every layout below (a row whose width is no multiple
of 128 is padded to whole blocks, ``_to_blocks``: a block never holds
the end of one row and the start of the next). What runs today:

- ``adamw_8bit`` with ``use_pallas=False`` (the benchmark's OLMoE cell,
  and every backend but the TPU by default): plain jnp that XLA fuses. A
  leaf whose last two dimensions are whole (8, 128) tiles keeps its
  moments in ``TILES`` layout, the leaf's own tile order in HBM: the
  update then views the gradient and hands back delta through a reshape +
  transpose that the TPU compiler takes as a bitcast, the per-block
  maximum is a lane reduce over the last axis, and decay and
  ``apply_updates`` fuse onto delta, which never exists in HBM. XLA still
  makes four passes a leaf (two block-maximum reduces, the parameter,
  the requantise): 24 bytes an element with a bf16 gradient, against 14
  for one pass. Any other leaf (1-D, odd widths) takes ``BLOCKS``:
  ``[nblocks, 128]`` rows, padded; on the TPU that flattening is a
  physical relayout of the gradient and another of delta (an (8, 128)
  tiled ``[..., 1024]`` array does not lie in rows of 128): 22 of the 81 ms
  of the OLMoE cell's optimizer pass when every leaf took it (PERF.md §6,
  PR 28).
  The layout follows the leaf because the leaf is what the gradient, the
  parameter and the apply already are; it is decided from the shape, not
  by an argument.
- ``adamw_8bit`` with ``use_pallas=True`` (the default on the TPU): the
  tree kernel, one ``pallas_call`` a leaf over ``BLOCKS`` rows (g, codes,
  scales in; codes', scales', delta out), between the same two relayouts.
- ``adamw_8bit_flat``: big leaves packed into a few flat buffers, one
  aliased Pallas pass a group with dense ("wide") scales (no benchmark
  configuration names it). ``bits=4``: jnp only, over ``BLOCKS`` rows.

Block size 128 = one lane row, so a block's maximum is a reduction along
lanes; whether that is cheap depends on the layout above, not on the
block size alone.

Quantization is blockwise through a sqrt map (``_sqrt_map_quant``: codes
= round(sign(y) sqrt|y| * 127), y = x / block max): on TPU a nonlinear
256-entry codebook lookup per element (the reference's dynamic map) would
serialize into gathers; the sqrt map keeps the whole update elementwise
on the VPU and keeps small second moments from rounding to zero.

The same math (``_adam8_block_math``, ``_sqrt_map_*``) runs in every
path, so numerics agree across them up to rounding ties.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 128  # quantization block = one VPU lane row
_ROWS = 256  # rows per pallas grid step (256*128 elems/step), tree form
# rows per grid step for the FLAT path. The per-grid-step overhead is
# ~3.6 us (measured: both the tree form and a 256-row flat form sit at
# ~47k total steps for 1.5B params and ~170 ms — step-bound, not
# HBM-bound). 2048*128 = 262k elems/step cuts the step count 8x and
# puts the pass back on the HBM roofline. VMEM at 2048 rows: ~4.5 MB
# of tiles + f32 intermediates, inside the ~16 MB budget.
_FLAT_ROWS = 2048


# where a Quantized8's 128-element blocks lie: static aux data that
# ``_layout_for`` decides from the leaf's shape, never an argument. For a
# leaf [..., R, C]:
BLOCKS = "blocks"  # codes [nblocks, BLOCK], scales [nblocks, 1]
# codes [..., R/8, C/BLOCK, 8, BLOCK], scales [..., R/8, C/BLOCK, 8]
TILES = "tiles"
_SUBLANES = 8  # rows of one f32 (8, 128) tile


@jax.tree_util.register_pytree_node_class
class Quantized8:
    """Blockwise quantized tensor: ``x ~ sqrt-map(codes) * scales``.

    ``codes``/``scales`` are pytree children; ``shape``/``signed``/
    ``layout`` are static aux data so jit never traces them. A block
    holds 128 consecutive elements of one row of the leaf in either
    layout (``_to_blocks``); ``TILES`` stores them in the order the
    leaf's own (8, 128) tiles lie in HBM (``_to_tiles``).
    """

    def __init__(self, codes, scales, shape, signed, layout=BLOCKS):
        self.codes = codes  # int8, see BLOCKS / TILES
        self.scales = scales  # f32
        self.shape = tuple(shape)
        self.signed = bool(signed)
        self.layout = layout

    def tree_flatten(self):
        return (self.codes, self.scales), (
            self.shape, self.signed, self.layout,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)

    def __repr__(self):
        return (
            f"Quantized8(shape={self.shape}, signed={self.signed}, "
            f"layout={self.layout}, codes={tuple(self.codes.shape)})"
        )


def _to_blocks(x):
    """``[nblocks, BLOCK]`` rows in which no block crosses a row of the
    leaf: a leaf ``[..., C]`` with ``C`` no multiple of ``BLOCK`` has
    each of its rows padded to whole blocks first (zeros, which move no
    block's maximum), any other leaf is flattened as it lies. Flattened
    unpadded, ``[2048, 25024]`` put the last 64 columns of one row and
    the first 64 of the next under one scale: an untied head's rarest and
    most frequent ids, whose second moments lie seven orders of magnitude
    apart (PERF.md, Findings PR 50)."""
    if x.ndim > 1 and x.shape[-1] % BLOCK:
        pad = (-x.shape[-1]) % BLOCK
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    flat = x.reshape(-1)
    pad = (-flat.size) % BLOCK
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, BLOCK)


def _from_blocks(blocks, shape):
    if len(shape) > 1 and shape[-1] % BLOCK:
        width = -(-shape[-1] // BLOCK) * BLOCK
        rows = blocks.reshape(*shape[:-1], width)
        return rows[..., : shape[-1]]
    return blocks.reshape(-1)[: math.prod(shape)].reshape(shape)


def _blocks_size(shape) -> int:
    """Elements of ``_to_blocks``' rows for a leaf of ``shape``."""
    if len(shape) > 1:
        shape = (*shape[:-1], -(-shape[-1] // BLOCK) * BLOCK)
    return -(-math.prod(shape) // BLOCK) * BLOCK


def _layout_for(shape) -> str:
    """``TILES`` where the leaf's last two dimensions are whole (8, 128)
    tiles: then ``_to_tiles`` is the array as it lies in HBM. Anything
    else (1-D leaves, odd widths) takes the padded ``BLOCKS`` path."""
    if len(shape) < 2 or shape[-1] % BLOCK or shape[-2] % _SUBLANES:
        return BLOCKS
    return TILES


def _to_tiles(x):
    """``[..., R, C]`` → ``[..., R/8, C/128, 8, 128]``: one (8, 128) tile
    in the last two dimensions, tiles in row-major order — byte for byte
    the f32 leaf under the TPU's (8, 128) tiling, so XLA takes the view as
    a bitcast where ``_to_blocks`` moves every byte. Row ``r`` of tile
    ``[..., i, j]`` is elements ``[128 j, 128 j + 128)`` of leaf row
    ``8 i + r``: the same 128 elements as one row of ``_to_blocks``, so
    scales and codes are the same numbers in another order. The leading
    dimensions stay as they are: flattened into one, the view is still
    a bitcast but XLA no longer fuses ``apply_updates`` onto ``delta``
    (seen in the compile of a ``[64, 2048, 1024]`` leaf for a v5e)."""
    *lead, R, C = x.shape
    tiled = x.reshape(*lead, R // _SUBLANES, _SUBLANES, C // BLOCK, BLOCK)
    return tiled.swapaxes(-3, -2)


def _from_tiles(t, shape):
    return t.swapaxes(-3, -2).reshape(shape)


def _sqrt_map_quant(x, signed, qmax):
    """Shared sqrt-map core: x [rows, N] f32 → (float codes in
    [-qmax, qmax] or [0, qmax], scales [rows, 1]).

    Power-2 ("sqrt") map, the reference's ``power-2`` qmap
    (low_bit/functional.py:531 ``create_pow_map``): normalize to the block
    max, code = round(sign(y)*sqrt(|y|)*qmax). The sqrt spreads codes
    toward zero, so the smallest representable nonzero value is
    scale/qmax^2 instead of scale/qmax — without it Adam's second moment
    underflows to 0 for small-magnitude coordinates and the update blows
    up through the eps denominator. Purely elementwise (no codebook
    gather), so it stays on the VPU.
    """
    if signed:
        scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    else:
        scale = jnp.max(x, axis=-1, keepdims=True)
    safe = jnp.maximum(scale, 1e-30)
    y = x / safe
    codes = jnp.round(jnp.sign(y) * jnp.sqrt(jnp.abs(y)) * qmax)
    lo = -float(qmax) if signed else 0.0
    return jnp.clip(codes, lo, float(qmax)), scale


def _sqrt_map_dequant(codes_f, scales, qmax):
    c = codes_f / qmax
    return jnp.sign(c) * c * c * scales


def _quant_block_math(x, signed):
    codes, scale = _sqrt_map_quant(x, signed, 127.0)
    return codes.astype(jnp.int8), scale


def _dequant_block_math(codes, scales):
    return _sqrt_map_dequant(codes.astype(jnp.float32), scales, 127.0)


# -- "wide" scale layout (the FLAT path) -------------------------------------
# A [nblocks, 1] f32 scale tensor is XLA-tile-padded to 128 lanes at
# rest — a 128x memory blowup (measured: 1.83 GB instead of 15 MB per
# moment at 1.5B params, enough to OOM the one-jit update). The flat
# path stores scales DENSE as [nblocks//128, 128]: scale of codes row
# r lives at [r//128, r%128]. The (R,128)->(R//128,128,128) reshapes
# below split only the sublane dim — free in VMEM.
def _quant_block_math_wide(x, signed):
    R = x.shape[0]
    x3 = x.reshape(R // 128, 128, 128)
    s = jnp.max(jnp.abs(x3) if signed else x3, axis=-1)  # [R//128, 128]
    safe = jnp.maximum(s, 1e-30)
    y = x3 / safe[:, :, None]
    codes = jnp.round(jnp.sign(y) * jnp.sqrt(jnp.abs(y)) * 127.0)
    lo = -127.0 if signed else 0.0
    codes = jnp.clip(codes, lo, 127.0).reshape(R, BLOCK)
    return codes.astype(jnp.int8), s


def _dequant_block_math_wide(codes, s2d):
    R = codes.shape[0]
    c = codes.astype(jnp.float32) / 127.0
    y = jnp.sign(c) * c * c
    y3 = y.reshape(R // 128, 128, 128)
    return (y3 * s2d[:, :, None]).reshape(R, BLOCK)


# -- "tiles" scale layout ----------------------------------------------------
# The block math wants a trailing-1 scale to broadcast over a block's 128
# lanes; at rest that 1 would pad to a whole lane row (the blowup the wide
# layout above avoids), so a TILES leaf keeps [..., R/8, C/128, 8].
def _quant_block_math_tiles(x, signed):
    codes, scale = _quant_block_math(x, signed)
    return codes, scale[..., 0]


def _dequant_block_math_tiles(codes, scales):
    return _dequant_block_math(codes, scales[..., None])


def quantize_8bit(
    x, signed: bool = True, layout: str | None = None
) -> Quantized8:
    """Quantize a leaf; the layout follows its shape (``_layout_for``)
    unless the caller's kernel wants ``BLOCKS``."""
    layout = layout or _layout_for(x.shape)
    x = x.astype(jnp.float32)
    if layout == TILES:
        codes, scales = _quant_block_math_tiles(_to_tiles(x), signed)
    else:
        codes, scales = _quant_block_math(_to_blocks(x), signed)
    return Quantized8(codes, scales, tuple(x.shape), signed, layout)


def dequantize_8bit(q: Quantized8):
    if q.layout == TILES:
        return _from_tiles(
            _dequant_block_math_tiles(q.codes, q.scales), q.shape
        )
    return _from_blocks(_dequant_block_math(q.codes, q.scales), q.shape)


# ---------------------------------------------------------------------------
# fused 8-bit adam update
# ---------------------------------------------------------------------------
def _adam8_block_math(
    g, m, v, lrA, invbc2, eps, b1, b2, classic_eps: bool = True
):
    """Shared fp32 math: returns (m_new, v_new, delta). All [rows, BLOCK].

    Written for the VPU hot path (the 1.5B kernel measured COMPUTE-
    bound, not HBM-bound): the bias corrections arrive premultiplied
    (``lrA = lr/bc1``, ``invbc2 = 1/bc2`` — scalars, computed once per
    update). ``classic_eps`` is a STATIC switch for where the traced
    ``eps`` scalar sits: True = outside the sqrt (the Adam paper form,
    the public default — exact 1/(sqrt+eps) via the rsqrt identity),
    False = inside (adafactor/optax ``eps_root`` convention, one rsqrt
    and no divide — the fastest form, selectable via the optimizers'
    ``eps_root`` argument)."""
    m_new = b1 * m + (1.0 - b1) * g
    v_new = b2 * v + (1.0 - b2) * g * g
    if classic_eps:
        # the straightforward form: sqrt+divide is safe at v == 0
        # (rsqrt identities NaN there), and the kernel is measured
        # structure-bound, not VPU-bound, so the extra op is free
        delta = -lrA * m_new / (jnp.sqrt(v_new * invbc2) + eps)
    else:
        delta = -lrA * m_new * lax.rsqrt(v_new * invbc2 + eps)
    return m_new, v_new, delta


def _adam8_kernel(
    scalar_ref,  # SMEM [3]: lrA (= lr/bc1), invbc2, eps_root  (f32)
    g_ref,  # [R, BLOCK] f32
    mc_ref,  # [R, BLOCK] i8
    ms_ref,  # [R, 1] f32
    vc_ref,  # [R, BLOCK] i8
    vs_ref,  # [R, 1] f32
    mc_out,
    ms_out,
    vc_out,
    vs_out,
    delta_out,  # [R, BLOCK] f32
    *,
    b1: float,
    b2: float,
    classic_eps: bool = True,
):
    lrA, invbc2, eps = (
        scalar_ref[0],
        scalar_ref[1],
        scalar_ref[2],
    )
    g = g_ref[:].astype(jnp.float32)
    m = _dequant_block_math(mc_ref[:], ms_ref[:])
    v = _dequant_block_math(vc_ref[:], vs_ref[:])
    m_new, v_new, delta = _adam8_block_math(
        g, m, v, lrA, invbc2, eps, b1, b2, classic_eps
    )
    mc, ms = _quant_block_math(m_new, signed=True)
    vc, vs = _quant_block_math(v_new, signed=False)
    mc_out[:] = mc
    ms_out[:] = ms
    vc_out[:] = vc
    vs_out[:] = vs
    delta_out[:] = delta.astype(delta_out.dtype)


def _adam8_update_pallas(
    g_blocks, mq, vq, scalars, b1, b2, interpret, classic_eps=True
):
    rows = g_blocks.shape[0]
    r = min(_ROWS, rows)
    if rows % r:
        # pad rows to the grid chunk; padded rows carry zeros
        pad = (-rows) % r
        g_blocks = jnp.pad(g_blocks, ((0, pad), (0, 0)))
        mq = Quantized8(
            jnp.pad(mq.codes, ((0, pad), (0, 0))),
            jnp.pad(mq.scales, ((0, pad), (0, 0))),
            mq.shape,
            mq.signed,
        )
        vq = Quantized8(
            jnp.pad(vq.codes, ((0, pad), (0, 0))),
            jnp.pad(vq.scales, ((0, pad), (0, 0))),
            vq.shape,
            vq.signed,
        )
    nrows = g_blocks.shape[0]
    grid = (nrows // r,)
    row_spec = pl.BlockSpec((r, BLOCK), lambda i: (i, 0))
    scale_spec = pl.BlockSpec((r, 1), lambda i: (i, 0))
    outs = pl.pallas_call(
        functools.partial(
            _adam8_kernel, b1=b1, b2=b2, classic_eps=classic_eps
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            row_spec,
            row_spec,
            scale_spec,
            row_spec,
            scale_spec,
        ],
        out_specs=[row_spec, scale_spec, row_spec, scale_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((nrows, BLOCK), jnp.int8),
            jax.ShapeDtypeStruct((nrows, 1), jnp.float32),
            jax.ShapeDtypeStruct((nrows, BLOCK), jnp.int8),
            jax.ShapeDtypeStruct((nrows, 1), jnp.float32),
            jax.ShapeDtypeStruct((nrows, BLOCK), jnp.float32),
        ],
        interpret=interpret,
    )(scalars, g_blocks, mq.codes, mq.scales, vq.codes, vq.scales)
    mc, ms, vc, vs, delta = outs
    return (
        Quantized8(mc[:rows], ms[:rows], mq.shape, True),
        Quantized8(vc[:rows], vs[:rows], vq.shape, False),
        delta[:rows],
    )


def _adam8_update_jnp(
    g_blocks, mq, vq, scalars, b1, b2, classic_eps=True
):
    """``g_blocks`` is the gradient in the moments' own view: ``[nblocks,
    BLOCK]`` rows for a ``BLOCKS`` (or the flat path's wide) state,
    ``_to_tiles(g)`` for a ``TILES`` one. ``delta`` comes back in the
    same view: the block math reduces over the last axis and broadcasts
    a block's scale along it in every layout."""
    lrA, invbc2, eps = scalars[0], scalars[1], scalars[2]
    if mq.layout == TILES:
        dequant, quant = _dequant_block_math_tiles, _quant_block_math_tiles
    elif mq.scales.shape[-1] == BLOCK:  # flat path's dense scale layout
        dequant, quant = _dequant_block_math_wide, _quant_block_math_wide
    else:
        dequant, quant = _dequant_block_math, _quant_block_math
    m = dequant(mq.codes, mq.scales)
    v = dequant(vq.codes, vq.scales)
    m_new, v_new, delta = _adam8_block_math(
        g_blocks, m, v, lrA, invbc2, eps, b1, b2, classic_eps
    )
    mc, ms = quant(m_new, signed=True)
    vc, vs = quant(v_new, signed=False)
    return (
        Quantized8(mc, ms, mq.shape, True, mq.layout),
        Quantized8(vc, vs, vq.shape, False, vq.layout),
        delta,
    )


# ---------------------------------------------------------------------------
# 4-bit (nibble-packed) state
# ---------------------------------------------------------------------------
@jax.tree_util.register_pytree_node_class
class Quantized4:
    """Blockwise sqrt-map quantized tensor at 4 bits: two codes per
    byte (the platform's int4 dtype is not usable here, so packing is
    explicit). Signed codes live in [-7,7] stored as code+8; unsigned
    in [0,15]. 8x less HBM than fp32 state."""

    def __init__(self, packed, scales, shape, signed):
        self.packed = packed  # uint8 [nblocks, BLOCK//2]
        self.scales = scales  # f32 [nblocks, 1]
        self.shape = tuple(shape)
        self.signed = bool(signed)

    def tree_flatten(self):
        return (self.packed, self.scales), (self.shape, self.signed)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux[0], aux[1])

    def __repr__(self):
        return (
            f"Quantized4(shape={self.shape}, signed={self.signed}, "
            f"nblocks={self.packed.shape[0]})"
        )


def _quant_block_math4(x, signed):
    """x: [rows, BLOCK] f32 → (uint8 packed [rows, BLOCK//2], scales).
    Same sqrt map as 8-bit at qmax 7 (signed, stored +8) / 15
    (unsigned); only the nibble packing is 4-bit-specific."""
    qmax = 7.0 if signed else 15.0
    c, scale = _sqrt_map_quant(x, signed, qmax)
    if signed:
        c = c + 8.0  # [1, 15]
    packed_src = c.astype(jnp.uint8)
    packed = packed_src[:, 0::2] | (packed_src[:, 1::2] << 4)
    return packed, scale


def _dequant_block_math4(packed, scales, signed):
    lo = packed & 0xF
    hi = (packed >> 4) & 0xF
    # interleave back to [rows, BLOCK]
    c = jnp.stack([lo, hi], axis=-1).reshape(packed.shape[0], -1)
    c = c.astype(jnp.float32)
    if signed:
        return _sqrt_map_dequant(c - 8.0, scales, 7.0)
    return _sqrt_map_dequant(c, scales, 15.0)


def quantize_4bit(x, signed: bool = True) -> Quantized4:
    packed, scales = _quant_block_math4(
        _to_blocks(x.astype(jnp.float32)), signed
    )
    return Quantized4(packed, scales, tuple(x.shape), signed)


def dequantize_4bit(q: Quantized4):
    return _from_blocks(
        _dequant_block_math4(q.packed, q.scales, q.signed), q.shape
    )


def _adam4_update_jnp(
    g_blocks, mq, vq, scalars, b1, b2, classic_eps=True
):
    """4-bit first moment, 8-bit second moment. Requantizing v at 4
    bits makes Adam's effective per-coordinate LR noisy enough to stall
    convergence (measured: 3x worse terminal loss on a quadratic);
    the first moment tolerates 4 bits fine — same conclusion as the
    4-bit-optimizer literature, which spends its complexity (rank-1
    factorized scaling) exactly on the second moment."""
    m = _dequant_block_math4(mq.packed, mq.scales, True)
    v = _dequant_block_math(vq.codes, vq.scales)
    m_new, v_new, delta = _adam8_block_math(
        g_blocks, m, v, scalars[0], scalars[1], scalars[2], b1, b2,
        classic_eps,
    )
    mp, ms = _quant_block_math4(m_new, signed=True)
    vc, vs = _quant_block_math(v_new, signed=False)
    return (
        Quantized4(mp, ms, mq.shape, True),
        Quantized8(vc, vs, vq.shape, False),
        delta,
    )


def _adam8_kernel_wide(
    scalar_ref,  # SMEM [3]: lrA (= lr/bc1), invbc2, eps_root  (f32)
    g_ref,  # [R, BLOCK] any float dtype
    mc_ref,  # [R, BLOCK] i8
    ms_ref,  # [R//128, 128] f32 — dense ("wide") scale layout
    vc_ref,
    vs_ref,
    mc_out,
    ms_out,
    vc_out,
    vs_out,
    delta_out,  # [R, BLOCK] in g's dtype
    *,
    b1: float,
    b2: float,
    classic_eps: bool = True,
):
    lrA, invbc2, eps = (
        scalar_ref[0],
        scalar_ref[1],
        scalar_ref[2],
    )
    g = g_ref[:].astype(jnp.float32)
    m = _dequant_block_math_wide(mc_ref[:], ms_ref[:])
    v = _dequant_block_math_wide(vc_ref[:], vs_ref[:])
    m_new, v_new, delta = _adam8_block_math(
        g, m, v, lrA, invbc2, eps, b1, b2, classic_eps
    )
    mc, ms = _quant_block_math_wide(m_new, signed=True)
    vc, vs = _quant_block_math_wide(v_new, signed=False)
    mc_out[:] = mc
    ms_out[:] = ms
    vc_out[:] = vc
    vs_out[:] = vs
    delta_out[:] = delta.astype(delta_out.dtype)


def _adam8_update_pallas_flat(
    g_blocks, mq, vq, scalars, b1, b2, interpret, classic_eps=True
):
    """One pallas pass over a pre-padded flat buffer (rows already a
    multiple of ``_FLAT_ROWS`` — the flat packer guarantees it, so no
    padding copies of GB-scale code arrays happen here). Moment codes
    and scales alias in-place (input_output_aliases): at 1.5B params
    the old+new codes would otherwise double the optimizer state's
    footprint mid-update. Scales use the dense wide layout (see
    ``_quant_block_math_wide``)."""
    nrows = g_blocks.shape[0]
    grid = (nrows // _FLAT_ROWS,)
    row_spec = pl.BlockSpec((_FLAT_ROWS, BLOCK), lambda i: (i, 0))
    scale_spec = pl.BlockSpec((_FLAT_ROWS // 128, 128), lambda i: (i, 0))
    mc, ms, vc, vs, delta = pl.pallas_call(
        functools.partial(
            _adam8_kernel_wide, b1=b1, b2=b2, classic_eps=classic_eps
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            row_spec,
            row_spec,
            scale_spec,
            row_spec,
            scale_spec,
        ],
        out_specs=[row_spec, scale_spec, row_spec, scale_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((nrows, BLOCK), jnp.int8),
            jax.ShapeDtypeStruct((nrows // 128, 128), jnp.float32),
            jax.ShapeDtypeStruct((nrows, BLOCK), jnp.int8),
            jax.ShapeDtypeStruct((nrows // 128, 128), jnp.float32),
            jax.ShapeDtypeStruct((nrows, BLOCK), g_blocks.dtype),
        ],
        input_output_aliases={2: 0, 3: 1, 4: 2, 5: 3},
        interpret=interpret,
    )(scalars, g_blocks, mq.codes, mq.scales, vq.codes, vq.scales)
    return (
        Quantized8(mc, ms, mq.shape, True),
        Quantized8(vc, vs, vq.shape, False),
        delta,
    )


def int8_moments_on(opt_state, mesh) -> tuple:
    """What a trainer asks of the state it built on ``mesh`` (a
    ``MeshConfig``). ``(tiles, blocks)``: elements held by the state's
    ``Quantized8`` moments, by layout tag (both moments counted; 0, 0
    for an fp32 optimizer), which ``PipelineStats.opt_q8_tiles_elems`` /
    ``opt_q8_blocks_elems`` report, so a leaf that fell back to the
    relayout path is seen. And a ValueError for ``adamw_8bit_flat`` on a
    model-sharded mesh."""
    flats = jax.tree.leaves(
        opt_state, is_leaf=lambda x: isinstance(x, Adam8FlatState)
    )
    has_flat = any(isinstance(x, Adam8FlatState) for x in flats)
    if max(mesh.fsdp, mesh.tp, mesh.ep, mesh.sp, mesh.pp) > 1 and has_flat:
        # the flat optimizer concatenates every big leaf per step:
        # on a model-sharded mesh that forces cross-shard
        # all-gathers and replicates the packed moment buffers,
        # silently defeating ZeRO/TP sharding
        raise ValueError(
            "adamw_8bit_flat is for replicated/dp-only states; use "
            "adamw_8bit (per-leaf) with fsdp/tp/ep/sp/pp sharding"
        )
    elems = {TILES: 0, BLOCKS: 0}
    for q in jax.tree.leaves(
        opt_state, is_leaf=lambda x: isinstance(x, Quantized8)
    ):
        if isinstance(q, Quantized8):
            elems[q.layout] += math.prod(q.shape)
    return elems[TILES], elems[BLOCKS]


class Adam8State(NamedTuple):
    count: jnp.ndarray
    mu: optax.Updates  # pytree of Quantized8
    nu: optax.Updates  # pytree of Quantized8


def adamw_8bit(
    learning_rate: float = 1e-3,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    min_quantized_size: int = 4096,
    use_pallas: bool | None = None,
    bits: int = 8,
    eps_root: float = 0.0,
) -> optax.GradientTransformation:
    """AdamW whose moments live in int8 (4x less optimizer-state HBM
    than fp32 Adam) or, with ``bits=4``, a nibble-packed first moment +
    int8 second moment (1.5 B/param, ~5.3x less) — the FSDP/ZeRO memory
    ceiling on big models. Parity: the reference ships both 4- and
    8-bit variants (low_bit/functional.py).

    Tensors smaller than ``min_quantized_size`` keep fp32 moments (the
    reference does the same for small params, where block stats are
    noisy and savings negligible). The fused Pallas kernel covers the
    8-bit path; the 4-bit path (nibble-packed first moment + int8
    second moment, 1.5 B/param state) runs the jnp math — XLA fuses the
    unpack→update→repack chain, and the platform's int4 dtype is not
    usable.

    ``eps`` is the classic Adam epsilon (outside the sqrt). Passing
    ``eps_root`` instead (with eps=0) moves the damping inside the
    sqrt (the optax ``eps_root`` convention) — one rsqrt, the fastest
    form; the two are mutually exclusive to keep the semantics obvious.
    """
    if bits not in (4, 8):
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    if eps_root and eps:
        raise ValueError(
            "pass either eps (classic, outside the sqrt) or eps_root "
            "(inside), not both"
        )
    classic = eps_root == 0.0
    eps_val = eps if classic else eps_root
    def _pallas_enabled():
        if bits != 8:
            return False
        if use_pallas is not None:
            return use_pallas
        return jax.default_backend() == "tpu"

    def init_fn(params):
        # the Pallas tree kernel and the 4-bit update take [nblocks, BLOCK]
        # rows; the jnp 8-bit update reads a leaf where it lies, so there
        # the layout follows the leaf's shape (``_layout_for``)
        layout = BLOCKS if _pallas_enabled() or bits == 4 else None

        def _init_m(p):
            zeros = jnp.zeros_like(p, jnp.float32)
            if p.size < min_quantized_size:
                return zeros
            # bits=4 packs the FIRST moment into nibbles; the second
            # stays int8 (see _adam4_update_jnp) → 1.5 bytes/param
            if bits == 4:
                return quantize_4bit(zeros, True)
            return quantize_8bit(zeros, True, layout)

        def _init_v(p):
            zeros = jnp.zeros_like(p, jnp.float32)
            if p.size < min_quantized_size:
                return zeros
            return quantize_8bit(zeros, False, layout)

        return Adam8State(
            count=jnp.zeros((), jnp.int32),
            mu=jax.tree.map(_init_m, params),
            nu=jax.tree.map(_init_v, params),
        )

    def update_fn(grads, state, params=None):
        count = state.count + 1
        cf = count.astype(jnp.float32)
        lrA = jnp.asarray(learning_rate, jnp.float32) / (1.0 - b1**cf)
        invbc2 = 1.0 / (1.0 - b2**cf)
        scalars = jnp.stack([lrA, invbc2, jnp.float32(eps_val)])

        def _one(g, m, v):
            if not isinstance(m, (Quantized8, Quantized4)):
                # small tensor: plain fp32 adam, same eps placement as
                # the kernel so small and big leaves share semantics
                m_new, v_new, delta = _adam8_block_math(
                    g, m, v, lrA, invbc2, eps_val, b1, b2, classic
                )
                return delta.astype(g.dtype), m_new, v_new
            # the gradient in the moments' own view, delta back through
            # its inverse. For TILES both are bitcasts on the TPU: decay,
            # later scales and apply_updates fuse onto delta, which then
            # never exists in HBM; _to_blocks moves every byte, twice
            tiles = isinstance(m, Quantized8) and m.layout == TILES
            g32 = g.astype(jnp.float32)
            g_view = _to_tiles(g32) if tiles else _to_blocks(g32)
            if isinstance(m, Quantized4):
                mq, vq, delta = _adam4_update_jnp(
                    g_view, m, v, scalars, b1, b2, classic
                )
            elif _pallas_enabled() and not tiles:
                mq, vq, delta = _adam8_update_pallas(
                    g_view, m, v, scalars, b1, b2, interpret=False,
                    classic_eps=classic,
                )
            else:
                mq, vq, delta = _adam8_update_jnp(
                    g_view, m, v, scalars, b1, b2, classic
                )
            if tiles:
                delta = _from_tiles(delta, g.shape)
            else:
                delta = _from_blocks(delta, g.shape)
            return delta.astype(g.dtype), mq, vq

        flat_g, treedef = jax.tree.flatten(grads)
        flat_m = treedef.flatten_up_to(state.mu)
        flat_v = treedef.flatten_up_to(state.nu)
        results = [
            _one(g, m, v) for g, m, v in zip(flat_g, flat_m, flat_v)
        ]
        updates = treedef.unflatten([r[0] for r in results])
        mu = treedef.unflatten([r[1] for r in results])
        nu = treedef.unflatten([r[2] for r in results])

        if weight_decay and params is not None:
            updates = jax.tree.map(
                lambda u, p: u - learning_rate * weight_decay * p,
                updates,
                params,
            )
        return updates, Adam8State(count=count, mu=mu, nu=nu)

    return optax.GradientTransformation(init_fn, update_fn)


class Adam8FlatState(NamedTuple):
    count: jnp.ndarray
    mu: tuple  # per-GROUP Quantized8 buffers over the big leaves
    nu: tuple
    mu_small: jnp.ndarray  # [S] f32 — all small leaves, flat
    nu_small: jnp.ndarray


class _FlatGroup(NamedTuple):
    """One packed group of big leaves (static — computed at trace time
    from leaf shapes, free under jit)."""

    idx: tuple  # leaf positions in this group
    offsets: tuple  # start offset of each leaf (BLOCK-aligned)
    total: int  # padded group size (multiple of BLOCK*_ROWS)


class _FlatLayout(NamedTuple):
    groups: tuple  # of _FlatGroup
    small_idx: tuple
    small_offsets: tuple
    small_total: int


def _flat_layout(
    leaves, min_quantized_size: int, group_elems: int
) -> _FlatLayout:
    """Pack big leaves into groups of ~``group_elems`` elements. Groups
    bound the transient HBM of the update (one group's grad concat +
    delta live at a time) — a single 1.5B-param flat buffer measured
    +6 GB of transients and OOMed next to bf16 params+grads, while
    per-group transients are ~2×group_elems bytes. Each leaf lies in
    its group as ``_to_blocks`` rows, so quantization blocks straddle
    neither leaves nor a leaf's rows (numerics identical to the per-leaf
    tree form)."""
    chunk = BLOCK * _FLAT_ROWS
    groups, g_idx, g_off, off = [], [], [], 0
    g_dtype = None
    small_idx, small_off, soff = [], [], 0

    def _close_group():
        nonlocal g_idx, g_off, off, g_dtype
        if g_idx:
            groups.append(
                _FlatGroup(
                    tuple(g_idx), tuple(g_off), -(-off // chunk) * chunk
                )
            )
            g_idx, g_off, off, g_dtype = [], [], 0, None

    for i, leaf in enumerate(leaves):
        if leaf.size >= min_quantized_size:
            # groups are dtype-HOMOGENEOUS: packing an f32 leaf into a
            # bf16 group would round its grads (and its delta) through
            # bf16, silently diverging from the per-leaf tree form
            if off and (
                off + leaf.size > group_elems or leaf.dtype != g_dtype
            ):
                _close_group()
            g_idx.append(i)
            g_off.append(off)
            g_dtype = leaf.dtype
            off += _blocks_size(leaf.shape)
        else:
            small_idx.append(i)
            small_off.append(soff)
            soff += leaf.size
    _close_group()
    return _FlatLayout(
        tuple(groups), tuple(small_idx), tuple(small_off), soff
    )


def _pack_group(leaves, group: _FlatGroup, dtype):
    """Concatenate one group's leaves (each as its ``_to_blocks`` rows)
    into a flat [group.total] buffer — one fused concat pass per
    group."""
    segs = [
        _to_blocks(leaves[i].astype(dtype)).reshape(-1) for i in group.idx
    ]
    used = group.offsets[-1] + _blocks_size(leaves[group.idx[-1]].shape)
    if group.total - used:
        segs.append(jnp.zeros((group.total - used,), dtype))
    return jnp.concatenate(segs)


def adamw_8bit_flat(
    learning_rate: float = 1e-3,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    min_quantized_size: int = 4096,
    use_pallas: bool | None = None,
    group_elems: int = 1 << 27,
    eps_root: float = 0.0,
) -> optax.GradientTransformation:
    """``adamw_8bit`` with FLAT-BUFFER state: big leaves' moments live
    in a handful of group-packed Quantized8 pairs and the hot path is
    one pallas pass per ~134M-element group (~12 at GPT-2 XL) plus one
    fused concat each — the per-leaf slices back out fuse into the
    apply. The per-leaf (tree) form dispatches ~5 kernels per leaf,
    ~800 launches on GPT-2 XL, measured 170-200 ms against a 38 ms
    flat-buffer roofline (review r3 #1); this form closes that gap.
    ``group_elems`` bounds the transient HBM (one group's grad concat +
    delta at a time) — a single 1.5B flat buffer OOMed next to bf16
    params+grads.

    Numerics are IDENTICAL to ``adamw_8bit``: each leaf lies in its
    group as ``_to_blocks`` rows, so quantization blocks (and their
    scales) never straddle leaves or a leaf's rows. Small leaves (< ``min_quantized_
    size``) keep fp32 moments, packed into one flat f32 vector pair —
    one fused elementwise update instead of ~100 tiny kernels.

    Intended for replicated / single-device training states. Sharded states keep the tree form: a flat
    buffer would force cross-shard concats of every leaf.

    ``eps``/``eps_root`` follow ``adamw_8bit``: classic outside-sqrt
    epsilon, or the faster inside-sqrt form — mutually exclusive.
    """
    if eps_root and eps:
        raise ValueError(
            "pass either eps (classic, outside the sqrt) or eps_root "
            "(inside), not both"
        )
    classic = eps_root == 0.0
    eps_val = eps if classic else eps_root

    def _pallas_enabled():
        if use_pallas is not None:
            return use_pallas
        return jax.default_backend() == "tpu"

    def init_fn(params):
        leaves = jax.tree.flatten(params)[0]
        layout = _flat_layout(leaves, min_quantized_size, group_elems)
        mu, nu = [], []
        for g in layout.groups:
            nblocks = g.total // BLOCK
            # scales in the dense wide layout [nblocks//128, 128] — the
            # natural [nblocks, 1] gets XLA-padded to 128 lanes at
            # rest, a 128x (GBs at 1.5B params) memory blowup
            mu.append(
                Quantized8(
                    jnp.zeros((nblocks, BLOCK), jnp.int8),
                    jnp.zeros((nblocks // 128, 128), jnp.float32),
                    (g.total,),
                    True,
                )
            )
            nu.append(
                Quantized8(
                    jnp.zeros((nblocks, BLOCK), jnp.int8),
                    jnp.zeros((nblocks // 128, 128), jnp.float32),
                    (g.total,),
                    False,
                )
            )
        return Adam8FlatState(
            count=jnp.zeros((), jnp.int32),
            mu=tuple(mu),
            nu=tuple(nu),
            mu_small=jnp.zeros((layout.small_total,), jnp.float32),
            nu_small=jnp.zeros((layout.small_total,), jnp.float32),
        )

    def update_fn(grads, state, params=None):
        count = state.count + 1
        cf = count.astype(jnp.float32)
        lrA = jnp.asarray(learning_rate, jnp.float32) / (1.0 - b1**cf)
        invbc2 = 1.0 / (1.0 - b2**cf)
        scalars = jnp.stack([lrA, invbc2, jnp.float32(eps_val)])
        leaves, treedef = jax.tree.flatten(grads)
        layout = _flat_layout(leaves, min_quantized_size, group_elems)
        out = [None] * len(leaves)

        mq_groups, vq_groups = [], []
        for gi, group in enumerate(layout.groups):
            # grads stay in their own dtype (bf16, say) —
            # the kernel upcasts per block in VMEM; a f32 flat buffer
            # would double the transient HBM
            gflat = _pack_group(leaves, group, leaves[group.idx[0]].dtype)
            g_blocks = gflat.reshape(-1, BLOCK)
            if _pallas_enabled():
                mq, vq, delta = _adam8_update_pallas_flat(
                    g_blocks, state.mu[gi], state.nu[gi], scalars,
                    b1, b2, interpret=False, classic_eps=classic,
                )
            else:
                mq, vq, delta = _adam8_update_jnp(
                    g_blocks.astype(jnp.float32), state.mu[gi],
                    state.nu[gi], scalars, b1, b2, classic,
                )
            mq_groups.append(mq)
            vq_groups.append(vq)
            delta_flat = delta.reshape(-1)
            for k, i in enumerate(group.idx):
                off = group.offsets[k]
                n = _blocks_size(leaves[i].shape)
                out[i] = _from_blocks(
                    lax.slice(delta_flat, (off,), (off + n,)),
                    leaves[i].shape,
                ).astype(leaves[i].dtype)

        if layout.small_idx:
            gs = jnp.concatenate(
                [
                    leaves[i].reshape(-1).astype(jnp.float32)
                    for i in layout.small_idx
                ]
            )
            m_new, v_new, ds = _adam8_block_math(
                gs, state.mu_small, state.nu_small, lrA, invbc2,
                eps_val, b1, b2, classic,
            )
            for k, i in enumerate(layout.small_idx):
                n = leaves[i].size
                off = layout.small_offsets[k]
                out[i] = (
                    lax.slice(ds, (off,), (off + n,))
                    .reshape(leaves[i].shape)
                    .astype(leaves[i].dtype)
                )
        else:
            m_new, v_new = state.mu_small, state.nu_small

        updates = treedef.unflatten(out)
        if weight_decay and params is not None:
            updates = jax.tree.map(
                lambda u, p: u - learning_rate * weight_decay * p,
                updates,
                params,
            )
        return updates, Adam8FlatState(
            count=count,
            mu=tuple(mq_groups),
            nu=tuple(vq_groups),
            mu_small=m_new,
            nu_small=v_new,
        )

    return optax.GradientTransformation(init_fn, update_fn)


def adamw_4bit(**kwargs) -> optax.GradientTransformation:
    """"4-bit" AdamW (nibble-packed first moment + int8 second moment):
    1.5 B/param of optimizer state vs 8 for fp32 Adam. Parity: the
    reference's 4-bit low-bit optimizer (which spends rank-1 factorized
    scaling on the second moment; here it keeps 8 bits instead — same
    memory class, far simpler, and it tracks fp32 trajectories in
    tests)."""
    return adamw_8bit(bits=4, **kwargs)
