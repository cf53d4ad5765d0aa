"""8-bit (blockwise-quantized state) AdamW for TPU: ``adamw_8bit``.

Parity: ATorch's low-bit optimizer — python driver
atorch/atorch/optimizers/low_bit/functional.py (vectorwise/blockwise
quantization, linear + nonlinear qmaps) backed by the CUDA kernels in
atorch/atorch/ops/csrc/{quantize.cu,dequantize.cu,quantization_optimizer.cu}.

The moments are int8 codes + one f32 scale per 128-element block: 128
consecutive elements of one row of the leaf, in either layout (a row
whose width is no multiple of 128 is padded to whole blocks,
``_to_blocks``: a block never holds the end of one row and the start of
the next). Block size 128 = one lane row, so a block's maximum is a
reduction along lanes. Quantization goes through a sqrt map
(``_sqrt_map_quant``: codes = round(sign(y) sqrt|y| * 127), y = x / block
max): on TPU a nonlinear 256-entry codebook lookup per element (the
reference's dynamic map) would serialize into gathers; the sqrt map keeps
the whole update elementwise on the VPU and keeps small second moments
from rounding to zero; a second moment more than 254^2 times under its
block's largest, which the map alone would still round to zero, reads
the least code, 1. Nothing a caller sets selects a code path:

**The layout follows the leaf** (``_layout_for``, from the shape alone:
the leaf is what the gradient, the parameter and the apply already are).
A leaf whose last two dimensions are whole (8, 128) tiles keeps its
moments in ``TILES``, the leaf's own tile order in HBM (codes ``[...,
R/8, C/128, 8, 128]``, scales ``[..., R/8, C/128, 8]``): the TPU compiler
takes the view of the gradient and the way back of delta as bitcasts.
Any other leaf (1-D, odd widths) takes ``BLOCKS``, ``[nblocks, 128]``
rows, padded; on the TPU that flattening is a physical relayout of the
gradient and another of delta (an (8, 128) tiled ``[..., 1024]`` array
does not lie in rows of 128): 22 of the 81 ms of the OLMoE cell's
optimizer pass when every leaf took it (PERF.md §6, PR 28). ``bits=4``
(``adamw_4bit``: a nibble-packed first moment, another state size) reads
rows, so it keeps every leaf in ``BLOCKS``.

**The step follows the layout, the backend and the caller's entry**, and
there are two:

- ``update`` (and then ``optax.apply_updates``), the statement
  (``_adam8_update_jnp``): plain jnp that XLA fuses, in every layout and
  on every backend. The per-block maximum is a lane reduce over the last
  axis, and decay and ``apply_updates`` fuse onto delta, which never
  exists in HBM. XLA makes four passes a ``TILES`` leaf (two block-maximum
  reduces, the parameter, the requantise): 24 bytes an element with a
  bf16 gradient. What a mesh of several devices, an offloaded state, a
  step that does not donate and every caller that wants the updates run.
- ``update_and_apply`` (``InPlaceTransformation``: ``(grads, state,
  params) -> (new_params, new_state)``), which gives a ``TILES`` leaf on a
  TPU (``takes_kernel``, read from the leaf and the backend) to ONE
  kernel, ``_q8_adam_step`` (the Pallas call ``q8_adam_step``): gradient,
  parameter, codes and scales read once where they lie, dequantise, Adam,
  decay and scale, requantise in registers through the functions the
  statement calls, parameter and moments written in place
  (``input_output_aliases``): 14 bytes an element (16 with a float32
  gradient) and one pass. Every other leaf falls through to the
  statement. ``in_place_entry`` says where a step may call the entry
  (``models/train.build_train_step``, ``parallel/pipeline.py``). The
  kernel's vector code is written for one strip of ``_STRIP`` tiles and
  looped, its block is ``_STEP_TILES`` tiles whatever the leaf's width
  (``_step_blocking``), and every call goes through one ``jax.jit`` whose
  per-step numbers ride in SMEM, so a program holds one lowered function a
  (shape, gradient dtype) and a small executable a kernel: a call site
  adds little to a program's first step (PERF.md §6, PR 61; PR 60's
  kernel, unrolled over a block 32 quantization blocks wide, cost the
  Nemotron cell 12 s there).

The state at rest is the statement's, so the two may take turns on one
state (a checkpoint of either restores under the other), and the same
math (``_adam8_block_math``, ``_sqrt_map_*``) runs in both, so numerics
agree up to rounding ties.
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

# where a Quantized8's 128-element blocks lie: static aux data that
# ``_layout_for`` decides from the leaf's shape, never an argument. For a
# leaf [..., R, C]:
BLOCKS = "blocks"  # codes [nblocks, BLOCK], scales [nblocks, 1]
# codes [..., R/8, C/BLOCK, 8, BLOCK], scales [..., R/8, C/BLOCK, 8]
TILES = "tiles"
_SUBLANES = 8  # rows of one f32 (8, 128) tile
# the one-pass step of a TILES leaf (``_q8_adam_step``). Tiles of one
# strip: the kernel's vector code is emitted for one strip and looped
# over the VMEM block, so a kernel's size does not go with its block or
# its leaf; sixteen tiles that do not depend on each other are what hides
# the lane reduces' and the square roots' latency (stand-alone on a
# [64, 2048, 1024] leaf PR 60 read 6.04 ms with 4 tiles in flight, 4.76
# with 8, 4.25 with 16 and 4.39 with 32; this kernel 5.61 ms with 8,
# 4.72 with 16, 4.36 with 32 and 4.41 with 64: PERF.md §6)
_STRIP = 32
# tiles a grid step: 262k elements, whatever the leaf's width. A grid
# step costs ~3.6 us (measured: at 256 rows of 128 a step a pass over
# 1.5B parameters sat at ~47k steps and ~170 ms, step-bound, not
# HBM-bound); 262k elements a step put such a pass back on the HBM
# roofline
_STEP_TILES = 256


# ---------------------------------------------------------------------------
# layouts
# ---------------------------------------------------------------------------
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


# ---------------------------------------------------------------------------
# the block math every path shares
# ---------------------------------------------------------------------------
def _sqrt_map_quant(x, signed, qmax):
    """Shared sqrt-map core: x [rows, N] f32 → (float codes in
    [-qmax, qmax] or [0, qmax], scales [rows, 1]).

    Power-2 ("sqrt") map, the reference's ``power-2`` qmap
    (low_bit/functional.py:531 ``create_pow_map``): normalize to the block
    max, code = round(sign(y)*sqrt(|y|)*qmax). The sqrt spreads codes
    toward zero, so the smallest representable nonzero value is
    scale/qmax^2 instead of scale/qmax — without it Adam's second moment
    underflows to 0 for small-magnitude coordinates and the update blows
    up through the eps denominator. An unsigned block's (second moments')
    least code is 1 for the same reason.
    Purely elementwise (no codebook gather), so it stays on the VPU.
    """
    # the codes of clip(round(sign(y) sqrt|y| qmax)), y = x / safe, in
    # fewer vector operations and bit for bit (the one-pass kernel is bound
    # by them): |y| is |x| / safe, a rounded product keeps its sign, no
    # finite |y| passes 1, and an unsigned block holds nothing below 0
    # (nor, since PR 67, below the least code's value)
    if signed:
        a = jnp.abs(x)
        scale = jnp.max(a, axis=-1, keepdims=True)
        safe = jnp.maximum(scale, 1e-30)
    else:
        # a second moment's least code is 1: one element whose gradient
        # is 254 times its neighbours' (a rare token's column of the head
        # under a loss weight of 1 / t: PERF.md §6, PR 67) would round
        # theirs to 0 beside first moments that are not, and their next
        # update would be m / eps, thousands of learning rates. Rounded up
        # the update errs small instead. The floor stands where the guard
        # against values below 0 stood (a block's own scalar, no vector
        # operation more): y >= (0.75 / qmax)^2 rounds to 1 or above. An
        # element that never met a gradient reads scale / qmax^2 too: its
        # first moment is 0, so it moves as little as before, and a block
        # of such elements has scale 0
        scale = jnp.max(x, axis=-1, keepdims=True)
        safe = jnp.maximum(scale, 1e-30)
        a = jnp.maximum(x, safe * (0.75 / qmax) ** 2)
    t = jnp.round(jnp.sqrt(a / safe) * qmax)
    return (jnp.where(x < 0, -t, t) if signed else t), scale


def _sqrt_map_dequant(codes_f, scales, qmax):
    c = codes_f / qmax
    return jnp.abs(c) * c * scales  # sign(c) c c, one operation less


def _quant_block_math(x, signed):
    codes, scale = _sqrt_map_quant(x, signed, 127.0)
    return codes.astype(jnp.int8), scale


def _dequant_block_math(codes, scales):
    return _sqrt_map_dequant(codes.astype(jnp.float32), scales, 127.0)


# -- "tiles" scale layout ----------------------------------------------------
# The block math wants a trailing-1 scale to broadcast over a block's 128
# lanes; at rest that 1 pads to a whole lane row (128 times the bytes, as a
# BLOCKS leaf's [nblocks, 1] scales do), so a TILES leaf keeps
# [..., R/8, C/128, 8].
def _quant_block_math_tiles(x, signed):
    codes, scale = _quant_block_math(x, signed)
    return codes, scale[..., 0]


def _dequant_block_math_tiles(codes, scales):
    return _dequant_block_math(codes, scales[..., None])


def quantize_8bit(
    x, signed: bool = True, layout: str | None = None
) -> Quantized8:
    """Quantize a leaf; the layout follows its shape (``_layout_for``)
    unless the caller reads rows and asks for ``BLOCKS`` (the 4-bit
    state's second moment)."""
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


# ---------------------------------------------------------------------------
# the statement
# ---------------------------------------------------------------------------
def _adam8_update_jnp(
    g_blocks, mq, vq, scalars, b1, b2, classic_eps=True
):
    """The statement: ``(m', v', delta)`` of one leaf as plain jnp.
    ``g_blocks`` is the gradient in the moments' own view: ``[nblocks,
    BLOCK]`` rows for a ``BLOCKS`` state, ``_to_tiles(g)`` for a ``TILES``
    one. ``delta`` comes back in the same view: the block math reduces
    over the last axis and broadcasts a block's scale along it in either
    layout."""
    lrA, invbc2, eps = scalars[0], scalars[1], scalars[2]
    if mq.layout == TILES:
        dequant, quant = _dequant_block_math_tiles, _quant_block_math_tiles
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


# ---------------------------------------------------------------------------
# the one-pass step of a TILES leaf
# ---------------------------------------------------------------------------
def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    """The one-pass kernel is compiled where the backend is a TPU and
    interpreted where a test asks for it anywhere else."""
    return jax.default_backend() != "tpu"


def _lane_dim(dims) -> int:
    """Which dimension of a ``TILES`` leaf's tile grid ``[..., R/8,
    C/128]`` lies along the lanes where the TPU keeps its scales
    ``[..., R/8, C/128, 8]``: the chip lays such a narrow-ended array out
    with the eight rows of a tile along the sublanes and, along the lanes,
    the dimension that pads least to whole lane rows, the later of two
    that pad alike (``{1,3,2,0}`` for ``[64, 256, 8, 8]``, ``{1,2,0}`` for
    ``[336, 128, 8]``, ``{0,3,2,1}`` for ``[2048, 2, 1, 8]``: seen in
    compiles for a v5e). The one-pass kernel reads the scales in that
    order, so the view is a bitcast; were the chip to choose otherwise it
    is a copy of 1/128 of the leaf, and nothing else changes."""
    return min(
        range(len(dims)),
        key=lambda k: (-(-dims[k] // BLOCK) * BLOCK / dims[k], -k),
    )


def _step_blocking(dims) -> tuple:
    """``(a, b, T, J)`` for a tile grid ``dims``: one program of the
    one-pass kernel takes ``T`` tiles along dimension ``a`` (the scales'
    lanes, ``_lane_dim``; at most one lane row) by ``J`` along ``b``, the
    other dimension that best fills ``_STEP_TILES`` (the later of two that
    do alike: neighbours in a row of tiles are neighbours in HBM); every
    other dimension is the grid's. The same rule for every leaf, so no
    width gets a block, or a kernel, of its own size."""
    a = _lane_dim(dims)
    T = min(dims[a], BLOCK)
    want = max(_STEP_TILES // T, 1)
    b = max(
        (k for k in range(len(dims)) if k != a),
        key=lambda k: (min(dims[k], want), k),
    )
    return a, b, T, min(dims[b], want)


def _scales_by_lane(s, a: int):
    """``[..., R/8, C/128, 8]`` (a tile's eight rows last, as the block
    math makes them and the state keeps them) with dimension ``a`` moved
    behind the eight: the view in which the one-pass kernel reads a
    ``TILES`` leaf's scales, a tile a lane (see ``_lane_dim``)."""
    return jnp.moveaxis(s, a, -1)


def _adam8_step_kernel(
    scalar_ref,  # SMEM [5]: lrA, invbc2, eps, lr * weight_decay, scale
    g_ref,  # the block's data: see ``by_rows``
    p_ref,
    mc_ref,
    ms_ref,  # [J, 8, T] f32: tile (i, j)'s eight scales at [j, :, i]
    vc_ref,
    vs_ref,
    p_out,
    mc_out,
    ms_out,
    vc_out,
    vs_out,
    *,
    b1: float,
    b2: float,
    classic_eps: bool,
    by_rows: bool,
    lanes_first: bool,
):
    """One program: ``T`` by ``J`` tiles of one leaf (``_step_blocking``).
    The body is written for ONE strip of ``_STRIP`` tiles (neighbours
    along ``T``) and looped over the VMEM block, so the vector code is as
    long as a strip and not as the block, and the strip is one array to
    the tracer, so the traced body is as long as one tile's. A strip goes
    through the module's shared math in registers: dequantise, Adam, decay
    and scale, the parameter, requantise; a tile's scales are one lane of
    the block's ``[8, T]`` scales, taken out by a masked lane sum and put
    back by a select.

    ``by_rows``: the data are ``[8 T, 128 J]``, rows of the leaf (``T``
    runs along the leaf's rows), and a strip ``[8 _STRIP, 128]``: the int8
    codes then fill their vector registers (32 rows each). Else they are
    ``[T, J, 8, 128]`` (``[J, T, 8, 128]`` unless ``lanes_first``), tiles
    of any two dimensions of the leaf, and a strip ``[_STRIP, 8, 128]``, a
    quarter of a register a tile of codes; there a last strip that would
    hang over starts earlier and makes a few tiles twice (inputs and
    outputs are different VMEM buffers)."""
    lrA, invbc2, eps = scalar_ref[0], scalar_ref[1], scalar_ref[2]
    decay, scale = scalar_ref[3], scalar_ref[4]
    blocks, _, tiles = ms_ref.shape
    strip = min(_STRIP, tiles)
    lanes = lax.broadcasted_iota(jnp.int32, (strip, _SUBLANES, tiles), 2)
    nth = lax.broadcasted_iota(jnp.int32, (strip, _SUBLANES, tiles), 0)
    lane = lanes[0]

    def one_block(j, carry):
        ms, vs = ms_ref[j], vs_ref[j]  # [8, T]

        def one_strip(s, new):
            first = jnp.minimum(s * strip, tiles - strip)
            if by_rows:
                rows = strip * _SUBLANES
                at = (
                    pl.ds(pl.multiple_of(first * _SUBLANES, rows), rows),
                    pl.ds(pl.multiple_of(j * BLOCK, BLOCK), BLOCK),
                )
            else:
                at = pl.ds(first, strip)
                at = (at, j) if lanes_first else (j, at)
            own = lanes == first + nth  # tile k of the strip: its lane
            here = (lane >= first) & (lane < first + strip)

            def taken(scales):  # [8, T] -> a strip's, a row a sublane
                mine = jnp.sum(
                    jnp.where(own, scales[None], 0.0), axis=2, keepdims=True
                )
                return mine.reshape(-1, 1) if by_rows else mine

            def put(mine, scales):  # a strip's new scales into [8, T]
                mine = mine.reshape(strip, _SUBLANES, 1)
                return jnp.where(
                    here, jnp.sum(jnp.where(own, mine, 0.0), axis=0), scales
                )

            p, g = p_ref[at], g_ref[at]
            m = _dequant_block_math(mc_ref[at], taken(ms))
            v = _dequant_block_math(vc_ref[at], taken(vs))
            m, v, delta = _adam8_block_math(
                g.astype(jnp.float32), m, v, lrA, invbc2, eps, b1, b2,
                classic_eps,
            )
            # the statement hands delta back in the gradient's dtype
            delta = delta.astype(g.dtype).astype(jnp.float32)
            # no decay is a 0 and no scale a 1: exact in float32
            p_out[at] = p + scale * (delta - decay * p)
            mc_out[at], m_scale = _quant_block_math(m, signed=True)
            vc_out[at], v_scale = _quant_block_math(v, signed=False)
            return put(m_scale, new[0]), put(v_scale, new[1])

        ms_out[j], vs_out[j] = lax.fori_loop(
            0, pl.cdiv(tiles, strip), one_strip, (ms, vs)
        )
        return carry

    lax.fori_loop(0, blocks, one_block, 0)


@functools.partial(
    jax.jit, static_argnames=("b1", "b2", "classic_eps", "interpret")
)
def _q8_adam_step(
    scalars, g, p, mc, ms, vc, vs, *, b1, b2, classic_eps, interpret
):
    """``(p', mc', ms', vc', vs')`` of one ``TILES`` leaf in one pass over
    HBM: gradient, parameter, codes and scales read once where they lie
    (``_to_tiles`` / ``_from_tiles``, ``_scales_by_lane``: views the TPU
    compiler takes as bitcasts), parameter, codes and scales written in
    place (``input_output_aliases``: a donating step holds nothing new).
    Codes and scales come and go as the state keeps them.

    One jit for every call site: what differs between two steps or two
    leaves of one shape rides in ``scalars``, so a program lowers this
    function once a (shape, gradient dtype) and calls it once a leaf. The
    block is ``_STEP_TILES`` tiles whatever the leaf's shape
    (``_step_blocking``); a last block that hangs over the leaf is
    computed and not written."""
    shape = p.shape
    dims = (*shape[:-2], shape[-2] // _SUBLANES, shape[-1] // BLOCK)
    n = len(dims)
    a, b, T, J = _step_blocking(dims)
    rest = [k for k in range(n) if k != a]
    # the scales' lanes run along the leaf's rows, its blocks beside them,
    # in whole strips: the data as the leaf's rows, else as its tiles
    by_rows = (a, b) == (n - 2, n - 1) and T % _STRIP == 0

    def blocked(k):
        return T if k == a else J if k == b else None

    def as_rows(x):
        return _from_tiles(x, shape)

    def as_is(x):
        return x

    # how the leaf's own arrays (gradient, parameter) and the codes, which
    # rest in the tile view, enter the kernel, and how they leave it
    if by_rows:
        data = pl.BlockSpec(
            (*(None,) * (n - 2), T * _SUBLANES, J * BLOCK), lambda *at: at
        )
        held = shape
        leaf_in, codes_in, leaf_out, codes_out = (
            as_is, as_rows, as_is, _to_tiles
        )
    else:
        data = pl.BlockSpec(
            (*map(blocked, range(n)), _SUBLANES, BLOCK),
            lambda *at: (*at, 0, 0),
        )
        held = (*dims, _SUBLANES, BLOCK)
        leaf_in, codes_in, leaf_out, codes_out = (
            _to_tiles, as_is, as_rows, as_is
        )
    scale = pl.BlockSpec(
        (*map(blocked, rest), _SUBLANES, T),
        lambda *at: (*(at[k] for k in rest), 0, at[a]),
    )
    codes = jax.ShapeDtypeStruct(held, jnp.int8)
    scales = jax.ShapeDtypeStruct(
        (*(dims[k] for k in rest), _SUBLANES, dims[a]), jnp.float32
    )
    p_new, mc, ms, vc, vs = pl.pallas_call(
        functools.partial(
            _adam8_step_kernel, b1=b1, b2=b2, classic_eps=classic_eps,
            by_rows=by_rows, lanes_first=a < b,
        ),
        grid=tuple(
            pl.cdiv(dims[k], blocked(k) or 1) for k in range(n)
        ),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            data, data, data, scale, data, scale,
        ],
        out_specs=[data, data, scale, data, scale],
        out_shape=[
            jax.ShapeDtypeStruct(held, jnp.float32),
            codes, scales, codes, scales,
        ],
        input_output_aliases={2: 0, 3: 1, 4: 2, 5: 3, 6: 4},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * n,
            vmem_limit_bytes=48 << 20,
        ),
        name="q8_adam_step",
        interpret=interpret,
    )(
        scalars, leaf_in(g), leaf_in(p), codes_in(mc),
        _scales_by_lane(ms, a), codes_in(vc), _scales_by_lane(vs, a),
    )
    return (
        leaf_out(p_new), codes_out(mc), jnp.moveaxis(ms, -1, a),
        codes_out(vc), jnp.moveaxis(vs, -1, a),
    )


# ---------------------------------------------------------------------------
# the optimizer
# ---------------------------------------------------------------------------
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
    noisy and savings negligible). Where each leaf's moments lie and how
    its step runs is read from the leaf, the backend and the entry the
    caller takes (the module's docstring); the 4-bit state runs the
    statement's jnp math — XLA fuses the unpack→update→repack chain, and
    the platform's int4 dtype is not usable.

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

    def init_fn(params):
        def _init_m(p):
            zeros = jnp.zeros_like(p, jnp.float32)
            if p.size < min_quantized_size:
                return zeros
            # bits=4 packs the FIRST moment into nibbles; the second
            # stays int8 (see _adam4_update_jnp) → 1.5 bytes/param
            if bits == 4:
                return quantize_4bit(zeros, True)
            return quantize_8bit(zeros, True)

        def _init_v(p):
            zeros = jnp.zeros_like(p, jnp.float32)
            if p.size < min_quantized_size:
                return zeros
            # the 4-bit update takes [nblocks, BLOCK] rows; the 8-bit
            # update reads a leaf where it lies, so there the layout
            # follows the leaf's shape (``_layout_for``)
            return quantize_8bit(zeros, False, BLOCKS if bits == 4 else None)

        return Adam8State(
            count=jnp.zeros((), jnp.int32),
            mu=jax.tree.map(_init_m, params),
            nu=jax.tree.map(_init_v, params),
        )

    def _bias_corrected(count):
        cf = count.astype(jnp.float32)
        lrA = jnp.asarray(learning_rate, jnp.float32) / (1.0 - b1**cf)
        return lrA, 1.0 / (1.0 - b2**cf)

    def _one(g, m, v, lrA, invbc2):
        """``(delta, m', v')`` of one leaf: the statement the one-pass
        kernel is held to."""
        if not isinstance(m, (Quantized8, Quantized4)):
            # small tensor: plain fp32 adam, same eps placement as
            # the quantized leaves so small and big share semantics
            m_new, v_new, delta = _adam8_block_math(
                g, m, v, lrA, invbc2, eps_val, b1, b2, classic
            )
            return delta.astype(g.dtype), m_new, v_new
        scalars = jnp.stack([lrA, invbc2, jnp.float32(eps_val)])
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
        else:
            mq, vq, delta = _adam8_update_jnp(
                g_view, m, v, scalars, b1, b2, classic
            )
        if tiles:
            delta = _from_tiles(delta, g.shape)
        else:
            delta = _from_blocks(delta, g.shape)
        return delta.astype(g.dtype), mq, vq

    def _decayed(u, p):
        return u - learning_rate * weight_decay * p

    def _leaves(grads, state):
        flat_g, treedef = jax.tree.flatten(grads)
        return (
            treedef, flat_g, treedef.flatten_up_to(state.mu),
            treedef.flatten_up_to(state.nu),
        )

    def update_fn(grads, state, params=None):
        count = state.count + 1
        lrA, invbc2 = _bias_corrected(count)
        treedef, flat_g, flat_m, flat_v = _leaves(grads, state)
        results = [
            _one(g, m, v, lrA, invbc2)
            for g, m, v in zip(flat_g, flat_m, flat_v)
        ]
        updates, mu, nu = (
            treedef.unflatten([r[i] for r in results]) for i in range(3)
        )
        if weight_decay and params is not None:
            updates = jax.tree.map(_decayed, updates, params)
        return updates, Adam8State(count=count, mu=mu, nu=nu)

    def update_and_apply_fn(grads, state, params, scale=None):
        """``(new_params, new_state)``: what ``update``, ``scale`` times
        the whole update (decay included, as ``optax.scale`` after this
        transformation) and ``optax.apply_updates`` make, leaf by leaf.
        A ``TILES`` leaf on a TPU takes the one-pass kernel, which writes
        the parameter and the moments where they lie; any other leaf and
        any other backend the statement, as ``update`` does."""
        from dlrover_tpu.common import trace_counts

        count = state.count + 1
        lrA, invbc2 = _bias_corrected(count)
        scalars = jnp.stack([
            lrA, invbc2, jnp.float32(eps_val),
            jnp.asarray(learning_rate * weight_decay, jnp.float32),
            jnp.asarray(1.0 if scale is None else scale, jnp.float32),
        ])

        def _step(g, p, m, v):
            if takes_kernel(p, m):
                # both moments, as ``int8_moments_on`` counts them
                trace_counts.count("opt_q8_kernel_elems", 2 * p.size)
                p_new, mc, ms, vc, vs = _q8_adam_step(
                    scalars, g, p, m.codes, m.scales, v.codes, v.scales,
                    b1=b1, b2=b2, classic_eps=classic,
                    interpret=_interpret(),
                )
                return (
                    p_new,
                    Quantized8(mc, ms, m.shape, True, TILES),
                    Quantized8(vc, vs, v.shape, False, TILES),
                )
            u, m, v = _one(g, m, v, lrA, invbc2)
            if weight_decay:
                u = _decayed(u, p)
            if scale is not None:
                u = scale * u
            return optax.apply_updates(p, u), m, v

        treedef, flat_g, flat_m, flat_v = _leaves(grads, state)
        results = [
            _step(g, p, m, v)
            for g, p, m, v in zip(
                flat_g, treedef.flatten_up_to(params), flat_m, flat_v
            )
        ]
        new_params, mu, nu = (
            treedef.unflatten([r[i] for r in results]) for i in range(3)
        )
        return new_params, Adam8State(count=count, mu=mu, nu=nu)

    return InPlaceTransformation(init_fn, update_fn, update_and_apply_fn)


def takes_kernel(p, m) -> bool:
    """THE rule for how ``update_and_apply`` executes a leaf's step, read
    from the leaf and the backend: the one-pass kernel where the moments
    are ``Quantized8`` in ``TILES``, the parameter is float32 and the
    backend is a TPU; the statement (``update`` and
    ``optax.apply_updates``) everywhere else. Whether one device owns the
    program is the caller's to know (``in_place_entry``): GSPMD refuses to
    partition a Mosaic call."""
    return (
        isinstance(m, Quantized8)
        and m.layout == TILES
        and p.dtype == jnp.float32
        and _on_tpu()
    )


class InPlaceTransformation(optax.GradientTransformationExtraArgs):
    """``init`` and ``update`` as optax has them (still the pair every
    optax caller unpacks), and one more entry, ``update_and_apply(grads,
    state, params) -> (new_params, new_state)``: what ``update`` and
    ``optax.apply_updates`` make, for a transformation that can write a
    parameter where it lies instead of handing back an update for XLA to
    apply (``adamw_8bit``'s one-pass kernel)."""

    def __new__(cls, init, update, update_and_apply):
        self = super().__new__(cls, init, update)
        self.update_and_apply = update_and_apply
        return self


def in_place_entry(tx, *, devices: int, donate: bool, resident: bool = True):
    """``tx.update_and_apply`` where a step may call it in place of
    ``tx.update`` and ``optax.apply_updates``, else None: the
    transformation has the entry, one device owns the program, and the
    state is on it and is the step's to overwrite. A step that does not
    donate keeps the two lines: in place there means a copy of every leaf
    first (1.1 to 3.0 GiB more in the compile of the eight int8 steps,
    PERF.md §6, PR 60)."""
    if devices == 1 and donate and resident:
        return getattr(tx, "update_and_apply", None)
    return None


def adamw_4bit(**kwargs) -> optax.GradientTransformation:
    """"4-bit" AdamW (nibble-packed first moment + int8 second moment):
    1.5 B/param of optimizer state vs 8 for fp32 Adam. Parity: the
    reference's 4-bit low-bit optimizer (which spends rank-1 factorized
    scaling on the second moment; here it keeps 8 bits instead — same
    memory class, far simpler, and it tracks fp32 trajectories in
    tests)."""
    return adamw_8bit(bits=4, **kwargs)


def int8_moments_on(opt_state, mesh) -> tuple:
    """What a trainer asks of the state it built on ``mesh`` (a
    ``MeshConfig``). ``(tiles, blocks)``: elements held by the state's
    ``Quantized8`` moments, by layout tag (both moments counted; 0, 0
    for an fp32 optimizer), which ``PipelineStats.opt_q8_tiles_elems`` /
    ``opt_q8_blocks_elems`` report, so a leaf that fell back to the
    relayout path is seen. Whole leaves are counted, so the answer is
    the same on every mesh."""
    del mesh
    elems = {TILES: 0, BLOCKS: 0}
    for q in jax.tree.leaves(
        opt_state, is_leaf=lambda x: isinstance(x, Quantized8)
    ):
        if isinstance(q, Quantized8):
            elems[q.layout] += math.prod(q.shape)
    return elems[TILES], elems[BLOCKS]
