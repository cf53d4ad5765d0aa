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

- ``adamw_8bit`` with ``use_pallas=False`` (the benchmark's eight int8
  cells, and every backend but the TPU by default). A leaf whose last two
  dimensions are whole (8, 128) tiles keeps its moments in ``TILES``
  layout, the leaf's own tile order in HBM (codes ``[..., R/8, C/128, 8,
  128]``, scales ``[..., R/8, C/128, 8]``), and has two ways to run:
  - ``update`` (and then ``optax.apply_updates``), the statement: plain
    jnp that XLA fuses. It views the gradient and hands back delta
    through a reshape + transpose that the TPU compiler takes as a
    bitcast, the per-block maximum is a lane reduce over the last axis,
    and decay and ``apply_updates`` fuse onto delta, which never exists in
    HBM. XLA still makes four passes a leaf (two block-maximum reduces,
    the parameter, the requantise): 24 bytes an element with a bf16
    gradient, against 14 for one pass. What every backend but the TPU
    runs, and on the TPU a mesh of several devices, an offloaded state,
    a step that does not donate and every caller that wants the updates.
  - ``update_and_apply`` (``InPlaceTransformation``: ``(grads, state,
    params) -> (new_params, new_state)``), which on a TPU gives such a
    leaf to ONE kernel, ``_q8_adam_step`` (the Pallas call
    ``q8_adam_step``): gradient, parameter, codes and scales read once
    where they lie, dequantise, Adam, decay and scale, requantise in
    registers through the functions the statement calls, parameter and
    moments written in place (``input_output_aliases``): 14 bytes an
    element (16 with a float32 gradient) and one pass. ``takes_kernel`` is
    the rule, read from the leaf and the backend; ``in_place_entry`` says
    where a step may call the entry (``models/train.build_train_step``,
    ``parallel/pipeline.py``). The kernel's vector code is written for one
    strip of ``_STRIP`` tiles and looped, its block is ``_STEP_TILES``
    tiles whatever the leaf's width (``_step_blocking``), and every call
    goes through one ``jax.jit`` whose per-step numbers ride in SMEM, so a
    program holds one lowered function a (shape, gradient dtype) and a
    small executable a kernel: a call site adds little to a program's
    first step (PERF.md §6, PR 61; PR 60's kernel, unrolled over a block
    32 quantization blocks wide, cost the Nemotron cell 12 s there). The
    state at rest is the statement's, so the two may take turns on one
    state (a checkpoint of either restores under the other).
  Any other leaf (1-D, odd widths) takes ``BLOCKS`` in either entry:
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
  ``use_pallas`` means this kernel and nothing else: it decides the
  layout of every leaf at ``init`` (``BLOCKS`` for all where it is on),
  and a ``TILES`` leaf's one-pass kernel asks for no option.
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
# the one-pass step of a TILES leaf (``_q8_adam_step``). Tiles of one
# strip: the kernel's vector code is emitted for one strip and looped
# over the VMEM block, so a kernel's size does not go with its block or
# its leaf; sixteen tiles that do not depend on each other are what hides
# the lane reduces' and the square roots' latency (stand-alone on a
# [64, 2048, 1024] leaf PR 60 read 6.04 ms with 4 tiles in flight, 4.76
# with 8, 4.25 with 16 and 4.39 with 32; this kernel 5.61 ms with 8,
# 4.72 with 16, 4.36 with 32 and 4.41 with 64: PERF.md §6)
_STRIP = 32
# tiles a grid step: 262k elements, the size that put the flat kernel on
# the HBM roofline (see ``_FLAT_ROWS``), whatever the leaf's width
_STEP_TILES = 256


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
    # the codes of clip(round(sign(y) sqrt|y| qmax)), y = x / safe, in
    # fewer vector operations and bit for bit (the one-pass kernel is bound
    # by them): |y| is |x| / safe, a rounded product keeps its sign, no
    # finite |y| passes 1, and an unsigned block holds nothing below 0
    if signed:
        a = jnp.abs(x)
        scale = jnp.max(a, axis=-1, keepdims=True)
    else:
        a = jnp.maximum(x, 0.0)
        scale = jnp.max(x, axis=-1, keepdims=True)
    safe = jnp.maximum(scale, 1e-30)
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
        return _on_tpu()

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

    def _bias_corrected(count):
        cf = count.astype(jnp.float32)
        lrA = jnp.asarray(learning_rate, jnp.float32) / (1.0 - b1**cf)
        return lrA, 1.0 / (1.0 - b2**cf)

    def _one(g, m, v, lrA, invbc2):
        """``(delta, m', v')`` of one leaf: the statement every path is
        held to."""
        if not isinstance(m, (Quantized8, Quantized4)):
            # small tensor: plain fp32 adam, same eps placement as
            # the kernel so small and big leaves share semantics
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
        return _on_tpu()

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
