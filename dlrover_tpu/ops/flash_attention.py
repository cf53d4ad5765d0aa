"""Block-tiled flash attention for TPU (Pallas) with a jnp fallback.

Parity: the reference integrates CUDA flash-attention (FA1/FA2 + GLM
custom-mask kernels) via wrapper modules at
atorch/atorch/modules/transformer/layers.py:54-1168 and TF bindings at
tfplus/tfplus/flash_attn/kernels/flash_attention_fwd_kernel.cc:172. The
TPU-native equivalent is a Pallas kernel: the (q_block, kv_block) tiles
ride the MXU, the online-softmax state (running max / sum) lives in VMEM
scratch, and HBM traffic is O(T) per query block instead of the O(T^2)
score matrix.

Design:

- ``flash_attention(q, k, v)`` — public entry, [B, T, H, D] layout, GQA
  (H_kv divides H, resolved in the BlockSpec index map — KV heads are
  never materialized ``H/H_kv`` times), causal or custom position masks,
  dynamic block offsets so ring attention (parallel/ring_attention.py)
  can reuse the same kernel per KV hop.
- Differentiable via ``jax.custom_vjp``: backward is two more Pallas
  kernels (dq pass and dk/dv pass) using the saved (o, logsumexp)
  residuals, the standard FA2 recomputation split.
- On non-TPU backends it dispatches to ``flash_attention_reference`` —
  identical math, pure jnp — so CPU tests are fast; the kernels
  themselves are tested under ``interpret=True``.
- **Short-sequence fused kernels**: when the [T, T] score tile fits
  VMEM (T <= 1024, measured crossover), the streaming form is pure
  overhead — at seq 512 / head_dim 64 the MXU work per program is tiny,
  so grid count, online-softmax rescaling passes, and the backward's
  double (s, p, dp) recompute dominate. The fused path runs one program
  per (batch element, head chunk) with a python-unrolled head loop,
  single-pass softmax, and ONE backward kernel computing s/p/dp once
  and emitting dq/dk/dv together (5 matmuls vs the streaming split's
  7). Two bodies, chosen by what is known when the program is traced:
  **the triangle walk** when the mask is causal alone and the query
  and key offsets are static and equal (a sequence attending to
  itself): row tile *i* of ``bq`` queries meets keys ``[0, (i+1)*bq)``
  only, masked on its diagonal tile, so a head computes (n+1)/2n of
  its score square (62.5 % with four row tiles) and nothing above the
  diagonal; **the whole square** otherwise (traced offsets: a ring
  hop; unequal offsets, ``mask_fn``, ``causal=False``, a T the row
  tile does not divide), masked afterwards, skipped whole when a ring
  hop's keys all lie in the future. Programs cover head CHUNKS: the
  square's unrolled per-head [T,T] f32 temporaries must stay within
  scoped VMEM (``_head_chunk``); the walk loops over its heads and is
  sized by its pipeline's blocks (``_walk_head_chunk``).
  ``common/trace_counts`` holds the call sites lowered, by body
  (``attn_tri_sites`` ...).
- **The streaming kernels' triangle path** (longer T, or GQA): a call
  that is causal alone with static equal offsets, square blocks and one
  sequence length knows its visible blocks when it is traced, so its
  grid is ONE axis over the n(n+1)/2 blocks at or under the diagonal
  (the step -> block tables ride as scalar prefetch: no grid step and
  no fetch above the diagonal), only the block on the diagonal is
  masked, the softmax scale rides in the exponent's argument and on the
  float32 accumulators, and the backward runs in one pass (scores, p, dp
  and ds once; a head's float32 dq resident in VMEM) while that fits,
  split into the dq and the dk / dv kernel beyond. **An edge block**, one
  its rows see part of (the block on the diagonal, and under a window
  each block its far edge crosses), is walked by the backward kernels in
  ``_EDGE_STRIPS`` row strips, each against the one span of the block's
  keys that any of its rows sees, rounded outward to the strip's height
  and masked inside as before (``_edge_strips``: Python integers when the
  program is traced, so every slice is a static slice of a ref): a strip
  adds to its own rows of dq and to the rows of dk and dv its span
  covers, and the score tiles wholly over the diagonal or past the window
  (6 of a diagonal block's 16) are never multiplied; the grid, the
  fetches and the blocks walked are what they were. The forward kernel
  computes an edge block whole and masks it (measured: in strips it
  loses), and a block seen whole is one strip with no mask. A caller
  that states
  no block gets 1024 x 1024 there (measured), 512 on the rectangular
  grid, which every other call keeps as it was. ``common/trace_counts``
  holds the kernels lowered, by grid (``attn_stream_tri_sites`` ...).
- **A window** (``window=W``, a static argument of its own and not a
  ``mask_fn``): a query sees itself and the ``W - 1`` keys before it. On
  the streaming triangle path the step tables list only the band of
  blocks a window can see, ``max(0, i - wb) <= j <= i`` with ``wb =
  ceil((W - 1) / block)`` (45 of the triangle's 136 blocks at T = 16384,
  W = 2048 in blocks of 1024), in the forward, the one-pass backward and
  the split one: a query block walks DOWN from its diagonal block (where
  every row sees itself, so the running maximum is finite before any
  block whose rows may see nothing of it), a key block's queries end at
  ``min(n - 1, j + wb)``; the diagonal block keeps its mask and the
  blocks the window's far edge crosses get their own, the rest none. The
  kernels are the triangle's with a static ``window`` (absent, they trace
  as they did) under names of their own (``flash_attn_window_*``); a block
  the far edge crosses is an edge block, its place static in a branch of
  its own, and the backward walks it in row strips as the diagonal's.
  Everywhere else (the fused family, the rectangular grid, the jnp path)
  the window is an exact mask with no skip, and ``common/trace_counts``
  says which (``attn_window_blocks_walked`` against ``_causal``; the
  score tiles the edge blocks multiply, ``attn_edge_tiles_multiplied`` of
  ``attn_edge_tiles``).
- **Block diffusion** (``block_diffusion_attention``, an entry of its own):
  a row fed twice, its noised copy before the clean one, under a rule
  that is neither triangle nor band (a noised query sees the noised keys
  of its own block of ``block_len`` and the clean keys of the blocks
  before it, a clean query the clean keys up to its own block's end). The
  ``flash_attn_bd_*`` kernels are the triangle path's with a third table:
  the steps list the blocks of the ``2n x 2n`` grid that hold a visible
  pair and no other (80 of 256 at L = 8192 in blocks of 1024), each with
  the rule that masks it, a query block's own block first; the forward
  walks the noised x noised blocks on the diagonal in row strips of their
  own span, the one-pass backward every edge block in strips.
- **Which call walks what**, in one place. *The triangle*: causal alone,
  static equal offsets (``_sees_triangle``): the fused family's row tiles
  at T <= 1024 and H = H_kv, else the streaming kernels' ``n (n + 1) /
  2`` blocks (square blocks over one sequence, ``_stream_plan``). *The
  band*: the same with a ``window``, on the streaming triangle path alone
  (``_band_steps``). *Block diffusion*: ``block_diffusion_attention``
  where ``_bd_block`` finds a block (it divides the row, the tables hold
  the grid, a head's float32 dq fits the one pass). *The rectangle*
  (every block of the grid, a causal call skipping invisible ones at run
  time): everything else, a ``mask_fn``, ``causal=False``, traced or
  unequal offsets (a ring hop), unequal blocks, and a window or the
  block-diffusion rule where their walks cannot run (each then an exact
  mask); the jnp path walks nothing and masks everything.
- ``layout="bhtd"`` lets callers hand over kernel-native [B, H, T, D]
  tensors (the model emits them straight from its QKV einsums), skipping
  the 25 MB-per-tensor relayout transposes on every call.
- **What a recomputed layer keeps**: what the forward rule hands the
  backward rule, q, k and v as the kernel read them (after head norm and
  rotation), ``o`` and the logsumexp, carries
  ``jax.ad_checkpoint.checkpoint_name``s (``KEPT``). Outside a
  ``jax.checkpoint`` whose policy saves those names a name is an identity
  and lowers to nothing; inside one (``models/transformer.recomputed``)
  the backward pass makes the layer's norms and projections again as far
  as their own gradients want them and hands the backward kernel the five
  arrays the first forward left: O(T D) bytes a head in place of O(T^2 D)
  operations, no second forward kernel, and no second pass over the
  stretch that only feeds it.

Mask contract: ``mask_fn(q_pos, k_pos)`` receives broadcastable int32
position arrays (shapes ``[bq, 1]`` and ``[1, bk]``) and must return an
elementwise bool mask, e.g. ``lambda q, k: q >= k`` for causal.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.common import trace_counts

MaskFn = Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]

NEG_INF = -1e30  # finite stand-in for -inf: keeps exp()=0 without NaN risk
_LANES = 128  # f32 VMEM tile lane count; scratch vectors are padded to it


@functools.lru_cache(maxsize=None)
def _window_mask(window: int) -> MaskFn:
    """A causal window as a ``mask_fn``: a query sees itself and the
    ``window - 1`` keys before it. What a window is wherever no band is
    walked (the fused square, the rectangular grid, the jnp path); one
    function a length, so that it can ride as a static argument."""

    def mask(q_pos, k_pos):
        ahead = q_pos - k_pos
        return (ahead >= 0) & (ahead < window)

    return mask


def _mask_for_block(q_pos, k_pos, causal, mask_fn):
    """[bq,1] x [1,bk] positions -> bool mask or None (= all visible)."""
    if mask_fn is not None:
        return mask_fn(q_pos, k_pos)
    if causal:
        return q_pos >= k_pos
    return None


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------
def _fwd_kernel(
    off_ref,  # SMEM [2]: (q_offset, k_offset) global position offsets
    q_ref,  # VMEM [1, 1, bq, D]
    k_ref,  # VMEM [1, 1, bk, D]
    v_ref,  # VMEM [1, 1, bk, D]
    o_ref,  # VMEM [1, 1, bq, D]
    lse_ref,  # VMEM [1, 1, bq, 1]
    acc_ref,  # scratch [bq, D] f32
    m_ref,  # scratch [bq, _LANES] f32
    l_ref,  # scratch [bq, _LANES] f32
    *,
    causal: bool,
    mask_fn: Optional[MaskFn],
    sm_scale: float,
    block_q: int,
    block_k: int,
):
    jk = pl.program_id(3)
    nk = pl.num_programs(3)
    q_off = off_ref[0] + pl.program_id(2) * block_q
    k_off = off_ref[1] + jk * block_k

    @pl.when(jk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # whole-block causal skip: no query in this block can see any key
    visible = True
    if causal and mask_fn is None:
        visible = q_off + block_q - 1 >= k_off

    @pl.when(visible)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q,
            k,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        s = s * sm_scale
        q_pos = q_off + lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
        k_pos = k_off + lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        mask = _mask_for_block(q_pos, k_pos, causal, mask_fn)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]  # [bq, 1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # fully-masked-so-far rows keep m_new == NEG_INF; exponentiate
        # against 0 there so p = exp(NEG_INF) = 0 instead of exp(0) = 1
        m_safe = jnp.where(m_new > NEG_INF * 0.5, m_new, 0.0)
        alpha = jnp.exp(m_prev - m_safe)
        p = jnp.exp(s - m_safe)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        # p in input precision for the MXU (f32 operands run the
        # systolic array at a fraction of bf16 rate); f32 accumulator
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype),
            v_ref[0, 0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(jk == nk - 1)
    def _finalize():
        l = l_ref[:, :1]
        # fully-masked rows: l == 0 -> output 0, lse = NEG_INF
        safe_l = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        m = m_ref[:, :1]
        lse = jnp.where(l > 0.0, m + jnp.log(safe_l), NEG_INF)
        lse_ref[0, 0] = lse


def _fwd_pallas(
    q,
    k,
    v,
    offsets,
    *,
    causal,
    mask_fn,
    sm_scale,
    block_q,
    block_k,
    interpret,
    layout="bthd",
    allow_fused=True,
    diagonal=False,
    window=None,
):
    # Kernel layout is [B, H, T, D]: TPU tiling needs the last two block
    # dims to be (seq_block, head_dim) — (8,128)-aligned or full-size.
    # ``layout="bhtd"`` callers hand kernel-native tensors (no relayout).
    if layout == "bhtd":
        B, H, Tq, D = q.shape
        Hkv, Tk = k.shape[1], k.shape[2]
        qt, kt, vt = q, k, v
    else:
        B, Tq, H, D = q.shape
        Tk, Hkv = k.shape[1], k.shape[2]
        qt = q.transpose(0, 2, 1, 3)
        kt = k.transpose(0, 2, 1, 3)
        vt = v.transpose(0, 2, 1, 3)
    nq, nk = Tq // block_q, Tk // block_k
    group = H // Hkv

    def in_layout(ot, lse4):
        if layout == "bhtd":
            return ot, lse4[..., 0]
        return ot.transpose(0, 2, 1, 3), lse4[..., 0]

    fused, n_tri, mask_fn = _call_plan(
        qt, kt, block_q, block_k, allow_fused=allow_fused, causal=causal,
        mask_fn=mask_fn, diagonal=diagonal, window=window,
    )
    if window is not None:
        _count_window_site(nq, block_q, window if n_tri else None)
    if fused:
        return in_layout(*_fused_fwd_call(
            qt, kt, vt, offsets,
            causal=causal, mask_fn=mask_fn, sm_scale=sm_scale,
            interpret=interpret, diagonal=diagonal,
        ))

    _count_site(
        _STREAM, n_tri or 0, walked=_band_steps(n_tri, block_q, window)
    )
    if n_tri:
        return in_layout(*_tri_fwd_call(
            qt, kt, vt, sm_scale=sm_scale, block=block_q,
            interpret=interpret, window=window,
        ))

    kernel = functools.partial(
        _fwd_kernel,
        causal=causal,
        mask_fn=mask_fn,
        sm_scale=sm_scale,
        block_q=block_q,
        block_k=block_k,
    )
    grid = (B, H, nq, nk)
    q_spec = pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, block_k, D), lambda b, h, i, j: (b, h // group, j, 0)
    )
    # minor dim 1 == full array dim, so the tile is legal and lse costs
    # [B,H,T] f32 in HBM instead of 128x that
    lse_spec = pl.BlockSpec(
        (1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0)
    )
    ot, lse4 = pl.pallas_call(
        kernel,
        name="flash_attn_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            q_spec,
            kv_spec,
            kv_spec,
        ],
        out_specs=[q_spec, lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tq, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Tq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel",
                "parallel",
                "parallel",
                "arbitrary",
            ),
        ),
        interpret=interpret,
    )(offsets, qt, kt, vt)
    return in_layout(ot, lse4)


# ---------------------------------------------------------------------------
# fused short-sequence kernels (one program per batch element and head
# chunk): the whole-square body and the triangle walk
# ---------------------------------------------------------------------------
# Eligibility: the [T, T] f32 score tile must fit scoped VMEM (see
# _head_chunk, which sizes the square body's head chunks against a
# 48 MB live-set budget under the raised _FUSED_VMEM_LIMIT). At T=2048
# a single head's backward live set (~3.5 x 16 MB) no longer fits; the
# streaming kernels take over there. Measured at T=1024, D=64 (v5e,
# bf16, PERF.md PR 36): the streaming kernels, which skip invisible
# blocks too, take 2.2x (forward) and 1.8x (backward) the square's
# time, so the cap stays.
_FUSED_MAX_T = 1024
# query rows a tile of the triangle walk. Measured at T=1024, D=64
# (forward / backward ms a call of [16, 12, 1024, 64]; square 0.702 /
# 1.833): 256 rows 0.542 / 1.428, 128 rows 0.620 / 1.424 (least work,
# but the forward's softmax no longer hides under its fetches), 512
# rows 0.541 / 1.590
_TRI_ROW_TILE = 256


def _fused_eligible(q_shape, k_shape, layout: str) -> bool:
    if layout == "bhtd":
        B, H, Tq, D = q_shape
        Hkv, Tk = k_shape[1], k_shape[2]
    else:
        B, Tq, H, D = q_shape
        Tk, Hkv = k_shape[1], k_shape[2]
    return Tq == Tk and Tq <= _FUSED_MAX_T and H == Hkv


def _row_tile(T: int) -> Optional[int]:
    """Query rows a tile of the triangle walk, or None where a walk
    would skip nothing or cannot tile T (T = 520 is fused-eligible)."""
    bq = _TRI_ROW_TILE
    return bq if T % bq == 0 and T // bq >= 2 else None


def _mask_diagonal_tile(s, row_tile: int):
    """Causal mask of a row tile's scores ``[bq, (i+1)*bq]`` when query
    and key offsets are equal: the last ``bq`` columns are the tile on
    the diagonal, every column before them is visible to every row."""
    below = s.shape[1] - row_tile
    r = lax.broadcasted_iota(jnp.int32, (row_tile, row_tile), 0)
    c = lax.broadcasted_iota(jnp.int32, (row_tile, row_tile), 1)
    diag = jnp.where(r >= c, s[:, below:], NEG_INF)
    if not below:
        return diag
    return jnp.concatenate([s[:, :below], diag], axis=1)


def _for_each_head(n_heads: int, head):
    """The triangle walk's head loop: a real loop (the row tiles inside
    ``head`` are unrolled), so a kernel compiles in a second whatever
    the chunk, and only the pipeline's blocks grow with it."""

    def body(h, carry):
        head(h)
        return carry

    lax.fori_loop(0, n_heads, body, 0)


_FUSED = (
    "attn_tri_sites", "attn_square_sites",
    "attn_tiles_walked", "attn_tiles_square",
)
_STREAM = (
    "attn_stream_tri_sites", "attn_stream_rect_sites",
    "attn_stream_blocks_walked", "attn_stream_blocks_rect",
)
_WINDOW = ("attn_window_blocks_walked", "attn_window_blocks_causal")
_EDGE = ("attn_edge_tiles_multiplied", "attn_edge_tiles")

# the names the forward rule gives what it hands the backward rule
# (``_flash_fwd_rule``: q, k, v as the kernel read them, ``o``, the
# logsumexp): what a ``jax.checkpoint`` around a layer saves of an
# attention call when its policy is ``save_only_these_names(*KEPT)``
KEPT = (
    "flash_attn_q", "flash_attn_k", "flash_attn_v", "flash_attn_o",
    "flash_attn_lse",
)


def _count_site(names, n: int, kernels: int = 1, walked=None):
    """``kernels`` kernels of one call site into ``common/trace_counts``
    under ``names`` (the fused family's or the streaming kernels'): a
    triangle site of ``n`` tiles a side and the tiles it walks of its
    whole, or with ``n`` 0 a site that takes the whole and walks no
    triangle. The fused family counts call sites, forward and backward
    each, by the body they took, in ``row_tile`` square score tiles; the
    streaming family counts kernels (a forward is one, a backward one in
    one pass and two split) by the grid they took, in the blocks a head
    walks (``walked`` where a window's band is less than the triangle).
    Counted when a program is traced, so it costs a step nothing; a
    program that came out of a cache of executables was not traced and
    adds nothing."""
    if walked is None:
        walked = n * (n + 1) // 2
    added = (n > 0, n == 0, walked, n * n)
    for name, k in zip(names, added):
        trace_counts.count(name, kernels * k)


def _count_window_site(n: int, block: int, band, kernels: int = 1):
    """``kernels`` kernels of one call site that was given a window, into
    ``common/trace_counts`` under ``_WINDOW``: the blocks a head walks
    there against the ``n (n + 1) / 2`` at or under its diagonal, ``n``
    blocks of ``block`` a side. ``band`` is the window's length where its
    band is walked; where it is None the window is a mask over whatever
    the call walks, and the site counts as having walked all of them.
    Called where a call's plan is made (``_fwd_pallas``, ``_bwd_pallas``)
    and nowhere else."""
    under = n * (n + 1) // 2
    walked = under if band is None else _band_steps(n, block, band)
    for name, k in zip(_WINDOW, (walked, under)):
        trace_counts.count(name, kernels * k)


def _count_edge_tiles(n: int, block: int, window, strips: int, *,
                      whole: bool = False, kernels: int = 1):
    """``kernels`` kernels of one call site on the streaming triangle
    path, into ``common/trace_counts`` under ``_EDGE``: the score tiles,
    the height of one of the block's ``strips`` row strips a side, that a
    head's edge blocks hold (the block on the diagonal ``n`` times and
    under a ``window`` each block its far edge crosses), and those of them
    the kernel multiplies: the spans of its strips (``_edge_strips``), or
    every tile where it computes an edge block ``whole``. Counted beside
    ``_count_site``, as it counts."""
    edges = [(n, _edge_limits(block, True, window))]
    if window is not None:
        edges += [
            (max(n - far, 0), _edge_limits(block, False, window, far))
            for far in _far_edges(block, window)
        ]
    rows = block // strips
    held = sum(visits for visits, _ in edges) * strips * strips
    multiplied = held if whole else sum(
        visits * (c1 - c0) // rows
        for visits, limits in edges
        for _, _, c0, c1 in _edge_strips(block, *limits, strips)
    )
    for name, k in zip(_EDGE, (multiplied, held)):
        trace_counts.count(name, kernels * k)


def _fused_fwd_kernel(
    off_ref,  # SMEM [2]
    q_ref,  # VMEM [1, Hc, T, D]
    k_ref,
    v_ref,
    o_ref,  # VMEM [1, Hc, T, D]
    lse_ref,  # VMEM [1, Hc, T, 1]
    *,
    causal: bool,
    mask_fn: Optional[MaskFn],
    sm_scale: float,
    n_heads: int,
    row_tile: Optional[int] = None,
):
    T = q_ref.shape[2]
    if row_tile is not None:
        # the triangle walk: causal, no mask_fn, offsets known equal

        def _head(h):
            for i in range(T // row_tile):
                rows = pl.ds(i * row_tile, row_tile)
                seen = pl.ds(0, (i + 1) * row_tile)
                s = jax.lax.dot_general(
                    q_ref[0, h, rows, :], k_ref[0, h, seen, :],
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                s = _mask_diagonal_tile(s * sm_scale, row_tile)
                # every row sees itself: m is finite and l > 0
                m = jnp.max(s, axis=-1, keepdims=True)  # [bq, 1]
                p = jnp.exp(s - m)
                l = jnp.sum(p, axis=-1, keepdims=True)
                acc = jax.lax.dot_general(
                    p.astype(v_ref.dtype), v_ref[0, h, seen, :],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                o_ref[0, h, rows, :] = (acc / l).astype(o_ref.dtype)
                lse_ref[0, h, rows, :] = m + jnp.log(l)

        _for_each_head(n_heads, _head)
        return

    def _compute():
        q_pos = off_ref[0] + lax.broadcasted_iota(jnp.int32, (T, 1), 0)
        k_pos = off_ref[1] + lax.broadcasted_iota(jnp.int32, (1, T), 1)
        mask = _mask_for_block(q_pos, k_pos, causal, mask_fn)
        # static unroll: one [T,T] live set at a time
        for h in range(n_heads):
            q = q_ref[0, h]
            k = k_ref[0, h]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            s = s * sm_scale
            if mask is not None:
                s = jnp.where(mask, s, NEG_INF)
            m = jnp.max(s, axis=-1, keepdims=True)  # [T, 1]
            m_safe = jnp.where(m > NEG_INF * 0.5, m, 0.0)
            p = jnp.exp(s - m_safe)
            l = jnp.sum(p, axis=-1, keepdims=True)
            # p rides the MXU in the INPUT precision (f32 operands run
            # the systolic array at a fraction of bf16 rate); the
            # accumulator stays f32 via preferred_element_type
            acc = jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0, h],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            safe_l = jnp.where(l > 0.0, l, 1.0)
            o_ref[0, h] = (acc / safe_l).astype(o_ref.dtype)
            lse_ref[0, h] = jnp.where(
                l > 0.0, m_safe + jnp.log(safe_l), NEG_INF
            )

    if causal and mask_fn is None:
        # whole-program causal skip: ring attention's fully-future KV
        # hops (k_offset past every query) stay near-free, as in the
        # streaming kernel's per-block pl.when gate
        visible = off_ref[0] + T - 1 >= off_ref[1]

        @pl.when(jnp.logical_not(visible))
        def _skip():
            o_ref[0] = jnp.zeros_like(o_ref[0])
            lse_ref[0] = jnp.full_like(lse_ref[0], NEG_INF)

        pl.when(visible)(_compute)
    else:
        _compute()


def _fused_bwd_kernel(
    off_ref,  # SMEM [2]
    q_ref,  # VMEM [1, H, T, D]
    k_ref,
    v_ref,
    do_ref,
    lse_ref,  # VMEM [1, H, T, 1]
    delta_ref,
    dq_ref,  # out [1, H, T, D]
    dk_ref,
    dv_ref,
    *scratch,
    causal: bool,
    mask_fn: Optional[MaskFn],
    sm_scale: float,
    n_heads: int,
    row_tile: Optional[int] = None,
):
    """One pass per head: s and p computed ONCE, then the three grad
    matmuls — the streaming FA2 split recomputes (s, p, dp) in both its
    dq and dk/dv kernels (7 matmuls/head vs 5 here). The triangle walk
    (``row_tile``) takes two more refs, ``dk_acc`` / ``dv_acc``: float32
    scratch ``[T, D]`` the row tiles add their keys' gradients into."""
    T = q_ref.shape[2]
    if row_tile is not None:
        dk_acc, dv_acc = scratch
        last = T // row_tile - 1

        def _head(h):
            # widest tile first: it writes every key's row, the
            # narrower ones add into theirs, nothing is zeroed
            for i in range(last, -1, -1):
                rows = pl.ds(i * row_tile, row_tile)
                seen = pl.ds(0, (i + 1) * row_tile)
                q = q_ref[0, h, rows, :]
                k = k_ref[0, h, seen, :]
                do = do_ref[0, h, rows, :]
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                s = _mask_diagonal_tile(s * sm_scale, row_tile)
                # lse is finite (every row sees itself); a masked
                # score gives exp(NEG_INF - lse) = 0
                p = jnp.exp(s - lse_ref[0, h, rows, :])  # [bq, W]
                dv = jax.lax.dot_general(
                    p.astype(q.dtype), do, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                dp = jax.lax.dot_general(
                    do, v_ref[0, h, seen, :], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                ds = p * (dp - delta_ref[0, h, rows, :]) * sm_scale
                ds_lo = ds.astype(q.dtype)
                dq_ref[0, h, rows, :] = jax.lax.dot_general(
                    ds_lo, k, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ).astype(dq_ref.dtype)
                dk = jax.lax.dot_general(
                    ds_lo, q, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                if i == last:
                    dk_acc[...] = dk
                    dv_acc[...] = dv
                else:
                    dk_acc[seen, :] += dk
                    dv_acc[seen, :] += dv
            dk_ref[0, h] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[0, h] = dv_acc[...].astype(dv_ref.dtype)

        _for_each_head(n_heads, _head)
        return

    def _compute():
        q_pos = off_ref[0] + lax.broadcasted_iota(jnp.int32, (T, 1), 0)
        k_pos = off_ref[1] + lax.broadcasted_iota(jnp.int32, (1, T), 1)
        mask = _mask_for_block(q_pos, k_pos, causal, mask_fn)
        for h in range(n_heads):
            q = q_ref[0, h]
            k = k_ref[0, h]
            s = (
                jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                * sm_scale
            )
            if mask is not None:
                s = jnp.where(mask, s, NEG_INF)
            lse = lse_ref[0, h]  # [T, 1]
            row_valid = lse > NEG_INF * 0.5
            p = jnp.where(row_valid, jnp.exp(s - lse), 0.0)  # [T, T]
            # every grad matmul feeds the MXU input-precision operands
            # (f32 operands run the systolic array at a fraction of
            # bf16 rate); accumulation stays f32
            p_lo = p.astype(q_ref.dtype)
            do = do_ref[0, h]
            # dv = p^T @ do
            dv_ref[0, h] = jax.lax.dot_general(
                p_lo, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).astype(dv_ref.dtype)
            dp = jax.lax.dot_general(
                do, v_ref[0, h], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - delta_ref[0, h]) * sm_scale  # [T, T]
            ds_lo = ds.astype(q_ref.dtype)
            dq_ref[0, h] = jax.lax.dot_general(
                ds_lo, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).astype(dq_ref.dtype)
            # dk = ds^T @ q
            dk_ref[0, h] = jax.lax.dot_general(
                ds_lo, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).astype(dk_ref.dtype)


    if causal and mask_fn is None:
        # mirror of the forward's whole-program causal skip
        visible = off_ref[0] + T - 1 >= off_ref[1]

        @pl.when(jnp.logical_not(visible))
        def _skip():
            dq_ref[0] = jnp.zeros_like(dq_ref[0])
            dk_ref[0] = jnp.zeros_like(dk_ref[0])
            dv_ref[0] = jnp.zeros_like(dv_ref[0])

        pl.when(visible)(_compute)
    else:
        _compute()

def _head_chunk(H: int, T: int, live_f32_per_head: float) -> int:
    """Heads per program of the WHOLE-SQUARE body: the unrolled head
    loop's [T, T] f32 temporaries occupy scoped VMEM stack; chunk so
    ``Hc * live set`` stays under a conservative budget (the raised
    ``vmem_limit_bytes`` leaves slack for the compiler's own
    scheduling)."""
    # measured on v5e (bf16, D=64/128): larger chunks amortize
    # per-program overhead — T=512 all-12-heads beats 9 by 27%, T=1024
    # Hc=4 beats Hc=2 by 28% — and Mosaic tolerates a live set past
    # physical VMEM by scheduling spills; the hard compile failure on
    # v5e lands near ~64 MB x live-factor, so 48 MB keeps margin
    return _largest_chunk(H, live_f32_per_head * T * T * 4, 48 << 20)


def _walk_head_chunk(H: int, T: int, D: int, itemsize: int,
                     wide: int, narrow: int) -> int:
    """Heads per program of the TRIANGLE walk. Its heads run in a real
    loop, so one head's ``[bq, T]`` temporaries are all the compute
    holds; what grows with the chunk is the pipeline: ``wide``
    ``[T, D]`` blocks and ``narrow`` ``[T, 1]`` f32 blocks a head, each
    double-buffered, the minor dimension padded to the 128 lanes."""
    # measured on v5e (bf16, T=1024, D=64; PERF.md, PR 36): the walk is
    # bound by the pipeline's traffic (forward) and the MXU (backward),
    # so the chunk only has to hide the per-program cost without
    # leaving a long first fetch exposed: forward 12 heads a program
    # 0.557 ms, 4 heads 0.542, 1 head 0.568; 25 heads 0.608, 5 heads
    # 0.564, 1 head 0.590; backward flat from 1 to 6. 16 MB of blocks
    # gives 4 / 2 heads of 12 and 5 / 1 of 25
    lanes = -(-D // _LANES) * _LANES
    per_head = 2 * T * (wide * lanes * itemsize + narrow * _LANES * 4)
    return _largest_chunk(H, per_head, 16 << 20)


def _largest_chunk(H: int, per_head_bytes: float, budget: int) -> int:
    best = 1
    for d in range(1, H + 1):
        if H % d == 0 and d * per_head_bytes <= budget:
            best = d
    return best


_FUSED_VMEM_LIMIT = 100 * 1024 * 1024


def _fused_plan(T, *, causal, mask_fn, diagonal, row_tile):
    """The row tile of the triangle walk, or None for the whole-square
    body: the walk needs the visible region known when the program is
    traced, which is a causal mask alone with query and key offsets
    static and equal (``diagonal``). ``row_tile`` overrides the tile
    derived from T (tests and timing)."""
    if not _sees_triangle(causal, mask_fn, diagonal):
        return None
    return _row_tile(T) if row_tile is None else row_tile


def _fused_fwd_call(qt, kt, vt, offsets, *, causal, mask_fn, sm_scale,
                    interpret, diagonal=False, row_tile=None):
    """[B,H,T,D] in -> (o [B,H,T,D], lse4 [B,H,T,1])."""
    B, H, T, D = qt.shape
    row_tile = _fused_plan(
        T, causal=causal, mask_fn=mask_fn, diagonal=diagonal,
        row_tile=row_tile,
    )
    _count_site(_FUSED, T // row_tile if row_tile else 0)
    if row_tile:  # q, k, v, o and lse
        Hc = _walk_head_chunk(H, T, D, qt.dtype.itemsize, wide=4, narrow=1)
    else:
        Hc = _head_chunk(H, T, live_f32_per_head=2.5)
    spec = pl.BlockSpec((1, Hc, T, D), lambda b, hc: (b, hc, 0, 0))
    row_spec = pl.BlockSpec((1, Hc, T, 1), lambda b, hc: (b, hc, 0, 0))
    return pl.pallas_call(
        functools.partial(
            _fused_fwd_kernel,
            causal=causal,
            mask_fn=mask_fn,
            sm_scale=sm_scale,
            n_heads=Hc,
            row_tile=row_tile,
        ),
        name="flash_attn_fused_fwd",
        grid=(B, H // Hc),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), spec, spec, spec],
        out_specs=[spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, D), qt.dtype),
            jax.ShapeDtypeStruct((B, H, T, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_FUSED_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(offsets, qt, kt, vt)


def _fused_bwd_call(qt, kt, vt, dot, lse4, delta4, offsets, *, causal,
                    mask_fn, sm_scale, interpret, diagonal=False,
                    row_tile=None):
    """[B,H,T,D] in -> (dq, dk, dv) each [B,H,T,D] (q dtype)."""
    B, H, T, D = qt.shape
    row_tile = _fused_plan(
        T, causal=causal, mask_fn=mask_fn, diagonal=diagonal,
        row_tile=row_tile,
    )
    _count_site(_FUSED, T // row_tile if row_tile else 0)
    if row_tile:  # q, k, v, do, dq, dk, dv and lse, delta
        Hc = _walk_head_chunk(H, T, D, qt.dtype.itemsize, wide=7, narrow=2)
    else:
        Hc = _head_chunk(H, T, live_f32_per_head=3.5)
    spec = pl.BlockSpec((1, Hc, T, D), lambda b, hc: (b, hc, 0, 0))
    row_spec = pl.BlockSpec((1, Hc, T, 1), lambda b, hc: (b, hc, 0, 0))
    return pl.pallas_call(
        functools.partial(
            _fused_bwd_kernel,
            causal=causal,
            mask_fn=mask_fn,
            sm_scale=sm_scale,
            n_heads=Hc,
            row_tile=row_tile,
        ),
        name="flash_attn_fused_bwd",
        grid=(B, H // Hc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            spec, spec, spec, spec, row_spec, row_spec,
        ],
        out_specs=[spec, spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, D), qt.dtype),
            jax.ShapeDtypeStruct((B, H, T, D), qt.dtype),
            jax.ShapeDtypeStruct((B, H, T, D), qt.dtype),
        ],
        # the triangle walk's float32 dk / dv of one head
        scratch_shapes=(
            [pltpu.VMEM((T, D), jnp.float32)] * 2 if row_tile else []
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_FUSED_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(offsets, qt, kt, vt, dot, lse4, delta4)


# ---------------------------------------------------------------------------
# backward kernels (FA2 split: dq pass, then dk/dv pass)
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(
    off_ref,
    q_ref,  # [1, 1, bq, D]
    k_ref,  # [1, 1, bk, D]
    v_ref,
    do_ref,  # [1, 1, bq, D]
    lse_ref,  # [1, 1, bq, 1]
    delta_ref,  # [1, 1, bq, 1]
    dq_ref,  # out [1, 1, bq, D]
    dq_acc,  # scratch [bq, D] f32
    *,
    causal,
    mask_fn,
    sm_scale,
    block_q,
    block_k,
):
    jk = pl.program_id(3)
    nk = pl.num_programs(3)
    q_off = off_ref[0] + pl.program_id(2) * block_q
    k_off = off_ref[1] + jk * block_k

    @pl.when(jk == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    visible = True
    if causal and mask_fn is None:
        visible = q_off + block_q - 1 >= k_off

    @pl.when(visible)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = (
            jax.lax.dot_general(
                q,
                k,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * sm_scale
        )
        q_pos = q_off + lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
        k_pos = k_off + lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        mask = _mask_for_block(q_pos, k_pos, causal, mask_fn)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        lse = lse_ref[0, 0, :, :1]  # [bq, 1]
        # fully-masked rows have lse == NEG_INF; exp(s - lse) would be
        # exp(0) = 1 there, leaking gradient through positions the
        # forward zeroed — zero p explicitly
        row_valid = lse > NEG_INF * 0.5
        p = jnp.where(row_valid, jnp.exp(s - lse), 0.0)
        # MXU operands stay in input precision (f32 operands run the
        # systolic array at a fraction of bf16 rate); f32 accumulation
        do = do_ref[0, 0]
        dp = jax.lax.dot_general(
            do,
            v_ref[0, 0],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        delta = delta_ref[0, 0, :, :1]
        ds = p * (dp - delta) * sm_scale
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds.astype(k.dtype),
            k,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(jk == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    off_ref,
    q_ref,  # [1, 1, bq, D]
    k_ref,  # [1, 1, bk, D]
    v_ref,
    do_ref,
    lse_ref,  # [1, 1, bq, 1]
    delta_ref,
    dk_ref,  # out [1, 1, bk, D]  (per q-head; summed over groups outside)
    dv_ref,
    dk_acc,  # scratch [bk, D] f32
    dv_acc,
    *,
    causal,
    mask_fn,
    sm_scale,
    block_q,
    block_k,
):
    iq = pl.program_id(3)
    nq = pl.num_programs(3)
    q_off = off_ref[0] + iq * block_q
    k_off = off_ref[1] + pl.program_id(2) * block_k

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    visible = True
    if causal and mask_fn is None:
        visible = q_off + block_q - 1 >= k_off

    @pl.when(visible)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = (
            jax.lax.dot_general(
                q,
                k,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * sm_scale
        )
        q_pos = q_off + lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
        k_pos = k_off + lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        mask = _mask_for_block(q_pos, k_pos, causal, mask_fn)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        lse = lse_ref[0, 0, :, :1]
        # zero p on fully-masked rows (see _bwd_dq_kernel)
        row_valid = lse > NEG_INF * 0.5
        p = jnp.where(row_valid, jnp.exp(s - lse), 0.0)  # [bq, bk]
        # MXU operands stay in input precision (f32 operands run the
        # systolic array at a fraction of bf16 rate); f32 accumulation
        do = do_ref[0, 0]
        # dv += p^T @ do
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p.astype(do.dtype),
            do,
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do,
            v_ref[0, 0],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        delta = delta_ref[0, 0, :, :1]
        ds = p * (dp - delta) * sm_scale  # [bq, bk]
        # dk += ds^T @ q
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds.astype(q.dtype),
            q,
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# the streaming kernels' triangle path: a causal call with no mask_fn,
# query and key offsets static and equal and block_q == block_k knows
# its visible blocks when the program is traced
# ---------------------------------------------------------------------------
# The (query block, key block) axes of the rectangular grid become ONE
# axis over the n(n+1)/2 blocks at or under the diagonal, with the step
# -> (i, j) tables as scalar prefetch: no grid step and no fetch for a
# block above the diagonal. Only the block on the diagonal is masked;
# every row sees its own position, so no row is empty and the
# rectangular bodies' guards against that (m_safe, row_valid) have
# nothing to guard. The softmax scale rides in the exponent's argument
# (the running maximum is of the raw scores: the scale is positive) and,
# for ds, on the float32 accumulators at finalize; operands stay as they
# come, p and ds are cast for the MXU where the rectangular bodies cast.
#
# blocks a side past which the two int32 tables (8,256 steps each)
# would crowd SMEM, T = 131072 in blocks of 1024: the rectangular grid
# takes over
_TRI_MAX_BLOCKS = 128


# Row strips the backward kernels walk an edge block in, a block of the
# triangle path that its rows see part of: the block on the diagonal, and
# under a window each block its far edge crosses. A strip multiplies its
# ``block // strips`` query rows against the one span of the block's keys
# that any of them sees, rounded outward to the strip's height, so no
# score tile that lies wholly over the diagonal or past the window is
# computed (6 of a diagonal block's 16); the grid, the fetches and the
# blocks walked stay as they are. Measured in blocks of 1024, ms a call
# of forward + backward less the forward alone (v5e, bf16,
# ``tools/attn_kernel_bench.py``, PERF.md section 6, PR 54), one strip / two
# / four: the triangle at [1, 32 / 4, 16384, 128] 34.59 / 33.76 / 33.42,
# its band under a window of 2048 12.51 / 10.89 / 10.30, the band of 512 at
# [1, 40 / 20, 16384, 128] 11.81 / 7.62 / 6.84. The forward kernel keeps
# its edge blocks whole: in strips its two matmuls a strip no longer hide
# its softmax (the same three calls' forward 16.82 / 17.42 / 17.23, 5.46 /
# 6.28 / 6.26 and 4.24 / 4.26 / 4.26).
_EDGE_STRIPS = 4


def _edge_strip_count(block: int, interpret: bool) -> int:
    """Strips of an edge block of ``block`` rows: ``_EDGE_STRIPS`` where a
    strip's rows, which its span of keys is a whole number of, are whole
    lane tiles, else fewer, or one. Interpreted there is no tile."""
    tile = 1 if interpret else _LANES
    strips = _EDGE_STRIPS
    while strips > 1 and (block % strips or block // strips % tile):
        strips //= 2
    return strips


def _stream_plan(Tq, Tk, block_q, block_k, *, causal, mask_fn, diagonal):
    """Blocks a side of the triangle path, or None for the rectangular
    grid: the same test ``_fused_plan`` makes for the fused family, and
    square blocks over one sequence so that the diagonal is a block's."""
    if not _sees_triangle(causal, mask_fn, diagonal):
        return None
    if block_q != block_k or Tq != Tk or Tq // block_q > _TRI_MAX_BLOCKS:
        return None
    return Tq // block_q


def _band_blocks(window, block: int):
    """Key blocks before its own that a query block can see through a
    window of ``window`` keys (a query sees itself and the ``window - 1``
    before it): the band is ``i - wb <= j <= i``. None without one."""
    if window is None:
        return None
    return -(-(window - 1) // block)


def _band_steps(n, block: int, window):
    """Blocks a head walks on the band of ``n`` blocks a side, or None
    where there is no band (no window, or no triangle path)."""
    if not n or window is None:
        return None
    wb = _band_blocks(window, block)
    return sum(min(i, wb) + 1 for i in range(n))


def _call_plan(qt, kt, block_q, block_k, *, allow_fused, causal, mask_fn,
               diagonal, window):
    """``(fused, n_tri, mask_fn)`` of a call over ``[B, H, T, D]``: the
    fused family or not, the blocks a side of the triangle path (None for
    the rectangular grid), and the mask the other bodies apply: a window
    is walked as a band on the triangle path alone, and is a mask
    everywhere else."""
    fused = allow_fused and _fused_eligible(qt.shape, kt.shape, "bhtd")
    n_tri = None if fused else _stream_plan(
        qt.shape[2], kt.shape[2], block_q, block_k,
        causal=causal, mask_fn=mask_fn, diagonal=diagonal,
    )
    if window is not None and not n_tri:
        mask_fn = _window_mask(window)
    return fused, n_tri, mask_fn


def _triangle_steps(n: int, by_key: bool, wb=None):
    """step -> (query block i, key block j) over the blocks with
    j <= i, in the order a kernel accumulates: a query block's keys
    ``j = 0..i`` (forward, dq), or with ``by_key`` a key block's
    queries ``i = j..n-1`` (dk / dv). With ``wb`` only the band ``i - wb
    <= j <= i`` a window can see: a key block's queries end at ``min(n -
    1, j + wb)``, and a query block's keys run DOWN from its own block to
    ``max(0, i - wb)``: the block on the diagonal, where every row sees
    itself, is a row's first, so the running maximum is finite before a
    block on the far edge whose rows may see nothing of it."""
    if wb is None:
        if by_key:
            kj, qi = np.triu_indices(n)
        else:
            qi, kj = np.tril_indices(n)
    elif by_key:
        qi, kj = zip(*(
            (i, j) for j in range(n) for i in range(j, min(n, j + wb + 1))
        ))
    else:
        qi, kj = zip(*(
            (i, j) for i in range(n)
            for j in range(i, max(0, i - wb) - 1, -1)
        ))
    return jnp.asarray(qi, jnp.int32), jnp.asarray(kj, jnp.int32)


def _edge_strips(block: int, lo, hi, strips: int):
    """The plan of an edge block whose visible pairs are ``lo <= row - col
    < hi`` (None: no such limit), rows and columns counted inside the
    block: ``((r0, r1, c0, c1), ...)``, a strip of query rows ``[r0, r1)``
    against the keys ``[c0, c1)``, the span any of its rows sees rounded
    outward to the strip's height. A strip that sees nothing of the block
    is not in the plan."""
    rows = block // strips
    plan = []
    for r0 in range(0, block, rows):
        r1 = r0 + rows
        # row - hi < col <= row - lo, over the strip's rows
        c0 = 0 if hi is None else max(0, r0 - hi + 1) // rows * rows
        c1 = block if lo is None else min(block, -(-(r1 - lo) // rows) * rows)
        if c0 < c1:
            plan.append((r0, r1, c0, c1))
    return tuple(plan)


def _far_edges(block: int, window: int):
    """How many blocks before the query block the blocks lie that a
    window's far edge crosses: past those it covers whole, up to the last
    it reaches (one value for 2048 or 512 in blocks of 1024, two for
    1536)."""
    return range(max(window // block, 1), _band_blocks(window, block) + 1)


def _edge_limits(block: int, on_diagonal: bool, window=None, far=None):
    """``(lo, hi)`` of a block's visible pairs ``lo <= row - col < hi``,
    or None for a block seen whole: the block on the diagonal sees ``0 <=
    row - col`` (``< window`` of it), a block ``far`` blocks before the
    query block that the window's far edge crosses ``row - col < window -
    far * block``."""
    if on_diagonal:
        return 0, window
    if far is None:
        return None
    return None, window - far * block


def _block_strips(block: int, strips: int, on_diagonal, window, far):
    """``(strip, lo, hi)`` over what a body multiplies of one block: the
    whole block as one strip with no mask, or an edge block's plan."""
    limits = _edge_limits(block, on_diagonal, window, far)
    if limits is None:
        return (((0, block, 0, block), None, None),)
    return tuple(
        (strip, *limits) for strip in _edge_strips(block, *limits, strips)
    )


def _tri_scores(q_ref, k_ref, strip, lo=None, hi=None):
    """Raw scores ``q k^T`` of one strip ``(r0, r1, c0, c1)`` of a block,
    float32: query rows ``[r0, r1)`` against keys ``[c0, c1)``, masked to
    ``lo <= row - col < hi`` wherever a pair of the strip may fail it (a
    block seen whole is one strip with neither limit)."""
    r0, r1, c0, c1 = strip
    s = jax.lax.dot_general(
        q_ref[0, 0, r0:r1, :], k_ref[0, 0, c0:c1, :],
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    below = lo is not None and r0 - (c1 - 1) < lo
    past = hi is not None and r1 - 1 - c0 >= hi
    if not (below or past):
        return s
    # row - col of the block is that of the strip plus r0 - c0
    ahead = (
        lax.broadcasted_iota(jnp.int32, s.shape, 0)
        - lax.broadcasted_iota(jnp.int32, s.shape, 1)
    )
    if below and past:
        seen = (ahead >= lo - (r0 - c0)) & (ahead < hi - (r0 - c0))
    elif below:
        seen = ahead >= lo - (r0 - c0)
    else:
        seen = ahead < hi - (r0 - c0)
    return jnp.where(seen, s, NEG_INF)


def _band_below(i, j, block: int, window: int, body):
    """``body(False[, far])`` on a band step under the diagonal: with no
    ``far`` where the window covers the whole block, and with each value
    of ``far``, static in its branch, at which its far edge crosses it."""
    whole = window // block - 1  # blocks back that are covered whole
    far = i - j
    if whole >= 1:
        pl.when((far >= 1) & (far <= whole))(
            functools.partial(body, False)
        )
    for edge in _far_edges(block, window):
        pl.when(far == edge)(functools.partial(body, False, edge))


def _band_by_query(i, j, block: int, window: int, body, write):
    """A query block's walk over its band, a step of it: ``body`` on the
    block on the diagonal (the row's first) and on those under it,
    ``write`` at the first block the window reaches (its last)."""
    pl.when(j == i)(functools.partial(body, True))
    _band_below(i, j, block, window, body)
    last = jnp.maximum(i - _band_blocks(window, block), 0)
    pl.when(j == last)(write)


def _tri_fwd_kernel(
    qi_ref,  # SMEM [steps]: the step's query block
    kj_ref,  # SMEM [steps]: the step's key block
    q_ref,  # VMEM [1, 1, b, D]
    k_ref,
    v_ref,
    o_ref,  # VMEM [1, 1, b, D]
    lse_ref,  # VMEM [1, 1, b, 1]
    acc_ref,  # scratch [b, D] f32
    m_ref,  # scratch [b, _LANES] f32: running maximum of RAW scores
    l_ref,  # scratch [b, _LANES] f32
    *,
    sm_scale: float,
    window=None,
):
    """One step of a query block's walk over its keys. With a ``window``
    the walk is the band's: from the block on the diagonal down to the
    first block the window reaches (``_triangle_steps``)."""
    step = pl.program_id(2)
    i, j = qi_ref[step], kj_ref[step]
    block = q_ref.shape[2]

    @pl.when(j == (0 if window is None else i))
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _block(on_diagonal, far=None):
        # whole, and masked where it is an edge block: row strips lose
        # here what they gain in the backward kernels (``_EDGE_STRIPS``)
        limits = _edge_limits(block, on_diagonal, window, far) or ()
        s = _tri_scores(q_ref, k_ref, (0, block, 0, block), *limits)
        m_prev = m_ref[:, :1]  # [b, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # a row's first block: exp(scale * (NEG_INF - m_new)) = 0
        alpha = jnp.exp((m_prev - m_new) * sm_scale)
        p = jnp.exp((s - m_new) * sm_scale)
        l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    def _write():
        l = l_ref[:, :1]
        o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[:, :1] * sm_scale + jnp.log(l)

    if window is not None:
        _band_by_query(i, j, block, window, _block, _write)
        return

    pl.when(j < i)(functools.partial(_block, False))

    @pl.when(j == i)
    def _last():  # the row's block on the diagonal
        _block(True)
        _write()


def _tri_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *,
              sm_scale, strip, lo=None, hi=None):
    """(p, ds / sm_scale) of one strip of a block (``_tri_scores``),
    float32: what both backward kernels recompute. ds is scaled where it
    has been summed."""
    r0, r1, c0, c1 = strip
    s = _tri_scores(q_ref, k_ref, strip, lo, hi)
    # lse is finite; a masked score gives exp(NEG_INF - lse) = 0
    p = jnp.exp(s * sm_scale - lse_ref[0, 0, r0:r1, :1])
    dp = jax.lax.dot_general(
        do_ref[0, 0, r0:r1, :], v_ref[0, 0, c0:c1, :],
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return p, p * (dp - delta_ref[0, 0, r0:r1, :1])


def _into_rows(acc, c0: int, c1: int, new, old: int):
    """``new`` into rows ``[c0, c1)`` of an accumulator: added to the
    first ``old`` of them, which hold a sum, and written to the rest,
    which hold nothing yet."""
    if old:
        head = new if old == c1 - c0 else new[:old]
        acc[c0:c0 + old, :] = acc[c0:c0 + old, :] + head
    if old < c1 - c0:
        acc[c0 + old:c1, :] = new[old:] if old else new


def _tri_bwd_dq_kernel(
    qi_ref, kj_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref,  # out [1, 1, b, D]
    dq_acc,  # scratch [b, D] f32
    *,
    sm_scale: float,
    strips: int,
    window=None,
):
    step = pl.program_id(2)
    i, j = qi_ref[step], kj_ref[step]
    block = q_ref.shape[2]

    @pl.when(j == (0 if window is None else i))
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _block(on_diagonal, far=None):
        for strip, lo, hi in _block_strips(
            block, strips, on_diagonal, window, far
        ):
            r0, r1, c0, c1 = strip
            _, ds = _tri_p_ds(
                q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                sm_scale=sm_scale, strip=strip, lo=lo, hi=hi,
            )
            k = k_ref[0, 0, c0:c1, :]
            dq_acc[r0:r1, :] = dq_acc[r0:r1, :] + jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    def _write():
        dq_ref[0, 0] = (dq_acc[:] * sm_scale).astype(dq_ref.dtype)

    if window is not None:  # the band's walk, as the forward's
        _band_by_query(i, j, block, window, _block, _write)
        return

    pl.when(j < i)(functools.partial(_block, False))

    @pl.when(j == i)
    def _last():
        _block(True)
        _write()


def _tri_bwd_kernel(
    qi_ref, kj_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    *refs,
    sm_scale: float,
    n_blocks: int,
    strips: int,
    window=None,
):
    """dk / dv over the triangle, key block by key block (outputs
    ``dk, dv`` ``[1, 1, b, D]`` per q-head, summed over groups outside;
    scratch ``dk_acc, dv_acc`` ``[b, D]`` f32). Led by a ``dq`` output
    ``[1, 1, T, D]`` and a ``dq_acc`` scratch ``[T, D]`` f32 it is the
    backward in ONE pass: scores, p, dp and ds computed once (five
    matmuls and one exponential pass a block where the split kernels
    run seven and two), dq summed in the float32 block that stays in
    VMEM while the head is swept and written at its last step. With a
    ``window`` a key block's queries end where the window leaves it. An
    edge block is walked in ``strips`` row strips (``_edge_strips``):
    each adds to the rows of dk and dv its span of keys covers, and to
    its own rows of dq."""
    if len(refs) == 6:
        dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = refs
    else:
        (dk_ref, dv_ref, dk_acc, dv_acc), dq_ref = refs, None
    step = pl.program_id(2)
    i, j = qi_ref[step], kj_ref[step]
    block = q_ref.shape[2]

    if dq_ref is not None:

        @pl.when(step == 0)
        def _init():
            dq_acc[...] = jnp.zeros_like(dq_acc)

    def _block(on_diagonal, far=None):
        # the block on the diagonal is the key block's first step: the
        # keys below ``written`` have been written by an earlier strip
        # and are added to, those past it have nothing to add to
        written = 0 if on_diagonal else block
        for strip, lo, hi in _block_strips(
            block, strips, on_diagonal, window, far
        ):
            r0, r1, c0, c1 = strip
            p, ds = _tri_p_ds(
                q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                sm_scale=sm_scale, strip=strip, lo=lo, hi=hi,
            )
            q, do = q_ref[0, 0, r0:r1, :], do_ref[0, 0, r0:r1, :]
            ds_lo = ds.astype(q.dtype)
            dv = jax.lax.dot_general(  # p^T @ do
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dk = jax.lax.dot_general(  # ds^T @ q
                ds_lo, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if dq_ref is not None:
                rows = pl.ds(
                    pl.multiple_of(i * block + r0, r1 - r0), r1 - r0
                )
                dq_acc[rows, :] = dq_acc[rows, :] + jax.lax.dot_general(
                    ds_lo, k_ref[0, 0, c0:c1, :], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            assert c0 <= written <= c1 or written == block, strip
            old = min(written, c1) - c0  # keys of the span to add to
            _into_rows(dv_acc, c0, c1, dv, old)
            _into_rows(dk_acc, c0, c1, dk, old)
            written = max(written, c1)
        assert written == block

    pl.when(i == j)(functools.partial(_block, True))
    if window is None:
        pl.when(i > j)(functools.partial(_block, False))
        last = n_blocks - 1
    else:
        _band_below(i, j, block, window, _block)
        last = jnp.minimum(j + _band_blocks(window, block), n_blocks - 1)

    @pl.when(i == last)
    def _finalize():
        dk_ref[0, 0] = (dk_acc[:] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)

    if dq_ref is not None:

        @pl.when(step == pl.num_programs(2) - 1)
        def _finalize_head():
            dq_ref[0, 0] = (dq_acc[...] * sm_scale).astype(dq_ref.dtype)


# the one-pass backward holds a head's float32 dq and both buffers of
# its dq block in VMEM; past this many bytes of them the split kernels
# run (T = 32768 at D = 128 in bf16 is the last that fits)
_ONE_PASS_MAX_BYTES = 32 << 20


def _one_pass_fits(T: int, D: int, itemsize: int) -> bool:
    lanes = -(-D // _LANES) * _LANES
    return T * lanes * (4 + 2 * itemsize) <= _ONE_PASS_MAX_BYTES


def _tri_call(kernel, name, steps, ins, in_specs, out_specs, out_shape,
              scratch, *, interpret, vmem_limit=None):
    """One kernel over the triangle grid ``(B, H, steps)``, the step ->
    block tables (two, or the block-diffusion walk's three) as scalar
    prefetch."""
    B, H = ins[0].shape[:2]
    return pl.pallas_call(
        kernel,
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(steps),
            grid=(B, H, steps[0].shape[0]),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit,
        ),
        interpret=interpret,
    )(*steps, *ins)


def _tri_specs(block: int, D: int, group: int):
    """Block specs of the triangle grid: blocks of query rows and of
    key rows, each by its table."""
    rows = lambda b, h, s, qi, kj, *_: (b, h, qi[s], 0)  # noqa: E731
    return (
        pl.BlockSpec((1, 1, block, D), rows),
        pl.BlockSpec(
            (1, 1, block, D),
            lambda b, h, s, qi, kj, *_: (b, h // group, kj[s], 0),
        ),
        # minor dim 1 == full array dim: a legal tile (see _fwd_pallas)
        pl.BlockSpec((1, 1, block, 1), rows),
        # dk / dv come out per QUERY head
        pl.BlockSpec(
            (1, 1, block, D), lambda b, h, s, qi, kj, *_: (b, h, kj[s], 0)
        ),
    )


def _tri_names(window):
    """A kernel's name on the triangle path, and with a window on its
    band: names of their own that a trace can tell apart."""
    stem = "flash_attn" if window is None else "flash_attn_window"
    return tuple(f"{stem}_{k}" for k in ("fwd", "bwd", "bwd_dq", "bwd_dkv"))


def _tri_fwd_call(qt, kt, vt, *, sm_scale, block, interpret, window=None):
    """[B,H,T,D] in -> (o [B,H,T,D], lse4 [B,H,T,1])."""
    B, H, T, D = qt.shape
    q_spec, kv_spec, row_spec, _ = _tri_specs(block, D, H // kt.shape[1])
    wb = _band_blocks(window, block)
    _count_edge_tiles(
        T // block, block, window, _edge_strip_count(block, interpret),
        whole=True,
    )
    return _tri_call(
        functools.partial(_tri_fwd_kernel, sm_scale=sm_scale, window=window),
        _tri_names(window)[0],
        _triangle_steps(T // block, by_key=False, wb=wb),
        (qt, kt, vt),
        [q_spec, kv_spec, kv_spec],
        [q_spec, row_spec],
        [
            jax.ShapeDtypeStruct((B, H, T, D), qt.dtype),
            jax.ShapeDtypeStruct((B, H, T, 1), jnp.float32),
        ],
        [
            pltpu.VMEM((block, D), jnp.float32),
            pltpu.VMEM((block, _LANES), jnp.float32),
            pltpu.VMEM((block, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )


def _tri_bwd_call(qt, kt, vt, dot, lse4, delta4, *, sm_scale, block,
                  interpret, window=None):
    """[B,H,T,D] in -> (dq in q's dtype, dk, dv float32 per QUERY
    head), as ``_rect_bwd_call``."""
    B, H, T, D = qt.shape
    n = T // block
    wb = _band_blocks(window, block)
    _, one_pass, dq_name, dkv_name = _tri_names(window)
    walked = _band_steps(n, block, window)
    q_spec, kv_spec, row_spec, kv_out_spec = _tri_specs(
        block, D, H // kt.shape[1]
    )
    ins = (qt, kt, vt, dot, lse4, delta4)
    in_specs = [q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec]
    dq_shape = jax.ShapeDtypeStruct((B, H, T, D), qt.dtype)
    dkv_shape = jax.ShapeDtypeStruct((B, H, T, D), jnp.float32)
    acc = pltpu.VMEM((block, D), jnp.float32)
    by_key = _triangle_steps(n, by_key=True, wb=wb)
    strips = _edge_strip_count(block, interpret)
    dkv_kernel = functools.partial(
        _tri_bwd_kernel, sm_scale=sm_scale, n_blocks=n, strips=strips,
        window=window,
    )
    kernels = 1 if _one_pass_fits(T, D, qt.dtype.itemsize) else 2
    _count_site(_STREAM, n, kernels=kernels, walked=walked)
    _count_edge_tiles(n, block, window, strips, kernels=kernels)
    if kernels == 1:
        whole_head = pl.BlockSpec(
            (1, 1, T, D), lambda b, h, s, qi, kj: (b, h, 0, 0)
        )
        return _tri_call(
            dkv_kernel, one_pass, by_key, ins, in_specs,
            [whole_head, kv_out_spec, kv_out_spec],
            [dq_shape, dkv_shape, dkv_shape],
            [pltpu.VMEM((T, D), jnp.float32), acc, acc],
            interpret=interpret, vmem_limit=_FUSED_VMEM_LIMIT,
        )
    dqt = _tri_call(
        functools.partial(
            _tri_bwd_dq_kernel, sm_scale=sm_scale, strips=strips,
            window=window,
        ),
        dq_name, _triangle_steps(n, by_key=False, wb=wb), ins,
        in_specs, q_spec, dq_shape, [acc], interpret=interpret,
    )
    dk_full, dv_full = _tri_call(
        dkv_kernel, dkv_name, by_key, ins, in_specs,
        [kv_out_spec, kv_out_spec], [dkv_shape, dkv_shape], [acc, acc],
        interpret=interpret,
    )
    return dqt, dk_full, dv_full


def _bwd_pallas(
    q,
    k,
    v,
    offsets,
    o,
    lse,
    do,
    *,
    causal,
    mask_fn,
    sm_scale,
    block_q,
    block_k,
    interpret,
    layout="bthd",
    allow_fused=True,
    diagonal=False,
    window=None,
):
    if layout == "bhtd":
        B, H, Tq, D = q.shape
        Hkv, Tk = k.shape[1], k.shape[2]
        qt, kt, vt, dot = q, k, v, do
        delta = jnp.einsum(
            "bhqd,bhqd->bhq",
            do.astype(jnp.float32),
            o.astype(jnp.float32),
        )
    else:
        B, Tq, H, D = q.shape
        Tk, Hkv = k.shape[1], k.shape[2]
        qt = q.transpose(0, 2, 1, 3)  # [B,H,T,D] kernel layout
        kt = k.transpose(0, 2, 1, 3)
        vt = v.transpose(0, 2, 1, 3)
        dot = do.transpose(0, 2, 1, 3)
        # delta_i = rowsum(do_i * o_i) — bandwidth-bound, XLA fuses it
        delta = jnp.einsum(
            "bqhd,bqhd->bhq",
            do.astype(jnp.float32),
            o.astype(jnp.float32),
        )
    group = H // Hkv
    delta4 = delta[..., None]  # [B,H,Tq,1]
    lse4 = lse[..., None]

    fused, n_tri, mask_fn = _call_plan(
        qt, kt, block_q, block_k, allow_fused=allow_fused, causal=causal,
        mask_fn=mask_fn, diagonal=diagonal, window=window,
    )
    if window is not None:
        # a backward is one kernel in the fused family and in one pass,
        # two split and on the rectangular grid
        one = fused or (n_tri and _one_pass_fits(Tq, D, qt.dtype.itemsize))
        _count_window_site(
            Tq // block_q, block_q, window if n_tri else None,
            kernels=1 if one else 2,
        )
    if fused:
        dqt, dkt, dvt = _fused_bwd_call(
            qt, kt, vt, dot, lse4, delta4, offsets,
            causal=causal, mask_fn=mask_fn, sm_scale=sm_scale,
            interpret=interpret, diagonal=diagonal,
        )
        if layout == "bhtd":
            return dqt, dkt.astype(k.dtype), dvt.astype(v.dtype)
        return (
            dqt.transpose(0, 2, 1, 3),
            dkt.transpose(0, 2, 1, 3).astype(k.dtype),
            dvt.transpose(0, 2, 1, 3).astype(v.dtype),
        )

    if n_tri:
        dqt, dk_full, dv_full = _tri_bwd_call(
            qt, kt, vt, dot, lse4, delta4, sm_scale=sm_scale,
            block=block_q, interpret=interpret, window=window,
        )
    else:
        dqt, dk_full, dv_full = _rect_bwd_call(
            qt, kt, vt, dot, lse4, delta4, offsets, causal=causal,
            mask_fn=mask_fn, sm_scale=sm_scale, block_q=block_q,
            block_k=block_k, interpret=interpret,
        )
    if layout == "bhtd":
        if group > 1:
            dk = dk_full.reshape(B, Hkv, group, Tk, D).sum(2)
            dv = dv_full.reshape(B, Hkv, group, Tk, D).sum(2)
        else:
            dk, dv = dk_full, dv_full
        return dqt, dk.astype(k.dtype), dv.astype(v.dtype)
    dq = dqt.transpose(0, 2, 1, 3)
    dk_t = dk_full.transpose(0, 2, 1, 3)  # [B,Tk,H,D]
    dv_t = dv_full.transpose(0, 2, 1, 3)
    if group > 1:
        dk = dk_t.reshape(B, Tk, Hkv, group, D).sum(3)
        dv = dv_t.reshape(B, Tk, Hkv, group, D).sum(3)
    else:
        dk, dv = dk_t, dv_t
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


def _rect_bwd_call(qt, kt, vt, dot, lse4, delta4, offsets, *, causal,
                   mask_fn, sm_scale, block_q, block_k, interpret):
    """The rectangular grid's two backward kernels over ``[B,H,T,D]``
    -> (dq in q's dtype, dk, dv float32 per QUERY head)."""
    B, H, Tq, D = qt.shape
    Tk = kt.shape[2]
    group = H // kt.shape[1]
    nq, nk = Tq // block_q, Tk // block_k
    _count_site(_STREAM, 0, kernels=2)
    common = dict(
        causal=causal,
        mask_fn=mask_fn,
        sm_scale=sm_scale,
        block_q=block_q,
        block_k=block_k,
    )
    q_spec = pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, block_k, D), lambda b, h, i, j: (b, h // group, j, 0)
    )
    row_spec = pl.BlockSpec(
        (1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0)
    )

    dqt = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        name="flash_attn_bwd_dq",
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            q_spec,
            kv_spec,
            kv_spec,
            q_spec,
            row_spec,
            row_spec,
        ],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Tq, D), qt.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel",
                "parallel",
                "parallel",
                "arbitrary",
            ),
        ),
        interpret=interpret,
    )(offsets, qt, kt, vt, dot, lse4, delta4)

    # dk/dv pass: grid iterates k blocks outer, q blocks inner. Outputs are
    # per q-head ([B,H,Tk,D]); GQA folds the head group by summing outside.
    q_spec2 = pl.BlockSpec(
        (1, 1, block_q, D), lambda b, h, j, i: (b, h, i, 0)
    )
    kv_spec2 = pl.BlockSpec(
        (1, 1, block_k, D), lambda b, h, j, i: (b, h // group, j, 0)
    )
    kv_out_spec = pl.BlockSpec(
        (1, 1, block_k, D), lambda b, h, j, i: (b, h, j, 0)
    )
    row_spec2 = pl.BlockSpec(
        (1, 1, block_q, 1), lambda b, h, j, i: (b, h, i, 0)
    )
    dk_full, dv_full = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common),
        name="flash_attn_bwd_dkv",
        grid=(B, H, nk, nq),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            q_spec2,
            kv_spec2,
            kv_spec2,
            q_spec2,
            row_spec2,
            row_spec2,
        ],
        out_specs=[kv_out_spec, kv_out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tk, D), jnp.float32),
            jax.ShapeDtypeStruct((B, H, Tk, D), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel",
                "parallel",
                "parallel",
                "arbitrary",
            ),
        ),
        interpret=interpret,
    )(offsets, qt, kt, vt, dot, lse4, delta4)
    return dqt, dk_full, dv_full


# ---------------------------------------------------------------------------
# custom-vjp wrapper around the pallas path (static offsets)
# ---------------------------------------------------------------------------
# Offsets are static here so they can ride nondiff_argnums; callers with
# *traced* offsets (ring attention's per-hop global positions) use the raw
# ``flash_attention_fwd``/``flash_attention_bwd`` pair and define their own
# VJP at the ring level, where the lse residual's gradient is handled.
@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11)
)
def _flash_pallas(
    q, k, v, offsets, causal, mask_fn, sm_scale, block_q, block_k, layout,
    allow_fused, window=None,
):
    o, _ = _fwd_pallas(
        q,
        k,
        v,
        jnp.asarray(offsets, jnp.int32),
        causal=causal,
        mask_fn=mask_fn,
        sm_scale=sm_scale,
        block_q=block_q,
        block_k=block_k,
        interpret=_interpret_default(),
        layout=layout,
        allow_fused=allow_fused,
        diagonal=_on_diagonal(*offsets),
        window=window,
    )
    return o


def _flash_fwd_rule(
    q, k, v, offsets, causal, mask_fn, sm_scale, block_q, block_k, layout,
    allow_fused, window,
):
    o, lse = _fwd_pallas(
        q,
        k,
        v,
        jnp.asarray(offsets, jnp.int32),
        causal=causal,
        mask_fn=mask_fn,
        sm_scale=sm_scale,
        block_q=block_q,
        block_k=block_k,
        interpret=_interpret_default(),
        layout=layout,
        allow_fused=allow_fused,
        diagonal=_on_diagonal(*offsets),
        window=window,
    )
    # one copy of each: the named ``o`` is the primal result too
    q, k, v, o, lse = map(checkpoint_name, (q, k, v, o, lse), KEPT)
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(
    offsets, causal, mask_fn, sm_scale, block_q, block_k, layout,
    allow_fused, window, res, do,
):
    q, k, v, o, lse = res
    dq, dk, dv = _bwd_pallas(
        q,
        k,
        v,
        jnp.asarray(offsets, jnp.int32),
        o,
        lse,
        do,
        causal=causal,
        mask_fn=mask_fn,
        sm_scale=sm_scale,
        block_q=block_q,
        block_k=block_k,
        interpret=_interpret_default(),
        layout=layout,
        allow_fused=allow_fused,
        diagonal=_on_diagonal(*offsets),
        window=window,
    )
    return dq, dk, dv


_flash_pallas.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _on_diagonal(q_offset, k_offset) -> bool:
    """Query and key offsets known equal while the program is traced:
    the sequence attends to itself (or a chunk on the diagonal of a
    chunked sequence does), so a causal mask's visible region is the
    lower triangle. A traced offset (a ring hop) is not known."""
    return (
        isinstance(q_offset, int)
        and isinstance(k_offset, int)
        and q_offset == k_offset
    )


def _interpret_default() -> bool:
    """Pallas kernels only compile on TPU; interpret elsewhere (tests)."""
    return jax.default_backend() != "tpu"


# Raw (non-differentiable) kernel entries for callers composing their own
# VJP — ring attention merges per-hop (o, lse) partials across devices.
def flash_attention_fwd(
    q,
    k,
    v,
    *,
    causal=True,
    sm_scale=None,
    mask_fn=None,
    q_offset=0,
    k_offset=0,
    block_q=None,
    block_k=None,
    interpret=None,
    layout="bthd",
    allow_fused=True,
    window=None,
):
    """Forward kernel; returns ``(o, lse)`` with lse ``[B,H,Tq]`` f32.

    ``allow_fused=False`` pins the streaming (block-tiled) kernels even
    when the fused short-seq form is eligible — for tests and A/B
    timing."""
    window = _checked_window(
        q, layout, causal, mask_fn, q_offset, k_offset, window
    )
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    bq, bk = _call_blocks(
        q, k, block_q, block_k, layout, causal, mask_fn, q_offset, k_offset
    )
    return _fwd_pallas(
        q,
        k,
        v,
        jnp.asarray(jnp.stack([q_offset, k_offset]), jnp.int32),
        causal=causal,
        mask_fn=mask_fn,
        sm_scale=scale,
        block_q=bq,
        block_k=bk,
        interpret=_interpret_default() if interpret is None else interpret,
        layout=layout,
        allow_fused=allow_fused,
        diagonal=_on_diagonal(q_offset, k_offset),
        window=window,
    )


def merge_partials(o_a, lse_a, o_b, lse_b):
    """Online-softmax merge of two partial attention results over the
    same queries, different key sets: ``o`` [B,T,H,D] f32 normalized,
    ``lse`` [B,H,T] f32 log-sum-exp. The algebra ring attention uses
    per hop (parallel/ring_attention.py), shared here so chunked
    single-device attention and cross-device merges cannot diverge."""
    lse_new = jnp.logaddexp(lse_a, lse_b)
    w_a = jnp.exp(lse_a - lse_new)
    w_b = jnp.exp(lse_b - lse_new)

    def to_o(w):  # [B,H,T] -> [B,T,H,1]
        return w.transpose(0, 2, 1)[..., None]

    return o_a * to_o(w_a) + o_b * to_o(w_b), lse_new


def flash_attention_fwd_chunked(
    q,
    k,
    v,
    *,
    causal: bool = True,
    sm_scale=None,
    mask_fn: Optional[MaskFn] = None,
    q_offset: int = 0,
    k_offset: int = 0,
    chunk: int = _FUSED_MAX_T,
):
    """Long-sequence forward as fused [chunk x chunk] tile calls plus
    online-softmax merges (``merge_partials``) — the streaming kernel's
    outer loop lifted to XLA level so every tile rides the fused
    short-seq kernel. ``[B,T,H,D]`` layout; T must divide by ``chunk``.
    Returns ``(o, lse[B,H,Tq])`` like ``flash_attention_fwd``.

    Exists because the fused kernel caps at T=``_FUSED_MAX_T`` (the
    [T,T] score tile must fit VMEM): a full-sequence caller (Ulysses'
    per-device attention after its all-to-all) otherwise drops to the
    streaming kernels for the WHOLE sequence, paying a different
    kernel strategy than ring attention's naturally-chunked hops — the
    like-for-like gap review r4 #8 flagged. Causal chunks below the
    diagonal are skipped entirely (the work-skipping a causal streaming
    grid does with masked blocks)."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    if Tq % chunk or Tk % chunk or chunk % 8:
        raise ValueError(
            f"{Tq=}/{Tk=} must divide into 8-aligned {chunk=}"
        )
    if not isinstance(q_offset, int) or not isinstance(k_offset, int):
        raise ValueError(
            "chunked driver needs static int offsets (tile skipping "
            "is decided at trace time)"
        )
    n_q, n_k = Tq // chunk, Tk // chunk
    o_parts, lse_parts = [], []
    for i in range(n_q):
        qi = lax.slice_in_dim(q, i * chunk, (i + 1) * chunk, axis=1)
        o_acc = None
        lse_acc = None
        for j in range(n_k):
            if causal and (
                q_offset + (i + 1) * chunk - 1 < k_offset + j * chunk
            ):
                continue  # tile fully above the causal diagonal
            o_j, lse_j = flash_attention_fwd(
                qi,
                lax.slice_in_dim(k, j * chunk, (j + 1) * chunk, axis=1),
                lax.slice_in_dim(v, j * chunk, (j + 1) * chunk, axis=1),
                causal=causal,
                sm_scale=sm_scale,
                mask_fn=mask_fn,
                q_offset=q_offset + i * chunk,
                k_offset=k_offset + j * chunk,
            )
            o_j = o_j.astype(jnp.float32)
            if o_acc is None:
                o_acc, lse_acc = o_j, lse_j
            else:
                o_acc, lse_acc = merge_partials(o_acc, lse_acc, o_j, lse_j)
        if o_acc is None:  # every key after every query: empty softmax
            o_acc = jnp.zeros((B, chunk, H, D), jnp.float32)
            lse_acc = jnp.full((B, H, chunk), NEG_INF, jnp.float32)
        o_parts.append(o_acc)
        lse_parts.append(lse_acc)
    o = jnp.concatenate(o_parts, axis=1).astype(q.dtype)
    lse = jnp.concatenate(lse_parts, axis=2)
    return o, lse


def flash_attention_bwd(
    q,
    k,
    v,
    o,
    lse,
    do,
    *,
    causal=True,
    sm_scale=None,
    mask_fn=None,
    q_offset=0,
    k_offset=0,
    block_q=None,
    block_k=None,
    interpret=None,
    layout="bthd",
    allow_fused=True,
    window=None,
):
    """Backward kernels; returns ``(dq, dk, dv)`` given saved residuals."""
    window = _checked_window(
        q, layout, causal, mask_fn, q_offset, k_offset, window
    )
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    bq, bk = _call_blocks(
        q, k, block_q, block_k, layout, causal, mask_fn, q_offset, k_offset
    )
    return _bwd_pallas(
        q,
        k,
        v,
        jnp.asarray(jnp.stack([q_offset, k_offset]), jnp.int32),
        o,
        lse,
        do,
        causal=causal,
        mask_fn=mask_fn,
        sm_scale=scale,
        block_q=bq,
        block_k=bk,
        interpret=_interpret_default() if interpret is None else interpret,
        layout=layout,
        allow_fused=allow_fused,
        diagonal=_on_diagonal(q_offset, k_offset),
        window=window,
    )


# Blocks of the streaming kernels where the caller states none. Measured
# on v5e (bf16, D = 128; ms a call forward / forward + backward, PERF.md
# PR 38) on the triangle path at [2, 16/16, 4096, 128] and
# [1, 32/2, 8192, 128]: 256 5.31 / 13.19 and 20.92 / 49.30, 512 2.59 /
# 6.81 and 9.70 / 24.39, 1024 1.24 / 5.04 and 4.44 / 17.39: a step's
# work on its [b, 1] running statistics and [b, D] accumulators costs as
# much as a quarter of a [b, 512] score tile each, and a wider tile
# halves it. The rectangular grid keeps the 512 it was measured at.
_BLOCK = 512
_TRI_BLOCK = 1024


def _sees_triangle(causal, mask_fn, diagonal: bool) -> bool:
    """The visible region is known to be the lower triangle when the
    program is traced: a causal mask alone, with query and key offsets
    static and equal (``_on_diagonal``). Both families test it."""
    return bool(causal and mask_fn is None and diagonal)


def _checked_window(q, layout, causal, mask_fn, q_offset, k_offset, window):
    """A call's ``window`` as the kernels take it: None where there is
    none, or where the offsets are static and no query is ``window`` or
    more keys past the first key (the call is then the plain causal one,
    and is traced as that)."""
    if window is None:
        return None
    if isinstance(window, bool) or not isinstance(window, int) or window < 1:
        raise ValueError(f"window {window!r}: a whole number of keys, 1 up")
    if not causal or mask_fn is not None:
        raise ValueError(
            "a window is of a causal call without a mask_fn (a query "
            "sees itself and the window - 1 keys before it)"
        )
    if isinstance(q_offset, int) and isinstance(k_offset, int):
        Tq = q.shape[2 if layout == "bhtd" else 1]
        if q_offset + Tq - 1 - k_offset < window:
            return None
    return window


def _validate_blocks(q, k, block_q, block_k, layout="bthd", triangle=False):
    seq_axis = 2 if layout == "bhtd" else 1
    Tq, Tk = q.shape[seq_axis], k.shape[seq_axis]
    if block_q is None and block_k is None:
        # the caller states none: the triangle path's where it divides
        # the one sequence and the head is no wider than measured
        wide = (
            triangle and Tq == Tk and Tq % _TRI_BLOCK == 0
            and q.shape[-1] <= _LANES
        )
        block_q = block_k = _TRI_BLOCK if wide else _BLOCK
    block_q, block_k = block_q or _BLOCK, block_k or _BLOCK
    bq, bk = min(block_q, Tq), min(block_k, Tk)
    if Tq % bq or Tk % bk or bq % 8 or bk % 8:
        # TPU sublane tiling wants 8-aligned seq blocks; the public entry
        # falls back to the jnp path on this error
        raise ValueError(
            f"sequence lengths ({Tq=}, {Tk=}) must divide into 8-aligned "
            f"blocks ({bq=}, {bk=}); pad inputs or pass other block sizes"
        )
    return bq, bk


def _call_blocks(q, k, block_q, block_k, layout, causal, mask_fn,
                 q_offset, k_offset):
    """``_validate_blocks`` for an entry's call, which knows here
    whether it will see the triangle."""
    return _validate_blocks(
        q, k, block_q, block_k, layout,
        triangle=_sees_triangle(
            causal, mask_fn, _on_diagonal(q_offset, k_offset)
        ),
    )


# ---------------------------------------------------------------------------
# the block-diffusion walk: a row fed twice, its noised copy before the
# clean one, under a rule that is neither triangle nor band
# ---------------------------------------------------------------------------
# Positions ``[0, 2 L)``: ``i < L`` is the noised copy of position ``i``,
# ``i >= L`` the clean copy of ``i - L``; ``b(i) = (i mod L) // B`` is a
# position's block of ``B``. A query sees a key iff
#   noised query, noised key:  b(key) == b(query)   ("eq")
#   noised query, clean key:   b(key) <  b(query)   ("lt")
#   clean query,  clean key:   b(key) <= b(query)   ("le")
# and a clean query sees no noised key. Every query sees its own position.
# Which blocks of the kernels' ``2n x 2n`` grid hold a visible pair is known
# when the program is traced, as the triangle's and the band's are: the
# step -> block tables list those blocks and no other (at L = 8192, B = 4 in
# blocks of 1024: 36 clean x clean, 36 noised x clean and the 8 noised x
# noised blocks on the diagonal, 80 of 256), and a third table says of each
# step which rule masks it (none where every pair is visible) and whether it
# is a row's first or last.
_BD = ("attn_bd_blocks_walked", "attn_bd_blocks_square")
_BD_WHOLE, _BD_LE, _BD_LT, _BD_EQ = 0, 1, 2, 3
_BD_FIRST, _BD_LAST = 4, 8
# Row strips of a block on the noised x noised diagonal, where a strip's
# rows see keys of the strip's own span alone (8: 128 x 128 tiles in a
# block of 1024, an eighth of the block)
_BD_EQ_STRIPS = 8


@functools.lru_cache(maxsize=None)
def block_diffusion_mask(seq: int, block_len: int) -> MaskFn:
    """The block-diffusion rule as a ``mask_fn`` over positions ``[0, 2
    seq)``: what the walk is wherever no walk is made (the jnp path, the
    rectangular grid), and what the tests hold the walk to. One function
    a ``(seq, block_len)``, so that it can ride as a static argument."""

    def mask(q_pos, k_pos):
        q_clean, k_clean = q_pos >= seq, k_pos >= seq
        qb = jnp.where(q_clean, q_pos - seq, q_pos) // block_len
        kb = jnp.where(k_clean, k_pos - seq, k_pos) // block_len
        # and / or / not alone: Mosaic takes no select between masks
        return (
            (q_clean & k_clean & (kb <= qb))
            | (~q_clean & k_clean & (kb < qb))
            | (~q_clean & ~k_clean & (kb == qb))
        )

    return mask


@functools.lru_cache(maxsize=None)
def _bd_blocks(seq: int, block_len: int, blk: int):
    """Every block ``(qi, kj, kind)`` of the ``2n x 2n`` grid, ``n = seq /
    blk``, that holds a visible pair: ``kind`` the rule that masks it, or
    ``_BD_WHOLE`` where all its pairs are visible."""
    n = seq // blk

    def ids(b):  # the first and last diffusion block a kernel block holds
        return b * blk // block_len, ((b + 1) * blk - 1) // block_len

    blocks = []
    for i in range(n):
        r0, r1 = ids(i)
        for j in range(n):
            c0, c1 = ids(j)
            for qi, kj, rule, some, whole in (
                (i, j, _BD_EQ, c0 <= r1 and r0 <= c1, r0 == r1 == c0 == c1),
                (i, n + j, _BD_LT, c0 < r1, c1 < r0),
                (n + i, n + j, _BD_LE, c0 <= r1, c1 <= r0),
            ):
                if some:
                    blocks.append((qi, kj, _BD_WHOLE if whole else rule))
    return tuple(blocks)


def _bd_steps(seq: int, block_len: int, blk: int, by_key: bool):
    """step -> (query block, key block, code) in the order a kernel
    accumulates: a query block's keys, the block that holds its own
    positions first (every row sees itself there, so the running maximum
    is finite before a block some of its rows see nothing of), or with
    ``by_key`` a key block's queries. ``code`` is the step's mask kind,
    plus ``_BD_FIRST`` / ``_BD_LAST`` on a row's first and last step."""
    row = (lambda b: b[1]) if by_key else (lambda b: b[0])
    col = (lambda b: b[0]) if by_key else (lambda b: b[1])
    ordered = sorted(
        _bd_blocks(seq, block_len, blk),
        key=lambda b: (row(b), col(b) != row(b), col(b)),
    )
    steps = []
    for at, (qi, kj, kind) in enumerate(ordered):
        first = at == 0 or row(ordered[at - 1]) != row(ordered[at])
        last = at == len(ordered) - 1 or (
            row(ordered[at + 1]) != row(ordered[at])
        )
        steps.append((qi, kj, kind + _BD_FIRST * first + _BD_LAST * last))
    return tuple(
        jnp.asarray(column, jnp.int32) for column in zip(*steps)
    )


def _bd_strips(blk: int, block_len: int, kind: int, strips: int):
    """The plan of an edge block of the walk, ``((r0, r1, c0, c1), ...)``:
    a strip of query rows against the one span of the block's keys that
    any of them sees, as ``_edge_strips``. Where diffusion blocks divide
    the kernel's, an edge block lies on a diagonal and its pattern is the
    same wherever it lies: a noised x noised block's strip sees its own
    span, a clean key block's strip every key up to its last row's. A
    block that diffusion blocks cross is one strip, masked by position."""
    while strips > 1 and (blk % strips or blk // strips % block_len):
        strips //= 2
    if blk % block_len or strips == 1:
        return ((0, blk, 0, blk),)
    rows = blk // strips
    return tuple(
        (r, r + rows, r if kind == _BD_EQ else 0, r + rows)
        for r in range(0, blk, rows)
    )


def _bd_strip_counts(blk: int, block_len: int, interpret: bool):
    """``(strips of an "eq" block, strips of an "le" / "lt" block)``."""
    tile = 1 if interpret else _LANES
    eq = _BD_EQ_STRIPS
    while eq > 1 and (blk % eq or blk // eq % tile):
        eq //= 2
    return eq, _edge_strip_count(blk, interpret)


def _bd_plan(blk: int, block_len: int, kind: int, strips, forward: bool):
    """What a kernel multiplies of one block of mask ``kind``: a block
    seen whole is one strip; the forward computes a clean key block's edge
    whole and masks it, as the triangle's forward does (``_EDGE_STRIPS``),
    and a noised x noised block in strips of its own span; the backward
    walks every edge block in strips. ``strips`` is ``_bd_strip_counts``'."""
    if kind == _BD_WHOLE or (forward and kind != _BD_EQ):
        return ((0, blk, 0, blk),)
    eq, edge = strips
    return _bd_strips(blk, block_len, kind, eq if kind == _BD_EQ else edge)


def _bd_block_ids(pos, block_len: int):
    """The diffusion block of each position (whole numbers from 0)."""
    if block_len & (block_len - 1) == 0:
        return lax.shift_right_logical(
            pos, jnp.int32(block_len.bit_length() - 1)
        )
    return lax.div(pos, jnp.int32(block_len))


def _bd_scores(q_ref, k_ref, strip, kind: int, i, j, *, seq, block_len):
    """Raw scores ``q k^T`` of one strip of block ``(i, j)``, float32,
    masked by the rule ``kind`` names on the positions the strip holds."""
    r0, r1, c0, c1 = strip
    s = jax.lax.dot_general(
        q_ref[0, 0, r0:r1, :], k_ref[0, 0, c0:c1, :],
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if kind == _BD_WHOLE:
        return s
    blk = q_ref.shape[2]
    n = seq // blk
    # a block's place in its copy of the row
    row0 = jnp.where(i >= n, i - n, i) * blk + r0
    col0 = jnp.where(j >= n, j - n, j) * blk + c0
    rb = _bd_block_ids(
        row0 + lax.broadcasted_iota(jnp.int32, (r1 - r0, 1), 0), block_len
    )
    cb = _bd_block_ids(
        col0 + lax.broadcasted_iota(jnp.int32, (1, c1 - c0), 1), block_len
    )
    seen = cb <= rb if kind == _BD_LE else (
        cb < rb if kind == _BD_LT else cb == rb
    )
    return jnp.where(seen, s, NEG_INF)


def _bd_each_kind(code, body):
    """``body(kind)`` under the branch of the step's mask kind, each kind
    static in its own."""
    kind = code & 3
    for static in (_BD_WHOLE, _BD_LE, _BD_LT, _BD_EQ):
        pl.when(kind == static)(functools.partial(body, static))


def _bd_fwd_kernel(
    qi_ref, kj_ref, code_ref,  # SMEM [steps] each
    q_ref, k_ref, v_ref,  # VMEM [1, 1, b, D]
    o_ref, lse_ref,  # VMEM [1, 1, b, D], [1, 1, b, 1]
    acc_ref, m_ref, l_ref,  # scratch, as ``_tri_fwd_kernel``'s
    *, sm_scale: float, seq: int, block_len: int, strips,
):
    """One step of a query block's walk over the key blocks it can see
    (``_bd_steps``), the triangle kernel's arithmetic: the running maximum
    is of raw scores, the scale rides in the exponent."""
    step = pl.program_id(2)
    i, j, code = qi_ref[step], kj_ref[step], code_ref[step]
    blk = q_ref.shape[2]

    @pl.when((code & _BD_FIRST) != 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _block(kind):
        for strip in _bd_plan(blk, block_len, kind, strips, True):
            r0, r1, c0, c1 = strip
            s = _bd_scores(
                q_ref, k_ref, strip, kind, i, j, seq=seq,
                block_len=block_len,
            )
            m_prev = m_ref[r0:r1, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp((m_prev - m_new) * sm_scale)
            p = jnp.exp((s - m_new) * sm_scale)
            l_new = l_ref[r0:r1, :1] * alpha + jnp.sum(
                p, axis=-1, keepdims=True
            )
            acc_ref[r0:r1, :] = acc_ref[r0:r1, :] * alpha + (
                jax.lax.dot_general(
                    p.astype(v_ref.dtype), v_ref[0, 0, c0:c1, :],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            )
            m_ref[r0:r1, :] = jnp.broadcast_to(m_new, (r1 - r0, _LANES))
            l_ref[r0:r1, :] = jnp.broadcast_to(l_new, (r1 - r0, _LANES))

    _bd_each_kind(code, _block)

    @pl.when((code & _BD_LAST) != 0)
    def _write():
        l = l_ref[:, :1]
        o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[:, :1] * sm_scale + jnp.log(l)


def _bd_bwd_kernel(
    qi_ref, kj_ref, code_ref,
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref,  # out [1, 1, 2 L, D]: a head's, resident while it is swept
    dk_ref, dv_ref,  # out [1, 1, b, D] per QUERY head, float32
    dq_acc, dk_acc, dv_acc,  # scratch f32 [2 L, D], [b, D], [b, D]
    *, sm_scale: float, seq: int, block_len: int, strips,
):
    """The backward in one pass, key block by key block, as
    ``_tri_bwd_kernel`` with its ``dq`` output: scores, p, dp and ds once a
    block. An edge block is walked in row strips (``_bd_strips``)."""
    step = pl.program_id(2)
    i, j, code = qi_ref[step], kj_ref[step], code_ref[step]
    blk = q_ref.shape[2]

    @pl.when(step == 0)
    def _init_head():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when((code & _BD_FIRST) != 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _block(kind):
        for strip in _bd_plan(blk, block_len, kind, strips, False):
            r0, r1, c0, c1 = strip
            s = _bd_scores(
                q_ref, k_ref, strip, kind, i, j, seq=seq,
                block_len=block_len,
            )
            # lse is finite (every row sees itself); a masked score gives
            # exp(NEG_INF - lse) = 0
            p = jnp.exp(s * sm_scale - lse_ref[0, 0, r0:r1, :1])
            q, do = q_ref[0, 0, r0:r1, :], do_ref[0, 0, r0:r1, :]
            dp = jax.lax.dot_general(
                do, v_ref[0, 0, c0:c1, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds_lo = (p * (dp - delta_ref[0, 0, r0:r1, :1])).astype(q.dtype)
            dv_acc[c0:c1, :] = dv_acc[c0:c1, :] + jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dk_acc[c0:c1, :] = dk_acc[c0:c1, :] + jax.lax.dot_general(
                ds_lo, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            rows = pl.ds(pl.multiple_of(i * blk + r0, r1 - r0), r1 - r0)
            dq_acc[rows, :] = dq_acc[rows, :] + jax.lax.dot_general(
                ds_lo, k_ref[0, 0, c0:c1, :], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    _bd_each_kind(code, _block)

    @pl.when((code & _BD_LAST) != 0)
    def _write():
        dk_ref[0, 0] = (dk_acc[:] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when(step == pl.num_programs(2) - 1)
    def _write_head():
        dq_ref[0, 0] = (dq_acc[...] * sm_scale).astype(dq_ref.dtype)


def _count_bd_site(seq: int, block_len: int, blk: int, strips, *,
                   forward: bool):
    """One kernel of the walk into ``common/trace_counts``: the blocks a
    head walks against the ``2n x 2n`` grid's (``_BD``), and the score
    tiles its edge blocks hold and multiply (``_EDGE``, as
    ``_count_edge_tiles``: a tile the height of a strip a side)."""
    blocks = _bd_blocks(seq, block_len, blk)
    for name, k in zip(_BD, (len(blocks), (2 * seq // blk) ** 2)):
        trace_counts.count(name, k)
    held = multiplied = 0
    for _, _, kind in blocks:
        if kind == _BD_WHOLE:
            continue
        # tiles the height of one of the block's backward strips a side
        tiles = len(_bd_plan(blk, block_len, kind, strips, False))
        rows = blk // tiles
        held += tiles * tiles
        multiplied += sum(
            (r1 - r0) // rows * ((c1 - c0) // rows)
            for r0, r1, c0, c1 in _bd_plan(
                blk, block_len, kind, strips, forward
            )
        )
    for name, k in zip(_EDGE, (multiplied, held)):
        trace_counts.count(name, k)


def _bd_fwd_call(qt, kt, vt, *, seq, block_len, sm_scale, blk, interpret):
    """[B,H,2L,D] in -> (o [B,H,2L,D], lse4 [B,H,2L,1])."""
    B, H, T, D = qt.shape
    q_spec, kv_spec, row_spec, _ = _tri_specs(blk, D, H // kt.shape[1])
    strips = _bd_strip_counts(blk, block_len, interpret)
    _count_bd_site(seq, block_len, blk, strips, forward=True)
    return _tri_call(
        functools.partial(
            _bd_fwd_kernel, sm_scale=sm_scale, seq=seq,
            block_len=block_len, strips=strips,
        ),
        "flash_attn_bd_fwd",
        _bd_steps(seq, block_len, blk, by_key=False),
        (qt, kt, vt),
        [q_spec, kv_spec, kv_spec],
        [q_spec, row_spec],
        [
            jax.ShapeDtypeStruct((B, H, T, D), qt.dtype),
            jax.ShapeDtypeStruct((B, H, T, 1), jnp.float32),
        ],
        [
            pltpu.VMEM((blk, D), jnp.float32),
            pltpu.VMEM((blk, _LANES), jnp.float32),
            pltpu.VMEM((blk, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )


def _bd_bwd_call(qt, kt, vt, dot, lse4, delta4, *, seq, block_len,
                 sm_scale, blk, interpret):
    """[B,H,2L,D] in -> (dq in q's dtype, dk, dv float32 per QUERY
    head), as ``_tri_bwd_call``'s one pass."""
    B, H, T, D = qt.shape
    q_spec, kv_spec, row_spec, kv_out_spec = _tri_specs(
        blk, D, H // kt.shape[1]
    )
    strips = _bd_strip_counts(blk, block_len, interpret)
    _count_bd_site(seq, block_len, blk, strips, forward=False)
    whole_head = pl.BlockSpec((1, 1, T, D), lambda b, h, s, *_: (b, h, 0, 0))
    dkv_shape = jax.ShapeDtypeStruct((B, H, T, D), jnp.float32)
    acc = pltpu.VMEM((blk, D), jnp.float32)
    return _tri_call(
        functools.partial(
            _bd_bwd_kernel, sm_scale=sm_scale, seq=seq,
            block_len=block_len, strips=strips,
        ),
        "flash_attn_bd_bwd",
        _bd_steps(seq, block_len, blk, by_key=True),
        (qt, kt, vt, dot, lse4, delta4),
        [q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        [whole_head, kv_out_spec, kv_out_spec],
        [jax.ShapeDtypeStruct((B, H, T, D), qt.dtype), dkv_shape, dkv_shape],
        [pltpu.VMEM((T, D), jnp.float32), acc, acc],
        interpret=interpret, vmem_limit=_FUSED_VMEM_LIMIT,
    )


def _bd_block(seq: int, D: int, itemsize: int, block):
    """The block of the walk's kernels over a doubled row of ``2 seq``, or
    None where they cannot run it: the stated ``block`` (1024 where it
    divides ``seq`` and the head is no wider than measured, else 512), if
    it divides the row into no more blocks than the tables hold and the
    one-pass backward's float32 dq of a head fits."""
    if block is None:
        wide = seq % _TRI_BLOCK == 0 and D <= _LANES
        block = min(_TRI_BLOCK if wide else _BLOCK, seq)
    if seq % block or block % 8 or 2 * seq // block > _TRI_MAX_BLOCKS:
        return None
    return block if _one_pass_fits(2 * seq, D, itemsize) else None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _bd_pallas(q, k, v, seq, block_len, sm_scale, blk, interpret):
    o, _ = _bd_fwd_call(
        q, k, v, seq=seq, block_len=block_len, sm_scale=sm_scale, blk=blk,
        interpret=interpret,
    )
    return o


def _bd_fwd_rule(q, k, v, seq, block_len, sm_scale, blk, interpret):
    o, lse4 = _bd_fwd_call(
        q, k, v, seq=seq, block_len=block_len, sm_scale=sm_scale, blk=blk,
        interpret=interpret,
    )
    # what a recomputed layer keeps, under the names every call's carry
    # (the logsumexp as [B,H,2L]: a minor dimension of 1 is a lane tile)
    q, k, v, o, lse = map(
        checkpoint_name, (q, k, v, o, lse4[..., 0]), KEPT
    )
    return o, (q, k, v, o, lse)


def _bd_bwd_rule(seq, block_len, sm_scale, blk, interpret, res, do):
    q, k, v, o, lse = res
    B, H, T, D = q.shape
    Hkv = k.shape[1]
    delta4 = jnp.einsum(
        "bhqd,bhqd->bhq", do.astype(jnp.float32), o.astype(jnp.float32)
    )[..., None]
    dq, dk, dv = _bd_bwd_call(
        q, k, v, do, lse[..., None], delta4, seq=seq, block_len=block_len,
        sm_scale=sm_scale, blk=blk, interpret=interpret,
    )
    if H != Hkv:
        dk = dk.reshape(B, Hkv, H // Hkv, T, D).sum(2)
        dv = dv.reshape(B, Hkv, H // Hkv, T, D).sum(2)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


_bd_pallas.defvjp(_bd_fwd_rule, _bd_bwd_rule)


def block_diffusion_attention(
    q,
    k,
    v,
    *,
    block_len: int,
    sm_scale: Optional[float] = None,
    layout: str = "bthd",
    block: Optional[int] = None,
    force: Optional[str] = None,
    interpret: Optional[bool] = None,
):
    """Attention over a row fed twice, ``q:[B,2L,H,D] k,v:[B,2L,Hkv,D]``
    (or ``[B,H,2L,D]`` with ``layout="bhtd"``): the noised copy of a row
    of ``L`` positions before the clean one, under the block-diffusion
    rule over blocks of ``block_len`` (``block_diffusion_mask``): a noised
    query sees the noised keys of its own block and the clean keys of the
    blocks before it, a clean query the clean keys up to its own block's
    end. Differentiable.

    On the TPU (``force="pallas"`` elsewhere) the ``flash_attn_bd_*``
    kernels walk only the blocks of the ``2L x 2L`` grid that hold a
    visible pair, forward and in a one-pass backward, and the clean
    half's keys and values are read by both halves' queries where they
    lie. Where those kernels cannot run the shape (``_bd_block``) the rule
    is a ``mask_fn`` over the rectangular grid, and on the jnp path over
    the materialized scores: exact, and nothing skipped
    (``attn_bd_blocks_walked`` then equals ``attn_bd_blocks_square``)."""
    seq_axis = 2 if layout == "bhtd" else 1
    T, D = q.shape[seq_axis], q.shape[-1]
    if T % 2 or (T // 2) % block_len or k.shape[seq_axis] != T:
        raise ValueError(
            f"a doubled row of {T} positions against "
            f"{k.shape[seq_axis]} keys is no two copies of whole blocks "
            f"of {block_len}"
        )
    seq = T // 2
    scale = sm_scale if sm_scale is not None else D**-0.5
    mode = force
    if mode is None:
        mode = "pallas" if jax.default_backend() == "tpu" else "reference"
    blk = _bd_block(seq, D, q.dtype.itemsize, block)
    if mode == "reference" or blk is None:
        if mode != "reference":
            # forward, dq and dk / dv kernels over the whole square
            square = (T // min(_BLOCK, T)) ** 2
            for name in _BD:
                trace_counts.count(name, 3 * square)
        return flash_attention(
            q, k, v, causal=False, sm_scale=scale, layout=layout,
            mask_fn=block_diffusion_mask(seq, block_len),
            force="reference" if mode == "reference" else force,
        )
    if layout != "bhtd":
        q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    trace_counts.count("attn_kept_sites", trace_counts.keeping())
    o = _bd_pallas(
        q, k, v, seq, block_len, scale, blk,
        _interpret_default() if interpret is None else interpret,
    )
    return o if layout == "bhtd" else o.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# jnp reference (CPU fallback + numerics oracle)
# ---------------------------------------------------------------------------
def flash_attention_reference(
    q,
    k,
    v,
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    mask_fn: Optional[MaskFn] = None,
    q_offset=0,
    k_offset=0,
    return_residuals: bool = False,
    window: Optional[int] = None,
):
    """Same semantics as the kernel, materialized scores. Differentiable."""
    if _checked_window(
        q, "bthd", causal, mask_fn, q_offset, k_offset, window
    ) is not None:
        mask_fn = _window_mask(window)
    D = q.shape[-1]
    H, Hkv = q.shape[2], k.shape[2]
    if Hkv != H:
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
    scale = sm_scale if sm_scale is not None else D**-0.5
    s = (
        jnp.einsum(
            "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
        )
        * scale
    )
    Tq, Tk = q.shape[1], k.shape[1]
    q_pos = (q_offset + jnp.arange(Tq))[:, None]
    k_pos = (k_offset + jnp.arange(Tk))[None, :]
    mask = _mask_for_block(q_pos, k_pos, causal, mask_fn)
    if mask is not None:
        s = jnp.where(mask[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    m_safe = jnp.maximum(m, NEG_INF)
    p = jnp.exp(s - m_safe)
    l = jnp.sum(p, axis=-1, keepdims=True)
    visible = m > NEG_INF / 2
    o = jnp.einsum("bhqk,bkhd->bqhd", p / jnp.maximum(l, 1e-30), v)
    o = jnp.where(
        visible.squeeze(-1)[..., None].transpose(0, 2, 1, 3), o, 0.0
    ).astype(q.dtype)
    if not return_residuals:
        return o
    lse = jnp.where(
        visible, m_safe + jnp.log(jnp.maximum(l, 1e-30)), NEG_INF
    ).squeeze(-1)
    return o, lse


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------
def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    mask_fn: Optional[MaskFn] = None,
    q_offset=0,
    k_offset=0,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    return_residuals: bool = False,
    force: Optional[str] = None,
    layout: str = "bthd",
    allow_fused: bool = True,
    window: Optional[int] = None,
):
    """Flash attention over ``q:[B,Tq,H,D] k,v:[B,Tk,Hkv,D]`` (or the
    kernel-native ``[B,H,T,D]`` with ``layout="bhtd"`` — no relayout
    transposes; the model's QKV einsums emit this directly).

    ``q_offset``/``k_offset`` are global position offsets (scalars, may be
    traced) so a caller holding one ring hop's KV block can evaluate the
    correct causal/custom mask. ``return_residuals`` adds the f32
    logsumexp ``[B,H,Tq]``, letting callers merge partial attention
    results across devices (online-softmax merge in ring attention).

    ``window`` (with ``causal``, without ``mask_fn``): a query sees
    itself and the ``window - 1`` keys before it, exactly, for any
    length, window and block. Where the streaming kernels' triangle path
    holds (static equal offsets, square blocks over one sequence) the
    forward and both backwards walk only the band of blocks the window
    can see, ``i - wb <= j <= i`` with ``wb = ceil((window - 1) /
    block)``, masking the block on the diagonal and the blocks its far
    edge crosses; everywhere else the window is a mask over what the
    call walks. A window no query can see past is no window.

    ``force``: ``None`` auto-picks (pallas on TPU, jnp elsewhere),
    ``"pallas"``/``"reference"`` override.

    The differentiable pallas path requires static int offsets; for
    traced offsets or ``return_residuals`` gradients, compose
    ``flash_attention_fwd``/``flash_attention_bwd`` directly (see ring
    attention).
    """
    window = _checked_window(
        q, layout, causal, mask_fn, q_offset, k_offset, window
    )
    mode = force
    if mode is None:
        mode = "pallas" if jax.default_backend() == "tpu" else "reference"
    if mode == "reference":
        # one reference call site: bhtd just transposes around it
        if layout == "bhtd":
            q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
        r = flash_attention_reference(
            q,
            k,
            v,
            causal=causal,
            sm_scale=sm_scale,
            mask_fn=mask_fn,
            q_offset=q_offset,
            k_offset=k_offset,
            return_residuals=return_residuals,
            window=window,
        )
        if layout != "bhtd":
            return r
        if return_residuals:
            return r[0].transpose(0, 2, 1, 3), r[1]
        return r.transpose(0, 2, 1, 3)

    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    try:
        bq, bk = _call_blocks(
            q, k, block_q, block_k, layout, causal, mask_fn, q_offset,
            k_offset,
        )
    except ValueError:
        if force is not None:
            raise
        seq_axis = 2 if layout == "bhtd" else 1
        if (
            allow_fused
            and q.shape[seq_axis] % 8 == 0
            and k.shape[seq_axis] % 8 == 0
            and _fused_eligible(q.shape, k.shape, layout)
            # the differentiable pallas path below needs static offsets;
            # traced-offset callers keep the jnp fallback (the raw-fwd
            # return_residuals path handles traced offsets fine)
            and (
                return_residuals
                or (
                    isinstance(q_offset, int)
                    and isinstance(k_offset, int)
                )
            )
        ):
            # block tiling is a STREAMING-kernel constraint; fused-kernel
            # shapes (T<=_FUSED_MAX_T, e.g. T=520) have none beyond
            # 8-alignment, so they stay on the Pallas path. The block
            # sizes are unused there but must be valid.
            bq = bk = 8
        else:
            # odd sequence length: the jnp path has no tiling constraint
            return flash_attention(
                q,
                k,
                v,
                causal=causal,
                sm_scale=scale,
                mask_fn=mask_fn,
                q_offset=q_offset,
                k_offset=k_offset,
                return_residuals=return_residuals,
                force="reference",
                layout=layout,
                window=window,
            )
    if return_residuals:
        # raw forward — callers own the VJP (e.g. the ring merge)
        return flash_attention_fwd(
            q,
            k,
            v,
            causal=causal,
            sm_scale=scale,
            mask_fn=mask_fn,
            q_offset=q_offset,
            k_offset=k_offset,
            block_q=bq,
            block_k=bk,
            layout=layout,
            allow_fused=allow_fused,
            window=window,
        )
    if not isinstance(q_offset, int) or not isinstance(k_offset, int):
        raise ValueError(
            "the differentiable pallas path needs static int offsets; "
            "use flash_attention_fwd/_bwd for traced offsets"
        )
    trace_counts.count("attn_kept_sites", trace_counts.keeping())
    return _flash_pallas(
        q, k, v, (q_offset, k_offset), causal, mask_fn, scale, bq, bk,
        layout, allow_fused, window
    )
