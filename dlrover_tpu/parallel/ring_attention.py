"""Ring attention: context parallelism over the ``sp`` mesh axis.

Parity: atorch ``DistributedSelfAttention``/``DistributedSoftmax``
(modules/distributed_transformer/distributed_attention.py:21,79) — the
reference shards KV over a sequence group, all-gathers micro-q chunks,
computes a cross-rank-stable softmax and reduce-scatters the context,
overlapping comm and compute on two CUDA streams.

The TPU-native design is a **ring**: every device keeps its own Q block
and passes KV blocks around the ``sp`` axis with ``lax.ppermute`` (one
ICI hop per step — no all-gather footprint), accumulating flash-attention
style online softmax in fp32. XLA overlaps the ``ppermute`` with the
block matmuls, which is the same comm/compute overlap the reference
hand-schedules with streams. Blockwise = native: each (q_block, kv_block)
product is one MXU-friendly matmul.

Used via ``shard_map`` with Q/K/V sharded [batch→(dp,fsdp), seq→sp,
heads→tp]; causal masking uses global positions so the result is exactly
single-device attention.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

MaskFn = Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]


def _block_attn(q, k, v, mask, sm_scale):
    """One (q_block, kv_block) flash step; returns (scores_exp@v, rowmax,
    rowsum) in fp32. q:[B,Tq,H,D] k,v:[B,Tk,H,D] mask:[Tq,Tk] bool."""
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    )
    s = s * sm_scale
    s = jnp.where(mask[None, None, :, :], s, -jnp.inf)
    m = jnp.max(s, axis=-1)  # [B,H,Tq]
    # rows with no visible keys: keep m finite so exp() stays 0, not NaN
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])  # [B,H,Tq,Tk]
    l = jnp.sum(p, axis=-1)  # [B,H,Tq]
    o = jnp.einsum(
        "bhqk,bkhd->bqhd", p, v.astype(jnp.float32)
    )  # fp32 accum
    return o, m_safe, l, jnp.isfinite(m)


def ring_attention_local(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis_name: str = "sp",
    causal: bool = True,
    sm_scale: Optional[float] = None,
    mask_fn: Optional[MaskFn] = None,
):
    """Per-device body (call inside ``shard_map``).

    q/k/v: [B, T_local, H, D] — this device's sequence block. GQA is
    supported (H_kv may divide H). ``mask_fn(q_pos, k_pos)`` overrides the
    causal rule for custom masks (GLM-style, parity:
    modules/transformer/layers.py custom-mask kernels).
    """
    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    B, T, H, D = q.shape
    Hkv = k.shape[2]
    if Hkv != H:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = sm_scale if sm_scale is not None else 1.0 / (D**0.5)

    q_pos = my_idx * T + jnp.arange(T)

    def step(carry, j):
        o_acc, m_acc, l_acc, kv = carry
        k_blk, v_blk = kv
        blk_idx = (my_idx - j) % n
        k_pos = blk_idx * T + jnp.arange(T)
        if mask_fn is not None:
            mask = mask_fn(q_pos, k_pos)
        elif causal:
            mask = q_pos[:, None] >= k_pos[None, :]
        else:
            mask = jnp.ones((T, T), dtype=bool)
        o, m, l, any_visible = _block_attn(q, k_blk, v_blk, mask, scale)
        # online-softmax merge of (o_acc,m_acc,l_acc) with (o,m,l)
        m_new = jnp.maximum(m_acc, jnp.where(any_visible, m, m_acc))
        alpha = jnp.exp(m_acc - m_new)  # rescale old
        beta = jnp.where(any_visible, jnp.exp(m - m_new), 0.0)
        l_new = l_acc * alpha + l * beta
        o_new = (
            o_acc * alpha.transpose(0, 2, 1)[..., None]
            + o * beta.transpose(0, 2, 1)[..., None]
        )
        # rotate KV one hop around the ring (overlapped by XLA with the
        # next block's matmuls)
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_nxt = lax.ppermute(k_blk, axis_name, perm)
        v_nxt = lax.ppermute(v_blk, axis_name, perm)
        return (o_new, m_new, l_new, (k_nxt, v_nxt)), None

    o0 = jnp.zeros((B, T, H, D), jnp.float32)
    # start from a very negative (but finite) running max so the first
    # merge is exact and alpha=exp(m_acc - m_new) never produces NaN
    m0 = jnp.full((B, H, T), jnp.finfo(jnp.float32).min)
    l0 = jnp.zeros((B, H, T), jnp.float32)
    (o, m, l, _), _ = lax.scan(
        step, (o0, m0, l0, (k, v)), jnp.arange(n)
    )
    out = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# kernel-backed ring: flash-attention Pallas kernel per KV hop
# ---------------------------------------------------------------------------
def _merge_partials(o_a, lse_a, o_b, lse_b):
    """Online-softmax merge — the shared helper in ops.flash_attention
    (one algebra for ring hops AND chunked single-device attention)."""
    from dlrover_tpu.ops.flash_attention import merge_partials

    return merge_partials(o_a, lse_a, o_b, lse_b)


def _ring_fwd_scan(q, k, v, axis_name, causal, sm_scale, mask_fn):
    from dlrover_tpu.ops.flash_attention import NEG_INF, flash_attention_fwd

    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    B, T, H, D = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, j):
        o_acc, lse_acc, kv = carry
        k_blk, v_blk = kv
        blk_idx = (my_idx - j) % n
        o_j, lse_j = flash_attention_fwd(
            q,
            k_blk,
            v_blk,
            causal=causal,
            sm_scale=sm_scale,
            mask_fn=mask_fn,
            q_offset=my_idx * T,
            k_offset=blk_idx * T,
        )
        o_new, lse_new = _merge_partials(
            o_acc, lse_acc, o_j.astype(jnp.float32), lse_j
        )
        k_nxt = lax.ppermute(k_blk, axis_name, perm)
        v_nxt = lax.ppermute(v_blk, axis_name, perm)
        return (o_new, lse_new, (k_nxt, v_nxt)), None

    o0 = jnp.zeros((B, T, H, D), jnp.float32)
    lse0 = jnp.full((B, H, T), NEG_INF, jnp.float32)
    (o, lse, _), _ = lax.scan(step, (o0, lse0, (k, v)), jnp.arange(n))
    return o.astype(q.dtype), lse


def _make_ring_flash(axis_name, causal, sm_scale, mask_fn):
    """Build the custom-vjp kernel ring for one static config.

    Forward: one flash kernel call per KV hop, partials merged with the
    online-softmax rule. Backward: a second ring pass — ``dq``
    accumulates locally; ``dk``/``dv`` partials travel *with* their KV
    block (rotated by the same ppermute), so after n hops each device
    holds the complete gradient of its own KV shard. The kernel's
    ``p = exp(s - lse_global)`` recomputation makes every per-hop
    contribution exact.
    """
    from dlrover_tpu.ops.flash_attention import flash_attention_bwd

    @jax.custom_vjp
    def ring_flash(q, k, v):
        o, _ = _ring_fwd_scan(
            q, k, v, axis_name, causal, sm_scale, mask_fn
        )
        return o

    def fwd(q, k, v):
        o, lse = _ring_fwd_scan(
            q, k, v, axis_name, causal, sm_scale, mask_fn
        )
        return o, (q, k, v, o, lse)

    def bwd(res, do):
        q, k, v, o, lse = res
        n = lax.psum(1, axis_name)
        my_idx = lax.axis_index(axis_name)
        T = q.shape[1]
        perm = [(i, (i + 1) % n) for i in range(n)]

        def step(carry, j):
            dq_acc, kv, dkv = carry
            k_blk, v_blk = kv
            dk_acc, dv_acc = dkv
            blk_idx = (my_idx - j) % n
            dq_j, dk_j, dv_j = flash_attention_bwd(
                q,
                k_blk,
                v_blk,
                o,
                lse,
                do,
                causal=causal,
                sm_scale=sm_scale,
                mask_fn=mask_fn,
                q_offset=my_idx * T,
                k_offset=blk_idx * T,
            )
            dq_acc = dq_acc + dq_j.astype(jnp.float32)
            dk_acc = dk_acc + dk_j.astype(jnp.float32)
            dv_acc = dv_acc + dv_j.astype(jnp.float32)
            # dk/dv ride along with their kv block around the ring
            k_nxt = lax.ppermute(k_blk, axis_name, perm)
            v_nxt = lax.ppermute(v_blk, axis_name, perm)
            dk_nxt = lax.ppermute(dk_acc, axis_name, perm)
            dv_nxt = lax.ppermute(dv_acc, axis_name, perm)
            return (dq_acc, (k_nxt, v_nxt), (dk_nxt, dv_nxt)), None

        dq0 = jnp.zeros(q.shape, jnp.float32)
        dkv0 = (
            jnp.zeros(k.shape, jnp.float32),
            jnp.zeros(v.shape, jnp.float32),
        )
        (dq, _, (dk, dv)), _ = lax.scan(
            step, (dq0, (k, v), dkv0), jnp.arange(n)
        )
        return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)

    ring_flash.defvjp(fwd, bwd)
    return ring_flash


def ring_flash_attention_local(
    q,
    k,
    v,
    *,
    axis_name: str = "sp",
    causal: bool = True,
    sm_scale: Optional[float] = None,
    mask_fn: Optional[MaskFn] = None,
):
    """Kernel-backed per-device ring body (call inside ``shard_map``).

    Same contract as ``ring_attention_local`` but each hop's block math
    runs in the Pallas flash-attention kernel (ops/flash_attention.py);
    GQA KV stays unexpanded all the way through the ring (H_kv heads on
    the wire instead of H).
    """
    # built per call: the custom_vjp wrapper is cheap to construct, and
    # callers jit the enclosing step, so trace caching happens above us
    # (an identity-keyed cache here would leak mask_fn closures)
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    fn = _make_ring_flash(axis_name, causal, scale, mask_fn)
    return fn(q, k, v)


def ring_self_attention(
    q,
    k,
    v,
    mesh,
    *,
    causal: bool = True,
    mask_fn: Optional[MaskFn] = None,
    use_kernel: Optional[bool] = None,
):
    """Global-view wrapper: shards [B,S,H,D] over the mesh and runs the
    ring. Inputs may be any layout; outputs match q's sharding.

    ``use_kernel=None`` auto-picks the Pallas-kernel ring on TPU and the
    jnp ring elsewhere (kernels run under the slow interpreter off-TPU).
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    spec = P(("dp", "fsdp"), "sp", "tp", None)
    if use_kernel:
        fn = functools.partial(
            ring_flash_attention_local, causal=causal, mask_fn=mask_fn
        )
    else:
        fn = functools.partial(
            ring_attention_local, causal=causal, mask_fn=mask_fn
        )
    return shard_map(
        fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)
