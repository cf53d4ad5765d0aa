"""Ulysses-style all-to-all sequence parallelism.

Parity: the reference's long-context story has two schemes — ring P2P
(atorch ring attention; ours in ``parallel/ring_attention.py``) and
DeepSpeed-Ulysses all-to-all context parallelism (the
sequence-parallel path its DS integration exposes). The all-to-all
scheme trades the ring's P-step pipeline for two fused collectives:

1. activations arrive sequence-sharded ``[B, S/sp, H, D]``;
2. one ``all_to_all`` re-shards them head-wise ``[B, S, H/sp, D]`` —
   every device then holds the FULL sequence for its head slice, so
   flash attention runs with no communication inside (the same Pallas
   kernel the ring uses: O(S·block) memory, masked-row-safe, GQA);
3. a second ``all_to_all`` brings outputs home to ``[B, S/sp, H, D]``.

When it wins: attention cost per device is identical to the ring's
total, but communication is two dense all-to-alls on ICI instead of
2(P-1) ppermute hops — fewer, larger transfers that overlap worse but
latency-bound shapes (moderate S, many heads) prefer. Constraint: sp
must divide the LOCAL head count — (num_heads / tp) % sp == 0 when tp
also shards heads (the ring only needs sp to divide S) — which is why
both schemes ship: pick per config, not per code change. GQA kv heads
ride the wire UNEXPANDED when sp divides them (H/Hkv× less kv
all-to-all traffic); otherwise they are repeated first.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
from jax import lax

from dlrover_tpu.ops.flash_attention import flash_attention
from dlrover_tpu.parallel.ring_attention import MaskFn


def ulysses_attention_local(
    q,
    k,
    v,
    *,
    axis_name: str = "sp",
    causal: bool = True,
    mask_fn: Optional[MaskFn] = None,
    use_kernel: Optional[bool] = None,
):
    """Per-device body (call inside ``shard_map`` manual over ``sp``):
    q/k/v [B, S_local, H, D] sequence-sharded → output in the same
    layout. The inner attention is ``ops.flash_attention`` (Pallas on
    TPU, reference elsewhere), which owns GQA head mapping and the
    fully-masked-row guard — identical numerics to the ring scheme."""
    sp = lax.psum(1, axis_name)
    H, Hkv = q.shape[2], k.shape[2]
    if H % sp:
        raise ValueError(
            f"ulysses needs sp={sp} to divide the local head count "
            f"{H}; use the ring scheme for this config"
        )
    if Hkv % sp:
        # fallback only: sp does not divide the kv heads, so expand
        # them pre-wire (costs H/Hkv x the kv all-to-all bytes)
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)

    def seq_to_heads(x):
        # [B, S/sp, h, D] -> [B, S, h/sp, D]: split the head axis
        # across devices, concatenate the sequence axis
        return lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    def heads_to_seq(x):
        return lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    force = None
    if use_kernel is not None:
        force = "pallas" if use_kernel else "reference"
    # contract adapter: this module (like the ring) hands mask_fn 1-D
    # position vectors; the kernel passes pre-broadcast [bq,1]/[1,bk]
    kernel_mask = (
        (lambda qp, kp: mask_fn(qp.reshape(-1), kp.reshape(-1)))
        if mask_fn is not None
        else None
    )
    out = flash_attention(
        seq_to_heads(q),
        seq_to_heads(k),
        seq_to_heads(v),
        causal=causal,
        mask_fn=kernel_mask,
        force=force,
    )
    return heads_to_seq(out)


def ulysses_self_attention(
    q,
    k,
    v,
    mesh,
    *,
    causal: bool = True,
    mask_fn: Optional[MaskFn] = None,
    use_kernel: Optional[bool] = None,
):
    """Global-view wrapper, layout-compatible with
    ``ring_self_attention``: shards [B,S,H,D] over the mesh
    (batch→(dp,fsdp), seq→sp, heads→tp) and runs the two-collective
    schedule."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P(("dp", "fsdp"), "sp", "tp", None)

    def fn(q_, k_, v_):
        return ulysses_attention_local(
            q_, k_, v_, causal=causal, mask_fn=mask_fn,
            use_kernel=use_kernel,
        )

    return shard_map(
        fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)
