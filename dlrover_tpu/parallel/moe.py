"""Mixture-of-Experts with expert parallelism over the ``ep`` mesh axis.

Parity: atorch ``MOELayer``/``_AllToAll``/top-k gating
(modules/moe/moe_layer.py:87,116,161; switch_gating.py:154) — the
reference dispatches tokens to experts with an explicit NCCL all-to-all
autograd function and a capacity-bucketed einsum combine.

TPU-native: gating + capacity bucketing are the same math, but the
dispatch is ``lax.all_to_all`` over the ``ep`` axis inside ``shard_map``
(single fused ICI collective, differentiable through JAX's AD), expert
FFNs are one batched einsum over the local experts (MXU-friendly), and a
second all-to-all brings expert outputs home. Static shapes via
capacity_factor keep everything jit-compatible (dropped tokens fall back
to the residual path, exactly like capacity-dropped tokens in the
reference).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


class MoEParams(NamedTuple):
    """Per-host expert weights: [E_local, ...]. Gate is replicated."""

    gate: jnp.ndarray  # [model, E_global]
    w_up: jnp.ndarray  # [E_local, model, hidden]
    w_down: jnp.ndarray  # [E_local, hidden, model]


def init_moe_params(
    key, num_experts: int, model_dim: int, hidden_dim: int, dtype=jnp.float32
) -> MoEParams:
    kg, ku, kd = jax.random.split(key, 3)
    scale = model_dim**-0.5
    return MoEParams(
        gate=jax.random.normal(kg, (model_dim, num_experts), dtype) * scale,
        w_up=jax.random.normal(
            ku, (num_experts, model_dim, hidden_dim), dtype
        )
        * scale,
        w_down=jax.random.normal(
            kd, (num_experts, hidden_dim, model_dim), dtype
        )
        * (hidden_dim**-0.5),
    )


def top1_gating(
    logits: jnp.ndarray, num_experts: int, capacity: int
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Switch-style top-1 gating (parity: switch_gating.py:154) —
    ``topk_gating`` with k=1 (ONE routing implementation to maintain),
    minus the z-loss for the legacy 3-tuple signature."""
    dispatch, combine, balance, _ = topk_gating(
        logits, num_experts, capacity, k=1
    )
    return dispatch, combine, balance


def topk_gating(
    logits: jnp.ndarray,
    num_experts: int,
    capacity: int,
    k: int = 2,
    normalize: bool = True,
    expert_caps: Optional[jnp.ndarray] = None,
    return_stats: bool = False,
):
    """Top-k gating (parity: switch_gating.py:154's top-k path /
    GShard top-2): each token is routed to its k best experts, with
    rank-0 assignments taking capacity priority over rank-1 (the GShard
    rule — a token's secondary expert must not evict another token's
    primary).

    Returns (dispatch [T,E,C], combine [T,E,C], balance_aux, z_loss):
    - balance_aux: Switch load-balance loss over PRIMARY assignments
      (E * sum(density * density_proxy));
    - z_loss: mean(logsumexp(logits)^2) — keeps router logits from
      drifting large (ST-MoE router z-loss), weighted by the caller.

    ``expert_caps`` ([E] ints <= ``capacity``): per-expert capacity
    re-split (ISSUE 13) — ``capacity`` stays the static bucket dim C,
    but expert e only KEEPS its first ``expert_caps[e]`` assignments;
    hot experts use the full bucket while cold ones ship padding.
    ``return_stats=True`` appends ``{"load": [E] primary-routing
    fraction, "drop": scalar fraction of (token, slot) assignments
    dropped by capacity}`` — the telemetry ``CapacityRebalancer``
    feeds on.
    """
    T = logits.shape[0]
    probs = jax.nn.softmax(logits, axis=-1)  # [T, E]
    vals, idx = lax.top_k(probs, k)  # [T, k]
    gates = (
        vals / (jnp.sum(vals, axis=-1, keepdims=True) + 1e-9)
        if normalize and k > 1
        else vals
    )
    onehots = jax.nn.one_hot(idx, num_experts, dtype=logits.dtype)  # [T,k,E]

    # capacity accounting rank-major: all rank-0 rows first, then rank-1
    # continues the same per-expert counters
    flat = onehots.transpose(1, 0, 2).reshape(k * T, num_experts)
    pos_flat = jnp.sum(jnp.cumsum(flat, axis=0) * flat, axis=-1) - 1.0
    pos = pos_flat.reshape(k, T).T  # [T, k]
    if expert_caps is not None:
        caps = jnp.asarray(expert_caps, jnp.float32)
        keep = pos < jnp.take(caps, idx)  # [T, k] per-expert cutoffs
    else:
        keep = pos < capacity
    gate_val = gates * keep
    pos_oh = jax.nn.one_hot(
        jnp.where(keep, pos, capacity).astype(jnp.int32),
        capacity,
        dtype=logits.dtype,
    )  # [T, k, C]
    routed = onehots[..., None] * pos_oh[:, :, None, :]  # [T,k,E,C]
    dispatch = jnp.sum(routed, axis=1)  # experts are distinct per token
    combine = jnp.sum(routed * gate_val[..., None, None], axis=1)

    density = jnp.mean(onehots[:, 0, :], axis=0)  # primary assignment
    density_proxy = jnp.mean(probs, axis=0)
    balance = jnp.sum(density * density_proxy) * num_experts
    z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    if return_stats:
        stats = {
            "load": density,
            "drop": 1.0
            - jnp.sum(keep.astype(jnp.float32)) / float(k * T),
        }
        return dispatch, combine, balance, z, stats
    return dispatch, combine, balance, z


def moe_layer_local(
    params: MoEParams,
    x: jnp.ndarray,
    *,
    axis_name: str = "ep",
    capacity_factor: float = 1.25,
    activation=jax.nn.gelu,
    top_k: int = 1,
    expert_caps: Optional[Tuple[int, ...]] = None,
):
    """Per-device MoE FFN body (call inside ``shard_map``).

    x: [tokens_local, model]. Experts are sharded over ``axis_name``:
    device i holds experts [i*E_local, (i+1)*E_local).

    ``expert_caps`` (static [E_global] ints, ``CapacityRebalancer.
    splits``): per-expert capacity re-split — the bucket dim becomes
    ``max(expert_caps)`` and expert e keeps only its first
    ``expert_caps[e]`` assignments (hot experts stop overflowing,
    cold ones ship padding in the all-to-all).
    """
    ep = 1 if axis_name is None else lax.psum(1, axis_name)
    e_local = params.w_up.shape[0]
    e_global = e_local * ep
    T, model = x.shape
    # top-k routes k slots per token; capacity scales with k so the
    # same capacity_factor keeps the same drop rate
    caps_arr = None
    if expert_caps:
        if len(expert_caps) != e_global:
            raise ValueError(
                f"expert_caps has {len(expert_caps)} entries for "
                f"{e_global} experts"
            )
        capacity = max(1, int(max(expert_caps)))
        caps_arr = jnp.asarray(expert_caps, jnp.float32)
    else:
        capacity = max(1, int(capacity_factor * top_k * T / e_global))

    logits = x @ params.gate  # [T, E_global]
    dispatch, combine, balance, z, stats = topk_gating(
        logits, e_global, capacity, k=top_k,
        expert_caps=caps_arr, return_stats=True,
    )
    aux = {
        "balance": balance,
        "z": z,
        "load": stats["load"],
        "drop": stats["drop"],
    }

    # bucket tokens: [E_global, C, model]; global expert id is
    # (owner_device, local_expert) row-major
    expert_in = jnp.einsum("tec,tm->ecm", dispatch, x)
    # dispatch all-to-all: send each owner its experts' buckets; receive
    # [ep(source), E_local, C, model]
    expert_in = expert_in.reshape(ep, e_local, capacity, model)
    if axis_name is not None:
        expert_in = lax.all_to_all(
            expert_in, axis_name, split_axis=0, concat_axis=0, tiled=False
        )
    expert_in = expert_in.transpose(1, 0, 2, 3).reshape(
        e_local, ep * capacity, model
    )

    # batched expert FFN: one einsum pair over local experts (MXU)
    h = jnp.einsum("ecm,emh->ech", expert_in, params.w_up)
    h = activation(h)
    expert_out = jnp.einsum("ech,ehm->ecm", h, params.w_down)

    # return all-to-all: route each source device's results home, then
    # regroup as [E_global, C, model]
    expert_out = expert_out.reshape(e_local, ep, capacity, model)
    expert_out = expert_out.transpose(1, 0, 2, 3)  # [ep(dest), E_local...]
    if axis_name is not None:
        expert_out = lax.all_to_all(
            expert_out, axis_name, split_axis=0, concat_axis=0, tiled=False
        )  # [ep(owner), E_local, C, model]
    expert_out = expert_out.reshape(e_global, capacity, model)

    out = jnp.einsum("tec,ecm->tm", combine, expert_out)
    return out.astype(x.dtype), aux


def moe_layer(params: MoEParams, x, mesh, **kw):
    """Global wrapper: x [B, S, model] sharded (batch→(dp,fsdp), seq→sp);
    expert weights sharded over ep on their first axis."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    xspec = P(("dp", "fsdp"), "sp", None)
    pspec = MoEParams(
        gate=P(None, None), w_up=P("ep", None, None), w_down=P("ep", None, None)
    )

    def body(p, xb):
        B, S, m = xb.shape
        flat = xb.reshape(B * S, m)
        out, aux = moe_layer_local(p, flat, **kw)
        # gating is per-local-token-group; average the aux losses over
        # every shard so the returned scalars really are replicated
        aux = jax.tree_util.tree_map(
            lambda a: lax.pmean(a, ("dp", "fsdp", "sp", "ep")), aux
        )
        return out.reshape(B, S, m), aux

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(pspec, xspec),
        out_specs=(
            xspec,
            {"balance": P(), "z": P(), "load": P(), "drop": P()},
        ),
        check_vma=False,
    )(params, x)


# -- capacity rebalancing (ISSUE 13) ----------------------------------------


class CapacityRebalancer:
    """Per-expert capacity re-split from measured routing load.

    The static ``capacity_factor`` sizes every expert's bucket for the
    UNIFORM-routing fiction; real routers skew, so hot experts drop
    tokens (capacity overflow) while cold experts ship padding. This
    tracker EMAs the per-expert primary-routing fraction (the ``load``
    gating stat) and periodically re-splits the same total slot budget
    proportionally: ``splits()`` returns static per-expert capacities
    (``TransformerConfig.capacity_splits``) the gating enforces via
    its per-expert cutoffs. The bucket dim becomes ``max(caps)`` —
    cold experts ship padding in the all-to-all — so wire/compute cost
    rises by at most ``boost``x while overflow drops fall (the bench's
    ``mesh_matrix_ep_drop_*`` gate).

    Host-side and deliberately tiny: observe() is fed from the train
    metrics (``moe_expert_load``), splits() is consulted at a
    recompile boundary (the trainer's ``moe_rebalance_interval``) —
    capacities are STATIC shapes, so a re-split costs one step rebuild
    through the AOT cache, amortized over the interval.
    """

    def __init__(
        self,
        num_experts: int,
        capacity_factor: float = 1.25,
        top_k: int = 1,
        ema: float = 0.8,
        boost: float = 2.0,
        floor: float = 0.25,
    ):
        import numpy as np

        if num_experts < 2:
            raise ValueError("rebalancing needs >= 2 experts")
        self.num_experts = num_experts
        self.capacity_factor = float(capacity_factor)
        self.top_k = int(top_k)
        self.ema = float(ema)
        self.boost = float(boost)
        self.floor = float(floor)
        self.load = np.full(num_experts, 1.0 / num_experts)
        self.observations = 0

    def observe(self, load) -> None:
        """Fold one per-expert primary-routing fraction vector (the
        ``load`` gating stat / ``moe_expert_load`` metric) into the
        EMA."""
        import numpy as np

        load = np.asarray(load, dtype=np.float64).reshape(-1)
        if load.shape[0] != self.num_experts:
            raise ValueError(
                f"load has {load.shape[0]} entries for "
                f"{self.num_experts} experts"
            )
        total = float(load.sum())
        if total <= 0:
            return
        load = load / total
        self.load = self.ema * self.load + (1.0 - self.ema) * load
        self.load = self.load / self.load.sum()
        self.observations += 1

    def splits(self, tokens_per_shard: int) -> Tuple[int, ...]:
        """Static per-expert capacities for a shard of
        ``tokens_per_shard`` routed tokens: the uniform budget
        ``E x base`` re-split proportionally to the load EMA, each
        expert clamped to [floor x base, boost x base] (and >= 1)."""
        import numpy as np

        base = max(
            1,
            int(
                self.capacity_factor
                * self.top_k
                * tokens_per_shard
                / self.num_experts
            ),
        )
        total = base * self.num_experts
        raw = self.load * total
        lo = max(1, int(round(self.floor * base)))
        hi = max(lo + 1, int(np.ceil(self.boost * base)))
        caps = np.clip(np.round(raw), lo, hi).astype(int)
        return tuple(int(c) for c in caps)
