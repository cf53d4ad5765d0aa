"""Mixture-of-Experts: one routing decision, two ways to move tokens.

Parity: atorch ``MOELayer``/``_AllToAll``/top-k gating
(modules/moe/moe_layer.py:87,116,161; switch_gating.py:154).

``route`` is the one implementation of the decision: router logits,
softmax, top-k, gate values and both auxiliary losses, all in float32.
What moves the tokens depends on where the experts live:

- On one device, or wherever every expert is local (``axis_name=None``
  or an ``ep`` axis of size 1), the k*T assignments are sorted by
  expert, the tokens gathered in that order, and each expert's
  projections run as one grouped matmul over the ragged groups
  (``lax.ragged_dot``). There is no bucket, so no token is dropped at
  any imbalance and ``capacity_factor`` has no effect.
- Over an ``ep`` axis of more than one device, tokens are packed into
  per-expert capacity buckets (static shapes for ``lax.all_to_all``
  inside ``shard_map``, a single fused ICI collective, differentiable
  through JAX's AD), expert FFNs are one batched einsum over the local
  experts, and a second all-to-all brings expert outputs home.
  Assignments beyond an expert's capacity are dropped there: the token
  keeps its other experts' outputs and the residual path.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


class MoEParams(NamedTuple):
    """Per-host expert weights: [E_local, ...]. Gate is replicated.
    ``w_gate`` is there for gated (SwiGLU) experts and None for the
    activation pair ``w_up`` / ``w_down``."""

    gate: jnp.ndarray  # [model, E_global]
    w_up: jnp.ndarray  # [E_local, model, hidden]
    w_down: jnp.ndarray  # [E_local, hidden, model]
    w_gate: Optional[jnp.ndarray] = None  # [E_local, model, hidden]


def init_moe_params(
    key, num_experts: int, model_dim: int, hidden_dim: int,
    dtype=jnp.float32, gated: bool = False,
) -> MoEParams:
    kg, ku, kd, kw = jax.random.split(key, 4)
    scale = model_dim**-0.5

    def up(k):
        return jax.random.normal(
            k, (num_experts, model_dim, hidden_dim), dtype
        ) * scale

    return MoEParams(
        gate=jax.random.normal(kg, (model_dim, num_experts), dtype) * scale,
        w_up=up(ku),
        w_down=jax.random.normal(
            kd, (num_experts, hidden_dim, model_dim), dtype
        )
        * (hidden_dim**-0.5),
        w_gate=up(kw) if gated else None,
    )


def route(logits: jnp.ndarray, k: int, normalize: bool):
    """THE routing decision, in float32: each token's ``k`` best
    experts by softmax probability, their gate values, and the two
    auxiliary losses. Shared by the one-device and the ``ep`` path.

    Returns ``(idx [T,k] int32, gates [T,k] f32, aux)``:
    - gates: the softmax probabilities of the chosen experts,
      renormalised to sum to one only where ``normalize`` says so
      (``TransformerConfig.norm_topk_prob``);
    - aux["balance"]: E * sum_i f_i * P_i, with f_i the share of all
      k*T assignments that went to expert i and P_i the mean router
      probability of expert i (the Switch loss over every assignment,
      as OLMoE trains with);
    - aux["z"]: mean(logsumexp(logits)^2), the ST-MoE router z-loss;
    - aux["load"]: f, the [E] vector; aux["counts"]: assignments per
      expert as int32.
    """
    T, num_experts = logits.shape
    logits = logits.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    vals, idx = lax.top_k(probs, k)
    gates = (
        vals / (jnp.sum(vals, axis=-1, keepdims=True) + 1e-9)
        if normalize and k > 1
        else vals
    )
    counts = jnp.sum(
        idx[..., None] == jnp.arange(num_experts, dtype=idx.dtype),
        axis=(0, 1),
        dtype=jnp.int32,
    )
    load = counts.astype(jnp.float32) / float(k * T)
    aux = {
        "balance": num_experts * jnp.sum(load * jnp.mean(probs, axis=0)),
        "z": jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2),
        "load": load,
        "counts": counts,
    }
    return idx, gates, aux


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
    """``route`` packed into capacity buckets, for the ``ep`` path
    (parity: switch_gating.py:154's top-k path / GShard top-2): rank-0
    assignments take capacity priority over rank-1 (the GShard rule —
    a token's secondary expert must not evict another token's primary).

    Returns (dispatch [T,E,C], combine [T,E,C], balance_aux, z_loss),
    the two losses as ``route`` defines them.

    ``expert_caps`` ([E] ints <= ``capacity``): per-expert capacity
    re-split (ISSUE 13) — ``capacity`` stays the static bucket dim C,
    but expert e only KEEPS its first ``expert_caps[e]`` assignments;
    hot experts use the full bucket while cold ones ship padding.
    ``return_stats=True`` appends ``{"load": [E] share of the k*T
    assignments, "drop": scalar fraction of (token, slot) assignments
    dropped by capacity}`` — the telemetry ``CapacityRebalancer``
    feeds on.
    """
    T = logits.shape[0]
    idx, gates, aux = route(logits, k, normalize)
    onehots = jax.nn.one_hot(idx, num_experts, dtype=jnp.float32)  # [T,k,E]

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
        dtype=jnp.float32,
    )  # [T, k, C]
    routed = onehots[..., None] * pos_oh[:, :, None, :]  # [T,k,E,C]
    dispatch = jnp.sum(routed, axis=1)  # experts are distinct per token
    combine = jnp.sum(routed * gate_val[..., None, None], axis=1)

    if return_stats:
        stats = {
            "load": aux["load"],
            "drop": 1.0
            - jnp.sum(keep.astype(jnp.float32)) / float(k * T),
        }
        return dispatch, combine, aux["balance"], aux["z"], stats
    return dispatch, combine, aux["balance"], aux["z"]


@jax.custom_vjp
def _permute_rows(x, perm, inv):
    """``x[perm]`` for a permutation ``perm`` with inverse ``inv``. Its
    cotangent is the gather ``dy[inv]``: the scatter-add that AD would
    write for a gather serialises on the TPU, and a permutation needs
    none."""
    return x[perm]


def _permute_rows_fwd(x, perm, inv):
    return x[perm], (perm, inv)


def _permute_rows_bwd(res, dy):
    perm, inv = res
    return dy[inv], None, None


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


def _expert_ffn(params: MoEParams, matmul, x, activation):
    """One expert FFN over whatever ``matmul(x, w)`` batches: SwiGLU
    where the experts are gated, the activation pair where not."""
    dt = x.dtype
    h = matmul(x, params.w_up.astype(dt))
    if params.w_gate is not None:
        h = jax.nn.silu(matmul(x, params.w_gate.astype(dt))) * h
    else:
        h = activation(h)
    return matmul(h, params.w_down.astype(dt))


def _moe_dropless(params: MoEParams, x, idx, gates, counts, activation):
    """Every expert local: sort the k*T assignments by expert, gather
    the tokens in that order, run each projection as one grouped matmul
    over the E ragged groups, weight by the gates and sum each token's
    k rows home. No bucket, so nothing is ever dropped."""
    T, model = x.shape
    k = idx.shape[1]
    with jax.named_scope("scope/layer/moe/dispatch"):
        # token-major [T*k]: assignment j is token j // k
        order = jnp.argsort(idx.reshape(T * k), stable=True)
        inv = jnp.argsort(order)
        xs = _permute_rows(jnp.repeat(x, k, axis=0), order, inv)
    with jax.named_scope("scope/layer/moe/experts"):
        ys = _expert_ffn(
            params,
            lambda a, w: lax.ragged_dot(a, w, counts),
            xs,
            activation,
        )
    with jax.named_scope("scope/layer/moe/combine"):
        home = _permute_rows(ys, inv, order).reshape(T, k, model)
        out = jnp.sum(
            home.astype(jnp.float32) * gates[..., None], axis=1
        )
    return out.astype(x.dtype)


def moe_layer_local(
    params: MoEParams,
    x: jnp.ndarray,
    *,
    axis_name: str = "ep",
    capacity_factor: float = 1.25,
    activation=jax.nn.gelu,
    top_k: int = 1,
    expert_caps: Optional[Tuple[int, ...]] = None,
    normalize: bool = True,
):
    """Per-device MoE FFN body (call inside ``shard_map``).

    x: [tokens_local, model]. Experts are sharded over ``axis_name``:
    device i holds experts [i*E_local, (i+1)*E_local). Where that is
    one device (``axis_name=None`` or an axis of size 1) the layer is
    dropless (``_moe_dropless``) and ``capacity_factor`` and
    ``expert_caps`` have no effect.

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

    with jax.named_scope("scope/layer/moe/route"):
        logits = jnp.dot(
            x.astype(jnp.float32),
            params.gate.astype(jnp.float32),
            precision=lax.Precision.HIGHEST,
        )  # [T, E_global]
        if ep == 1:
            idx, gates, aux = route(logits, top_k, normalize)
    if ep == 1:
        counts = aux.pop("counts")
        out = _moe_dropless(params, x, idx, gates, counts, activation)
        aux["drop"] = jnp.float32(0.0)
        return out, aux

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

    with jax.named_scope("scope/layer/moe/route"):
        dispatch, combine, balance, z, stats = topk_gating(
            logits, e_global, capacity, k=top_k, normalize=normalize,
            expert_caps=caps_arr, return_stats=True,
        )
    aux = {
        "balance": balance,
        "z": z,
        "load": stats["load"],
        "drop": stats["drop"],
    }

    with jax.named_scope("scope/layer/moe/dispatch"):
        # bucket tokens: [E_global, C, model]; global expert id is
        # (owner_device, local_expert) row-major
        expert_in = jnp.einsum("tec,tm->ecm", dispatch.astype(x.dtype), x)
        # dispatch all-to-all: send each owner its experts' buckets;
        # receive [ep(source), E_local, C, model]
        expert_in = expert_in.reshape(ep, e_local, capacity, model)
        expert_in = lax.all_to_all(
            expert_in, axis_name, split_axis=0, concat_axis=0, tiled=False
        )
        expert_in = expert_in.transpose(1, 0, 2, 3).reshape(
            e_local, ep * capacity, model
        )

    with jax.named_scope("scope/layer/moe/experts"):
        # batched expert FFN: one einsum per projection over the local
        # experts (MXU)
        expert_out = _expert_ffn(
            params,
            lambda a, w: jnp.einsum("eck,ekn->ecn", a, w),
            expert_in,
            activation,
        )

    with jax.named_scope("scope/layer/moe/combine"):
        # return all-to-all: route each source device's results home,
        # then regroup as [E_global, C, model]
        expert_out = expert_out.reshape(e_local, ep, capacity, model)
        expert_out = expert_out.transpose(1, 0, 2, 3)  # [ep(dest), ...]
        expert_out = lax.all_to_all(
            expert_out, axis_name, split_axis=0, concat_axis=0, tiled=False
        )  # [ep(owner), E_local, C, model]
        expert_out = expert_out.reshape(e_global, capacity, model)
        out = jnp.einsum(
            "tec,ecm->tm", combine, expert_out.astype(jnp.float32)
        )
    return out.astype(x.dtype), aux


def moe_layer(params: MoEParams, x, mesh, **kw):
    """Global wrapper: x [B, S, model] sharded (batch→(dp,fsdp), seq→sp);
    expert weights sharded over ep on their first axis."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    xspec = P(("dp", "fsdp"), "sp", None)
    expert = P("ep", None, None)
    pspec = MoEParams(
        gate=P(None, None), w_up=expert, w_down=expert,
        w_gate=None if params.w_gate is None else expert,
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


def fold_routing_report(metrics, stats) -> None:
    """Fold a reported step's own ``moe_drop_rate`` / ``moe_expert_load``
    into ``PipelineStats.moe_*``; nothing for a dense model. Copies to
    the host of a step already waited for: an op on the device here
    would queue behind the step in flight."""
    if "moe_drop_rate" not in metrics:
        return
    import numpy as np

    drop = np.asarray(metrics["moe_drop_rate"])
    load = np.asarray(metrics["moe_expert_load"])
    stats.moe_reports += 1
    stats.moe_drop_rate_sum += float(drop)
    stats.moe_max_load_sum += float(load.max()) * load.size


# -- capacity rebalancing (ISSUE 13) ----------------------------------------


class CapacityRebalancer:
    """Per-expert capacity re-split from measured routing load.

    The static ``capacity_factor`` sizes every expert's bucket for the
    UNIFORM-routing fiction; real routers skew, so hot experts drop
    tokens (capacity overflow) while cold experts ship padding. This
    tracker EMAs the per-expert share of the assignments (the ``load``
    gating stat) and periodically re-splits the same total slot budget
    proportionally: ``splits()`` returns static per-expert capacities
    (``TransformerConfig.capacity_splits``) the gating enforces via
    its per-expert cutoffs. The bucket dim becomes ``max(caps)`` —
    cold experts ship padding in the all-to-all — so wire/compute cost
    rises by at most ``boost``x while overflow drops fall (the bench's
    ``mesh_matrix_ep_drop_*`` gate).

    Host-side and deliberately tiny: observe() is fed from the train
    metrics (``moe_expert_load``), splits() is consulted at a
    recompile boundary of the caller's choosing — capacities are STATIC
    shapes, so a re-split costs one step rebuild.
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
        """Fold one per-expert assignment-share vector (the
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
