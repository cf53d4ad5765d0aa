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
from jax.ad_checkpoint import checkpoint_name

from dlrover_tpu.common import trace_counts


class MoEParams(NamedTuple):
    """Per-host expert weights: [E_local, ...]. Gate is replicated.
    ``w_gate`` is there for gated (SwiGLU) experts and None for the
    activation pair ``w_up`` / ``w_down``. ``bias`` is the sigmoid
    router's selection bias, one an expert (no gradient: the train step
    moves it, ``move_router_bias``); ``shared_up`` / ``shared_down`` the
    one expert every token passes beside its routed ones, SwiGLU with
    ``shared_gate`` where the experts are gated, its output times
    ``sigmoid(x . shared_out_gate)`` where it has that vector. On a chip
    that holds a share of the experts ``E_local`` is the number held."""

    gate: jnp.ndarray  # [model, E_global]
    w_up: jnp.ndarray  # [E_local, model, hidden]
    w_down: jnp.ndarray  # [E_local, hidden, model]
    w_gate: Optional[jnp.ndarray] = None  # [E_local, model, hidden]
    bias: Optional[jnp.ndarray] = None  # [E_global]
    shared_up: Optional[jnp.ndarray] = None  # [model, shared]
    shared_down: Optional[jnp.ndarray] = None  # [shared, model]
    shared_gate: Optional[jnp.ndarray] = None  # [model, shared]
    shared_out_gate: Optional[jnp.ndarray] = None  # [model]


def init_moe_params(
    key, num_experts: int, model_dim: int, hidden_dim: int,
    dtype=jnp.float32, gated: bool = False, held: int = 0,
    selection_bias: bool = False, shared_dim: int = 0,
    shared_out_gate: bool = False,
) -> MoEParams:
    """``held`` (0 = all): how many of the ``num_experts`` the router
    scores have their matrices here. The shared expert is gated (SwiGLU)
    where the routed ones are."""
    kg, ku, kd, kw = jax.random.split(key, 4)
    scale = model_dim**-0.5
    e_local = held or num_experts

    def up(k):
        return jax.random.normal(
            k, (e_local, model_dim, hidden_dim), dtype
        ) * scale

    shared = {}
    if shared_dim:
        ks, kt = jax.random.split(jax.random.fold_in(key, 1))
        shared = dict(
            shared_up=jax.random.normal(
                ks, (model_dim, shared_dim), dtype
            ) * scale,
            shared_down=jax.random.normal(
                kt, (shared_dim, model_dim), dtype
            ) * (shared_dim**-0.5),
        )
        kg2, ko = jax.random.split(jax.random.fold_in(key, 2))
        if gated:
            shared["shared_gate"] = jax.random.normal(
                kg2, (model_dim, shared_dim), dtype
            ) * scale
        if shared_out_gate:
            shared["shared_out_gate"] = jax.random.normal(
                ko, (model_dim,), dtype
            ) * scale
    return MoEParams(
        gate=jax.random.normal(kg, (model_dim, num_experts), dtype) * scale,
        w_up=up(ku),
        w_down=jax.random.normal(
            kd, (e_local, hidden_dim, model_dim), dtype
        )
        * (hidden_dim**-0.5),
        w_gate=up(kw) if gated else None,
        bias=jnp.zeros((num_experts,), dtype) if selection_bias else None,
        **shared,
    )


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def keep_best_groups(choose, groups: int, kept: int):
    """Group-limited selection (DeepSeek-V3's): ``choose`` [T, E] with the
    experts in ``groups`` equal groups by index; a group scores the sum of
    its two largest entries, and outside a token's ``kept`` best groups
    every entry becomes -inf, so that no top-k takes it."""
    T, num_experts = choose.shape
    with jax.named_scope("scope/layer/moe/route/groups"):
        grouped = choose.reshape(T, groups, num_experts // groups)
        score = jnp.sum(lax.top_k(grouped, 2)[0], axis=-1)  # [T, groups]
        _, best = lax.top_k(score, kept)
        keep = jnp.any(
            best[..., None] == jnp.arange(groups, dtype=best.dtype), axis=1
        )  # [T, groups]
        return jnp.where(keep[..., None], grouped, -jnp.inf).reshape(
            T, num_experts
        )


def route(logits: jnp.ndarray, k: int, normalize: bool, *,
          kind: str = "softmax", bias=None, scale: float = 1.0,
          groups: Tuple[int, int] = (1, 1)):
    """THE routing decision, in float32: each token's ``k`` best
    experts by softmax probability, their gate values, and the two
    auxiliary losses. Shared by the one-device and the ``ep`` path.

    ``kind="sigmoid"``: every expert is scored by the sigmoid of its own
    logit; the ``k`` with the largest ``score + bias`` are chosen, and
    their gate values are the scores WITHOUT the bias (so the bias
    steers the load and never the output: it takes no gradient), over
    their sum where ``normalize``. The balance loss is then over the
    scores normalised to sum to one a token, and there is no z-loss.
    ``scale`` multiplies the gate values of either kind. ``groups =
    (n, kept)`` with ``n`` > 1 limits the choice to the experts of each
    token's ``kept`` best of ``n`` groups (``keep_best_groups``, on the
    scores the choice is made by: with the bias).

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
    if kind == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        choose = scores
        if bias is not None:
            choose = scores + lax.stop_gradient(bias.astype(jnp.float32))
        if groups[0] > 1:
            choose = keep_best_groups(lax.stop_gradient(choose), *groups)
        _, idx = lax.top_k(choose, k)
        vals = jnp.take_along_axis(scores, idx, axis=-1)
        gates = (
            vals / (jnp.sum(vals, axis=-1, keepdims=True) + 1e-20)
            if normalize
            else vals
        )
        probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        if groups[0] > 1:
            _, idx = lax.top_k(
                keep_best_groups(lax.stop_gradient(probs), *groups), k
            )
            vals = jnp.take_along_axis(probs, idx, axis=-1)
        else:
            vals, idx = lax.top_k(probs, k)
        gates = (
            vals / (jnp.sum(vals, axis=-1, keepdims=True) + 1e-9)
            if normalize and k > 1
            else vals
        )
    if scale != 1.0:
        gates = gates * scale
    counts = jnp.sum(
        idx[..., None] == jnp.arange(num_experts, dtype=idx.dtype),
        axis=(0, 1),
        dtype=jnp.int32,
    )
    load = counts.astype(jnp.float32) / float(k * T)
    aux = {
        "balance": num_experts * jnp.sum(load * jnp.mean(probs, axis=0)),
        "z": jnp.float32(0.0) if kind == "sigmoid" else jnp.mean(
            jax.scipy.special.logsumexp(logits, axis=-1) ** 2
        ),
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
    routing: Optional[dict] = None,
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
    idx, gates, aux = route(logits, k, normalize, **(routing or {}))
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


def _add_shared_expert(out, params: MoEParams, x, activation):
    """``out`` plus the shared expert's output for every token, where
    the parameters have one: SwiGLU where they have its gate projection,
    the activation pair where not; times ``sigmoid(x . shared_out_gate)``,
    a scalar a token, where they have that vector."""
    if params.shared_up is None:
        return out
    with jax.named_scope("scope/layer/moe/shared"):
        h = x @ params.shared_up.astype(x.dtype)
        if params.shared_gate is not None:
            h = jax.nn.silu(x @ params.shared_gate.astype(x.dtype)) * h
        else:
            h = activation(h)
        y = h @ params.shared_down.astype(x.dtype)
        if params.shared_out_gate is not None:
            gate = jax.nn.sigmoid(jnp.dot(
                x, params.shared_out_gate.astype(x.dtype),
                preferred_element_type=jnp.float32,
            ))
            y = (y.astype(jnp.float32) * gate[:, None]).astype(x.dtype)
        return out + y


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


def share_rows(assignments: int, count: int, num_experts: int) -> int:
    """Rows of one round of ``_moe_share``: twice what the ``count`` held
    experts get of ``assignments`` when the routing is balanced, in whole
    512s, and at most all of them."""
    rows = -(-2 * assignments * count // num_experts // 512) * 512
    return min(rows, -(-assignments // 8) * 8)


# the names the first round of a share gives what its backward pass reads
# and no elementwise pass makes again: the gathered rows, what each grouped
# matmul hands the activation, what the round returns. What a
# ``jax.checkpoint`` around a layer saves of it when its policy holds them
# (``models/transformer.recomputed``); anywhere else a name is an identity
KEPT = ("moe_share_xs", "moe_share_h", "moe_share_ys")


def _moe_share(params: MoEParams, x, idx, gates, counts, activation, held):
    """A chip's share of the experts, ``held = (offset, count)``: the
    router scored all ``E`` experts, the weights are those of ``count``
    of them, and this computes their part of every token's output. What
    the chips that hold the others would add is left out; however uneven
    the routing, every assignment to a held expert is computed.

    The k*T assignments are sorted with the held experts' first (key
    ``(expert - offset) mod E``). Only those rows are ever gathered: they
    are taken ``share_rows`` at a time, in as many rounds as they need
    (one, unless the held experts draw more than twice their balanced
    share), each round a gather of its tokens, the grouped matmuls over
    the held groups as they fall into the round, and a scatter-add of
    the gated rows onto their tokens. Before this, the whole k*T-row buffer was gathered,
    masked and permuted home with 6 % of its rows real, a quarter of the
    step (PERF.md, Findings PR 37).

    Rows of a round past the last held row belong to no matmul, and what
    ``lax.ragged_dot`` leaves there is undefined on the TPU (zeros at one
    shape, NaN at another), in its result and in the cotangent it hands
    back: every buffer of a round is zeroed there, which zeroes the
    cotangents too, so nothing undefined reaches a token or a weight.

    The first round, all of them unless the share is overloaded, is
    differentiated in line: the backward pass reads the gathered rows and
    the grouped matmuls' results it left (``KEPT``) and runs no forward
    work for it (a site of ``common/trace_counts``,
    ``moe_share_kept_sites``). Only the rounds past it are made again in
    the backward pass (``rounds``), which adds their cotangents onto the
    first round's."""
    offset, count = held
    T, model = x.shape
    k = idx.shape[1]
    num_experts = counts.shape[0]
    R = share_rows(T * k, count, num_experts)
    name_xs, name_h, name_ys = KEPT

    def one_round(onto, x, experts, flat_gates, order, starts, ends, lo):
        """``onto`` [T, model] float32 and what the held rows lo .. lo + R
        of the sorted assignments add to their tokens."""
        n_held = ends[-1]
        with jax.named_scope("scope/layer/moe/dispatch"):
            mine = lax.dynamic_slice(order, (lo,), (R,))
            real = (lo + jnp.arange(R) < n_held)[:, None]
            token = mine // k
            xs = checkpoint_name(jnp.where(real, x[token], 0), name_xs)
            sizes = jnp.clip(ends, lo, lo + R) - jnp.clip(starts, lo, lo + R)

        def matmul(a, w):
            out = jnp.where(real, lax.ragged_dot(a, w, sizes), 0)
            # what the activation reads, or what the round returns
            return checkpoint_name(out, name_h if a is xs else name_ys)

        with jax.named_scope("scope/layer/moe/experts"):
            ys = _expert_ffn(
                MoEParams(None, *experts), matmul, xs, activation
            )
        with jax.named_scope("scope/layer/moe/combine"):
            weight = jnp.where(real, flat_gates[mine][:, None], 0.0)
            return onto.at[token].add(ys.astype(jnp.float32) * weight)

    def over_rounds(round_fn, acc, ends):
        """``acc`` after the rounds past the first, as many as the held
        rows need: none where they fit one round."""
        return lax.fori_loop(
            1, (ends[-1] + R - 1) // R,
            lambda i, acc: round_fn(acc, i * R), acc,
        )

    # Differentiated by hand: the rounds past the first are a loop of as
    # many trips as there are held rows to take (which ``jax.grad`` cannot
    # reverse), a round is made again in the backward pass, and its
    # cotangents are ADDED into one accumulator each. ``jax.grad`` of a
    # scan over all possible rounds kept every round's residuals, the
    # expert matrices among them, and added into the accumulators in
    # skipped rounds too: 16.5 GiB of temporaries and 125 ms a step
    # (PERF.md, PR 37). It hands its differentiated arguments on, and the
    # first round reads them from there: so the first round's cotangents
    # arrive in the backward rule and ARE the accumulators, and a loop of
    # no trip neither zeroes nor adds an array the experts' size.
    @jax.custom_vjp
    def rounds(x, experts, flat_gates, order, starts, ends):
        out = over_rounds(
            lambda out, lo: one_round(
                out, x, experts, flat_gates, order, starts, ends, lo
            ),
            jnp.zeros((T, model), jnp.float32), ends,
        )
        return out, (x, experts, flat_gates)

    def rounds_fwd(x, experts, flat_gates, order, starts, ends):
        res = (x, experts, flat_gates, order, starts, ends)
        return rounds(*res), res

    def rounds_bwd(res, cotangents):
        x, experts, flat_gates, order, starts, ends = res
        d_out, of_first_round = cotangents
        nothing = jnp.zeros((T, model), jnp.float32)

        def pull(acc, lo):
            _, vjp = jax.vjp(
                lambda *diff: one_round(
                    nothing, *diff, order, starts, ends, lo
                ),
                x, experts, flat_gates,
            )
            return jax.tree_util.tree_map(jnp.add, acc, vjp(d_out))

        return (*over_rounds(pull, of_first_round, ends), None, None, None)

    rounds.defvjp(rounds_fwd, rounds_bwd)

    trace_counts.count("moe_share_kept_sites")
    with jax.named_scope("scope/layer/moe/dispatch"):
        key = ((idx - offset) % num_experts).reshape(T * k)
        order = jnp.argsort(key, stable=True)  # assignment j is token j // k
        order = jnp.pad(order, (0, -(-T * k // R) * R - T * k))
        ends = jnp.cumsum(counts[offset:offset + count])
        starts = ends - counts[offset:offset + count]
    out, share = rounds(
        x, (params.w_up, params.w_down, params.w_gate),
        gates.reshape(T * k), order, starts, ends,
    )
    # the first round is every share's, and ``jax.grad``'s to reverse: the
    # backward pass reads what it left and makes nothing of it again
    out = one_round(out, *share, order, starts, ends, 0)
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
    router: str = "softmax",
    routed_scale: float = 1.0,
    held: Optional[Tuple[int, int]] = None,
    groups: Tuple[int, int] = (1, 1),
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

    ``router``, ``routed_scale`` and ``groups`` are ``route``'s ``kind``,
    ``scale`` and ``groups``. ``held = (offset, count)``, on one device
    only: the
    weights are ``count`` of the experts the gate scores (``_moe_share``). The shared expert, where the parameters have
    one, is added to every token's output.
    """
    ep = 1 if axis_name is None else lax.psum(1, axis_name)
    e_local = params.w_up.shape[0]
    e_global = params.gate.shape[1]
    if held is not None and held[1] == e_global:
        held = None
    if held is not None and ep > 1:
        raise ValueError(
            "a chip's share of the experts is a one-device layout; over "
            "an ep axis the axis is the share"
        )
    T, model = x.shape
    routing = dict(
        kind=router, bias=params.bias, scale=routed_scale, groups=groups
    )

    with jax.named_scope("scope/layer/moe/route"):
        logits = jnp.dot(
            x.astype(jnp.float32),
            params.gate.astype(jnp.float32),
            precision=lax.Precision.HIGHEST,
        )  # [T, E_global]
        if ep == 1:
            idx, gates, aux = route(logits, top_k, normalize, **routing)
    if ep == 1:
        counts = aux.pop("counts")
        if held is None:
            out = _moe_dropless(params, x, idx, gates, counts, activation)
        else:
            out = _moe_share(
                params, x, idx, gates, counts, activation, held
            )
        aux["drop"] = jnp.float32(0.0)
        return _add_shared_expert(out, params, x, activation), aux

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
            expert_caps=caps_arr, return_stats=True, routing=routing,
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
    return _add_shared_expert(out.astype(x.dtype), params, x, activation), aux


def moe_layer(params: MoEParams, x, mesh, **kw):
    """Global wrapper: x [B, S, model] sharded (batch→(dp,fsdp), seq→sp);
    expert weights sharded over ep on their first axis."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    xspec = P(("dp", "fsdp"), "sp", None)
    expert = P("ep", None, None)
    def whole(a):
        return None if a is None else P(*([None] * a.ndim))

    pspec = MoEParams(
        gate=P(None, None), w_up=expert, w_down=expert,
        w_gate=None if params.w_gate is None else expert,
        bias=whole(params.bias), shared_up=whole(params.shared_up),
        shared_down=whole(params.shared_down),
        shared_gate=whole(params.shared_gate),
        shared_out_gate=whole(params.shared_out_gate),
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


def fold_routing_report(metrics, stats, held=None) -> None:
    """Fold a reported step's own ``moe_drop_rate`` / ``moe_expert_load``
    into ``PipelineStats.moe_*``; nothing for a dense model. ``held =
    (offset, count)`` names the experts this chip holds (None: all): the
    share of the assignments that fell on them goes to
    ``moe_held_share_sum``. Copies to the host of a step already waited
    for: an op on the device here would queue behind the step in
    flight."""
    if "moe_drop_rate" not in metrics:
        return
    import numpy as np

    drop = np.asarray(metrics["moe_drop_rate"])
    load = np.asarray(metrics["moe_expert_load"])
    stats.moe_reports += 1
    stats.moe_drop_rate_sum += float(drop)
    stats.moe_max_load_sum += float(load.max()) * load.size
    offset, count = held or (0, load.size)
    stats.moe_held_share_sum += float(load[offset:offset + count].sum())


def move_router_bias(params, before, layer_loads, rate: float):
    """The auxiliary-loss-free balance rule, after a train step: every
    expert layer's selection bias moves by ``rate`` towards the experts
    that got less than the mean share of this step's assignments,
    ``b_i += rate * sign(mean(load) - load_i)``, from its value
    ``before`` the optimizer's update, which is thrown away (the bias
    has no gradient and must take no weight decay). ``params`` and
    ``before`` are the model's tree after and before the update,
    ``layer_loads`` [sparse layers, E] in layer order. Layers without a
    bias stay as they are."""
    layers, i = [], 0
    for layer, old in zip(params["layers"], before["layers"]):
        if "moe" in layer:
            if layer["moe"].bias is not None:
                load = layer_loads[i]
                step = rate * jnp.sign(jnp.mean(load) - load)
                bias = old["moe"].bias
                layer = dict(layer, moe=layer["moe"]._replace(
                    bias=bias + step.astype(bias.dtype)
                ))
            i += 1
        layers.append(layer)
    return dict(params, layers=layers)


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
    rises by at most ``boost``x while overflow drops fall
    (``tests/test_mesh_matrix.py::TestCapacityRebalance``).

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
