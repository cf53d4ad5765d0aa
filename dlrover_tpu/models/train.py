"""Sharded training step builder.

Replaces the reference's ``auto_accelerate`` *application* path (atorch
accelerate.py:34 ``model_transform``: wrap model in FSDP/TP/amp/etc.):
on TPU the "transform" is just computing a ``NamedSharding`` for every
param/optimizer leaf from the logical-axis tree and ``jit``-ing one train
step with those shardings — XLA emits the same collectives the wrappers
implement by hand (ZeRO-3 all-gather/reduce-scatter for the ``fsdp`` axis,
megatron TP collectives for ``tp``).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from dlrover_tpu.models.config import TransformerConfig
from dlrover_tpu.models.transformer import (
    check_window_mesh,
    forward,
    init_params,
    logical_axes,
    loss_fn,
)
from dlrover_tpu.parallel.mesh import MeshConfig, batch_sharding, build_mesh
from dlrover_tpu.parallel.sharding_rules import (
    ShardingRules,
    apply_rules,
    default_lm_rules,
)


@jax.tree_util.register_dataclass
@dataclass
class TrainState:
    step: Any
    params: Any
    opt_state: Any
    # error-feedback residual of the int8-compressed gradient sync
    # (parallel/grad_sync.py): per-bucket (dp, padded) fp32, carried
    # across steps so quantization noise cancels instead of biasing
    # the trajectory. None (the default) contributes NO pytree leaves,
    # so every pre-existing checkpoint/spec/reshard tree is unchanged;
    # it is attached opt-in via ``grad_sync.ensure_residual`` and
    # stripped before checkpoints/reshards (``strip_residual``).
    grad_residual: Any = None


def param_shardings(cfg: TransformerConfig, mesh, rules=None):
    rules = rules or default_lm_rules()
    return apply_rules(logical_axes(cfg), rules, mesh)


def opt_state_shardings(params_shape, p_sh, tx, mesh, opt_shape=None):
    """Shardings for ``tx.init``'s state: each leaf inherits its param's
    sharding (ZeRO: m/v shard with the param), scalars are replicated.

    Optimizer moments mirror the param tree, so an opt-state leaf's tree
    path *ends with* its param's full path (e.g. inner_state[0].mu
    ['layers'][3]['attn']['wq']). Match structurally on the path suffix
    (shape-checked) rather than by (shape, dtype) — two same-shaped,
    differently-sharded params (square w_up/w_down) must not alias.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    replicated = NamedSharding(mesh, P())
    if opt_shape is None:
        opt_shape = jax.eval_shape(
            lambda: tx.init(_zeros_like_tree(params_shape))
        )

    def _path_key(path):
        return tuple(str(k) for k in path)

    param_shapes = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params_shape)[0]:
        param_shapes[_path_key(path)] = leaf.shape
    sh_by_path = {}
    for path, sh in jax.tree_util.tree_flatten_with_path(p_sh)[0]:
        sh_by_path[_path_key(path)] = sh

    def opt_leaf_sharding(path, leaf):
        key = _path_key(path)
        for start in range(len(key)):
            suffix = key[start:]
            # shape-checked but deliberately not dtype-checked: moments in
            # a different precision (mu_dtype=bf16) still shard with their
            # param
            if param_shapes.get(suffix) == leaf.shape:
                return sh_by_path[suffix]
        return replicated

    return jax.tree_util.tree_map_with_path(opt_leaf_sharding, opt_shape)


def state_shardings(
    cfg: TransformerConfig, mesh, tx, rules=None,
    offload_opt_state: bool = False,
) -> TrainState:
    """Shardings for the whole TrainState. ``offload_opt_state`` swaps
    the optimizer-state leaves to pinned-host memory (same partitioning,
    host bytes — ops/host_offload.py, the CPU-offload Adam analog)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    p_sh = param_shardings(cfg, mesh, rules)
    replicated = NamedSharding(mesh, P())
    params_shape = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)
    )
    opt_sh = opt_state_shardings(params_shape, p_sh, tx, mesh)
    if offload_opt_state:
        from dlrover_tpu.ops.host_offload import offload_shardings

        opt_shape = jax.eval_shape(
            lambda: tx.init(_zeros_like_tree(params_shape))
        )
        opt_sh = offload_shardings(opt_sh, opt_shape)
    return TrainState(step=replicated, params=p_sh, opt_state=opt_sh)


def state_spec(
    cfg: TransformerConfig, mesh, tx, rules=None,
    offload_opt_state: bool = False,
) -> TrainState:
    """Abstract TrainState of ``ShapeDtypeStruct``-with-sharding leaves —
    the restore *target* a restarted worker hands to
    ``CheckpointEngine.load`` (ckpt/sharding.py ``target_shards``).
    Unlike a zeros template it allocates nothing on device, so restore
    peak HBM is the incoming state, not 2x it."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    # trace init_params/tx.init once each (state_shardings would re-trace)
    p_sh = param_shardings(cfg, mesh, rules)
    params_shape = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)
    )
    opt_shape = jax.eval_shape(
        lambda: tx.init(_zeros_like_tree(params_shape))
    )
    opt_sh = opt_state_shardings(
        params_shape, p_sh, tx, mesh, opt_shape=opt_shape
    )
    if offload_opt_state:
        from dlrover_tpu.ops.host_offload import offload_shardings

        opt_sh = offload_shardings(opt_sh, opt_shape)

    def _spec(shape_leaf, sh_leaf):
        return jax.ShapeDtypeStruct(
            shape_leaf.shape, shape_leaf.dtype, sharding=sh_leaf
        )

    return TrainState(
        step=jax.ShapeDtypeStruct(
            (), jnp.int32, sharding=NamedSharding(mesh, P())
        ),
        params=jax.tree_util.tree_map(_spec, params_shape, p_sh),
        opt_state=jax.tree_util.tree_map(_spec, opt_shape, opt_sh),
    )


def pad_row_weights(n_real: int, n_padded: int):
    """Loss row-weights for a zero-padded batch (micro-batch
    rebalance): real rows weigh ``n_padded / n_real`` and pad rows 0,
    so the plain mean over the padded batch equals the mean over the
    real rows — and the per-shard mean-of-means the explicit dp sync
    computes does too (the scale is uniform, so shard means compose
    exactly)."""
    import numpy as np

    if not 0 < n_real <= n_padded:
        raise ValueError(
            f"need 0 < n_real <= n_padded, got {n_real}/{n_padded}"
        )
    w = np.zeros((n_padded,), np.float32)
    w[:n_real] = n_padded / float(n_real)
    return w


def pad_batch_rows(x, n_padded: int):
    """Zero-pad a [B, ...] host batch to ``n_padded`` rows (the
    trainer's collate step for a rebalanced strategy; the matching
    ``pad_row_weights`` zero the pads out of the loss)."""
    import numpy as np

    x = np.asarray(x)
    if x.shape[0] >= n_padded:
        return x
    pad = np.zeros((n_padded - x.shape[0],) + x.shape[1:], x.dtype)
    return np.concatenate([x, pad], axis=0)


def _zeros_like_tree(shape_tree):
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), shape_tree
    )


def init_sharded_state(
    key, cfg: TransformerConfig, mesh, tx, rules=None,
    offload_opt_state: bool = False,
) -> Tuple[TrainState, TrainState]:
    """Initialize params/opt state directly into their shardings (no
    host-size materialization of the full model). With
    ``offload_opt_state`` the optimizer state is initialized DIRECTLY
    into pinned-host memory — it never occupies HBM, so states larger
    than the chip (fp32 Adam at 1.5B+) initialize fine."""
    sh = state_shardings(
        cfg, mesh, tx, rules, offload_opt_state=offload_opt_state
    )

    init_p = jax.jit(
        functools.partial(init_params, cfg=cfg), out_shardings=sh.params
    )
    params = init_p(key)
    init_o = jax.jit(tx.init, out_shardings=sh.opt_state)
    opt_state = init_o(params)
    step = jax.device_put(
        jnp.zeros((), jnp.int32), sh.step
    )
    return TrainState(step=step, params=params, opt_state=opt_state), sh


def _grad_sync_plan(
    cfg, mesh, grad_compress: str, grad_bucket_mb: int,
    grad_slices: int = 1, grad_topk_density: float = 0.25,
):
    """BucketPlan for the explicit sync path, or None when this mesh
    keeps GSPMD's native schedule — the gate lives in ONE place
    (``grad_sync.plan_for_mesh``, shared with the Strategy-level
    ``resolve_plan`` the trainer/cost model consult). dp x ep meshes
    get an ``EPSyncPlan`` (the fully-manual all-to-all region), 3D
    dp x fsdp x tp a tp-local ``BucketPlan``; pp meshes plan through
    the pipeline builder instead. The remaining compositions fall
    back with a once-per-mesh log naming the axes
    (``note_gspmd_fallback``): the strategy search stamps the opt
    names onto every candidate and such a candidate must still
    build."""
    from dlrover_tpu.parallel.grad_sync import (
        note_gspmd_fallback,
        plan_for_mesh,
    )

    plan = plan_for_mesh(
        cfg, mesh,
        grad_compress=grad_compress,
        grad_bucket_mb=grad_bucket_mb,
        slices=grad_slices,
        grad_topk_density=grad_topk_density,
    )
    if plan is None:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        note_gspmd_fallback(sizes)
    return plan


def build_train_step(
    cfg: TransformerConfig,
    mesh,
    tx,
    rules: Optional[ShardingRules] = None,
    donate: bool = True,
    grad_accum: int = 1,
    offload_opt_state: bool = False,
    opt_shardings=None,
    donate_inputs: bool = False,
    comm_overlap: bool = False,
    grad_compress: str = "none",
    grad_bucket_mb: int = 4,
    grad_slices: int = 1,
    batch_pad: int = 0,
    grad_topk_density: float = 0.25,
) -> Callable:
    """jitted (state, tokens, targets) → (state, metrics).

    ``donate_inputs``: also donate the token/target buffers — they are
    consumed by the first layer (and the microbatch reshape under
    ``grad_accum``), so XLA reuses their HBM as scratch instead of
    keeping a live copy across the step. Only for single-use batches
    (a prefetched batch the caller never touches again); a caller that
    feeds the same arrays every step must leave this off.

    ``grad_accum=K``: split the batch into K microbatches scanned
    sequentially, average their grads, apply ONE optimizer update — the
    large-global-batch recipe that also amortizes the optimizer's
    param-sized HBM pass over K× the tokens (at 1B+ params that pass is
    a visible slice of the step). Batch must divide by K; activation
    memory is per-microbatch.

    ``offload_opt_state``: the optimizer state lives in pinned-host
    memory between steps (ops/host_offload.py — the CPU-offload Adam
    analog); the step streams it in before ``tx.update`` and back out
    after, a cost ``grad_accum`` amortizes like the reference amortizes
    PCIe.

    ``comm_overlap`` / ``grad_compress`` ("int8", "int8_topk" —
    block top-k on the cross-slice DCN shard leg at
    ``grad_topk_density`` — or "auto", resolved per mesh from the
    measured ICI:DCN ratio): route gradient sync
    through the explicit bucketed scheduler (parallel/grad_sync.py) —
    per-bucket reduce-scatter + all-gather under ``shard_map`` on
    dp meshes (independent collectives XLA's latency-hiding scheduler
    can overlap with backward compute), a ZeRO-style reduce-scatter
    into the fsdp shard layout on dp x fsdp meshes (no gather leg),
    and a bucketed dp-axis sync under the tp/sp submesh on dp x tp/sp
    meshes; local fp32 accumulation under ``grad_accum`` means only
    the final microbatch syncs (wire traffic cut K×), and optionally
    int8-quantized wire payloads with error feedback when the state
    carries a residual (``grad_sync.ensure_residual``; dp/fsdp plans
    only). dp x ep meshes sync inside one fully-manual (dp, ep)
    region with the MoE all-to-alls; 3D dp x fsdp x tp composes the
    ZeRO and tp legs; only the remaining exotica (pp/ep composed with
    other model axes) fall back to the GSPMD default schedule, with a
    once-per-mesh log naming the axes. ``batch_pad`` is the
    micro-batch rebalance (zero-weight pad rows; see
    ``pad_row_weights``)."""
    check_window_mesh(cfg, mesh)
    # the state leaves the step in the layout it is initialized and
    # restored in. Left to GSPMD, the outputs of a sharded mesh drift
    # (replicated 1-D params and their moments came back sharded over
    # fsdp): the second step then recompiles for the new argument
    # layout, and a restarted worker's fresh target no longer matches
    # what was staged to shm, so the restore falls through to storage.
    # With ``offload_opt_state`` the opt tree is the MIXED one from
    # offload_shardings: host-kind tensors, device-kind scalars
    # (identical to the device tree off TPU, where placement is a
    # numeric no-op — host_offload.py). Callers that already computed
    # state_shardings pass its opt_state through ``opt_shardings``.
    st_sh = state_shardings(
        cfg, mesh, tx, rules, offload_opt_state=offload_opt_state
    )
    opt_sh = None
    if offload_opt_state:
        opt_sh = (
            opt_shardings
            if opt_shardings is not None
            else st_sh.opt_state
        )

    # grad_slices: DCN slice count of a hybrid dp axis
    # (MeshConfig.dp_slices() — the concrete Mesh cannot carry it);
    # > 1 plans the two-level ICI/DCN sync schedule
    plan = (
        _grad_sync_plan(
            cfg, mesh, grad_compress, grad_bucket_mb,
            grad_slices=grad_slices,
            grad_topk_density=grad_topk_density,
        )
        if (comm_overlap or grad_compress != "none")
        else None
    )
    if (
        plan is not None
        and getattr(plan, "kind", "") == "ep"
        and grad_accum > 1
    ):
        # the ep path syncs inside its one fully-manual region; a
        # grad-accum scan around it would sync every microbatch —
        # keep GSPMD's schedule instead of silently paying K syncs
        from dlrover_tpu.parallel.grad_sync import note_gspmd_fallback

        note_gspmd_fallback(
            dict(zip(mesh.axis_names, mesh.devices.shape)),
            reason=f"ep explicit sync with grad_accum={grad_accum}: "
            f"the manual region syncs per call",
        )
        plan = None
    # synced grads are pinned to the params' canonical shardings:
    # sync_grads hands back bucket slices whose GSPMD layout is the
    # flat bucket's (fsdp chunks / whatever auto-tp propagation
    # chose), and without the constraint the updated state would
    # drift off the layout the AOT executable was compiled with
    grad_sh = param_shardings(cfg, mesh, rules) if plan is not None else None

    if batch_pad and grad_accum > 1:
        raise ValueError(
            "batch_pad (micro-batch rebalance) requires grad_accum=1"
        )
    if batch_pad and cfg.num_experts:
        # the router's balance/z aux losses are computed over ALL
        # tokens — pad rows would shift them (and the capacity sizing)
        # even at loss weight 0, breaking the "gradients are those of
        # the real batch" contract; MoE models keep the idle-ranks
        # degradation instead (_rebalanced_strategy_for returns None)
        raise ValueError(
            "batch_pad is not supported for MoE models: the gating "
            "aux losses would see the pad tokens"
        )

    def _row_w(B: int):
        """Static loss row-weights for a padded batch of B rows (the
        trailing ``batch_pad`` rows weigh 0), or None unpadded."""
        if not batch_pad:
            return None
        return jnp.asarray(pad_row_weights(B - batch_pad, B))

    def _noise_step(state):
        """What a step hands ``loss_fn`` beside the batch: its own number
        where the objective draws noise (a row met again in a later step
        is noised anew), nothing otherwise."""
        return (state.step,) if cfg.objective else ()

    def grads_and_loss(params, tokens, targets, *step):
        def lf(p):
            return loss_fn(
                p, tokens, targets, cfg, mesh, return_aux=True,
                row_weights=_row_w(tokens.shape[0]),
                noise_step=step[0] if step else None,
            )

        return jax.value_and_grad(lf, has_aux=True)(params)

    def local_grads_and_loss(params, tokens, targets, *step):
        """Per-device UNsynchronized grads under ``shard_map``: each
        device differentiates the loss of its own batch shard
        (mesh=None inside — no sharding constraints in a manual
        region), and every output gains a leading data axis of
        per-device size 1 so 'different value on every device' has a
        GSPMD-legal sharded representation (``P(plan.stack_axes)``).

        dp and ZeRO plans run full-manual (the data axes are the only
        real axes). dp x tp/sp plans run manual over **dp only**
        (``axis_names``): tp/sp stay GSPMD axes inside the body, so
        the model-sharded matmuls keep their native partitioned
        schedule instead of being computed replicated per device —
        each dp rank here is the whole tp submesh."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        kw = {}
        if plan.three_d:
            # manual over the data axes only; tp/sp stay GSPMD auto
            # for the matmuls (the sync itself later goes FULLY
            # manual in _sync_grads_3d — psum_scatter cannot run in
            # a partial-manual region)
            kw["axis_names"] = frozenset({"dp", "fsdp"})
            batch_spec = P(("dp", "fsdp"))
        elif plan.auto_axes:
            kw["axis_names"] = frozenset({"dp"})
            batch_spec = P(("dp",))  # tp/sp/ep sharding rides as auto
        else:
            batch_spec = P(("dp", "fsdp"), "sp")

        def body(p, x, y, w, *step):
            def lf(pp):
                return loss_fn(
                    pp, x, y, cfg, None, return_aux=True,
                    # replicated dummy when unpadded (batch_pad is a
                    # build-time constant)
                    row_weights=w if batch_pad else None,
                    noise_step=step[0] if step else None,
                )

            (loss, aux), g = jax.value_and_grad(lf, has_aux=True)(p)
            lead = lambda a: a[None]  # noqa: E731
            return (
                lead(loss),
                jax.tree_util.tree_map(lead, aux),
                jax.tree_util.tree_map(lead, g),
            )

        # row weights shard with the batch rows (uniform scale, so the
        # per-shard mean-of-means still composes exactly — see
        # pad_row_weights)
        w = _row_w(tokens.shape[0])
        w_spec = P(batch_spec[0]) if w is not None else P()
        stacked = P(plan.stack_axes)
        return shard_map(
            body,
            mesh=mesh,
            in_specs=(P(), batch_spec, batch_spec, w_spec)
            + (P(),) * len(step),
            out_specs=(stacked, stacked, stacked),
            check_vma=False,
            **kw,
        )(
            params,
            tokens,
            targets,
            w if w is not None else jnp.zeros((1,), jnp.float32),
            *step,
        )

    def _microbatches(tokens, targets):
        B = tokens.shape[0]
        if B % grad_accum:
            raise ValueError(
                f"batch {B} must divide into grad_accum={grad_accum}"
            )
        mb = B // grad_accum
        return (
            tokens.reshape(grad_accum, mb, *tokens.shape[1:]),
            targets.reshape(grad_accum, mb, *targets.shape[1:]),
        )

    def ep_synced_grads(state, tokens, targets):
        """The dp x ep explicit path: ONE fully-manual (dp, ep)
        region computes per-dp-rank local grads WITH the MoE
        dispatch/combine all-to-alls inside it (expert weights enter
        as their LOCAL 1/ep slices; ``moe_axis="ep"`` threads the
        manual axis into the gating body) and bucket-syncs them over
        dp in place (``grad_sync.sync_local_tree``). The loss is
        seeded on ep rank 0 only — every ep rank computes the same
        loss through the rank-crossing all-to-alls, so seeding all of
        them would hand the expert weights an ep-scaled cotangent;
        rank 0's backward still reaches every rank's experts through
        the all-to-all transpose, and the ep-replicated dense grads
        are shared back with one selection psum."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from dlrover_tpu.parallel.grad_sync import sync_local_tree

        p_leaves, p_def = jax.tree_util.tree_flatten(state.params)
        expert_ids = set(plan.expert_leaf_ids)
        dim_by_id = dict(
            zip(plan.expert_leaf_ids, plan.expert_leaf_dims)
        )
        dense_ids = [
            i for i in range(len(p_leaves)) if i not in expert_ids
        ]

        def _leaf_spec(i):
            if i not in expert_ids:
                return P()
            entries = [None] * p_leaves[i].ndim
            entries[dim_by_id[i]] = "ep"
            return P(*entries)

        param_specs = tuple(_leaf_spec(i) for i in range(len(p_leaves)))
        batch_spec = P(("dp",))

        def body(leaves_in, x, y, w, *step):
            params = jax.tree_util.tree_unflatten(
                p_def, list(leaves_in)
            )
            ep_idx = jax.lax.axis_index("ep")

            def lf(p):
                loss, aux = loss_fn(
                    p, x, y, cfg, None, return_aux=True,
                    moe_axis="ep",
                    # the w operand is a replicated dummy when the
                    # strategy is unpadded (batch_pad is a build-time
                    # constant)
                    row_weights=w if batch_pad else None,
                    noise_step=step[0] if step else None,
                )
                seed = (ep_idx == 0).astype(loss.dtype)
                return loss * seed, (loss, aux)

            (_, (loss, aux)), g = jax.value_and_grad(
                lf, has_aux=True
            )(params)
            g_leaves = list(jax.tree_util.tree_flatten(g)[0])
            for i in dense_ids:
                # dense grads are nonzero only on ep rank 0 (the loss
                # seed) — psum over ep is selection, not averaging
                g_leaves[i] = jax.lax.psum(g_leaves[i], "ep")
            with jax.named_scope("scope/grad_sync"):
                e_synced, ss_e = sync_local_tree(
                    [g_leaves[i] for i in plan.expert_leaf_ids],
                    plan.expert_plan,
                )
                d_synced, ss_d = sync_local_tree(
                    [g_leaves[i] for i in dense_ids], plan.dense_plan
                )
            out = [None] * len(g_leaves)
            for i, gl in zip(plan.expert_leaf_ids, e_synced):
                out[i] = gl
            for i, gl in zip(dense_ids, d_synced):
                out[i] = gl
            gnorm = jnp.sqrt(jax.lax.psum(ss_e, "ep") + ss_d)
            loss = jax.lax.pmean(loss, "dp")
            aux = jax.tree_util.tree_map(
                lambda a: jax.lax.pmean(a, "dp"), aux
            )
            return tuple(out), loss, aux, gnorm

        from dlrover_tpu.models.transformer import _zero_aux

        aux_specs = jax.tree_util.tree_map(
            lambda _: P(), _zero_aux(cfg)
        )
        # micro-batch rebalance row weights shard with the batch rows
        # (None -> a replicated dummy the body ignores), same contract
        # as local_grads_and_loss
        w = _row_w(tokens.shape[0])
        grads_leaves, loss, aux, gnorm = shard_map(
            body,
            mesh=mesh,
            in_specs=(
                param_specs,
                batch_spec,
                batch_spec,
                P(("dp",)) if w is not None else P(),
            ) + (P(),) * len(_noise_step(state)),
            out_specs=(param_specs, P(), aux_specs, P()),
            check_vma=False,
        )(
            tuple(p_leaves),
            tokens,
            targets,
            w if w is not None else jnp.zeros((1,), jnp.float32),
            *_noise_step(state),
        )
        grads = jax.tree_util.tree_unflatten(
            p_def, list(grads_leaves)
        )
        grads = jax.tree_util.tree_map(
            lambda g, sh: jax.lax.with_sharding_constraint(g, sh),
            grads,
            grad_sh,
        )
        return loss, aux, grads, gnorm, state.grad_residual

    def synced_grads(state, tokens, targets):
        """The explicit scheduler: local grads (accumulated in fp32
        across microbatches WITHOUT collectives), then ONE bucketed
        sync per optimizer step — with grad_accum=K the wire traffic
        is K× below the per-microbatch GSPMD sync, and the grad norm
        falls out of the bucket walk instead of a second tree pass."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dlrover_tpu.models.transformer import _zero_aux
        from dlrover_tpu.parallel.grad_sync import sync_grads

        if grad_accum > 1:
            xs, ys = _microbatches(tokens, targets)
            stacked_sh = NamedSharding(mesh, P(plan.stack_axes))

            def body(carry, xy):
                g_acc, loss_acc, aux_acc = carry
                loss_s, aux_s, g_s = local_grads_and_loss(
                    state.params, *xy, *_noise_step(state)
                )
                g_acc = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(jnp.float32), g_acc, g_s
                )
                aux_acc = jax.tree_util.tree_map(
                    lambda a, b: a + jnp.mean(b), aux_acc, aux_s
                )
                return (g_acc, loss_acc + jnp.mean(loss_s), aux_acc), None

            zeros_g = jax.tree_util.tree_map(
                lambda p: jax.lax.with_sharding_constraint(
                    jnp.zeros((plan.total,) + p.shape, jnp.float32),
                    stacked_sh,
                ),
                state.params,
            )
            (g_sum, loss_sum, aux_sum), _ = jax.lax.scan(
                body, (zeros_g, jnp.float32(0.0), _zero_aux(cfg)), (xs, ys)
            )
            k = jnp.float32(grad_accum)
            g_stacked = jax.tree_util.tree_map(
                lambda g: g / k, g_sum
            )
            loss = loss_sum / k
            aux = jax.tree_util.tree_map(lambda a: a / k, aux_sum)
        else:
            loss_s, aux_s, g_stacked = local_grads_and_loss(
                state.params, tokens, targets, *_noise_step(state)
            )
            loss = jnp.mean(loss_s)
            aux = jax.tree_util.tree_map(jnp.mean, aux_s)
        # residual present => error feedback; absent => EF-less
        # compression (structure-preserving: the step never conjures
        # state leaves, so AOT executables and donation stay valid —
        # the trainer opts into EF via grad_sync.ensure_residual).
        # Gate on the PLAN's resolved mode, not the request string:
        # "auto" and downgrades (topk on a single-slice mesh) resolve
        # at plan time.
        residual = (
            state.grad_residual
            if getattr(plan, "compressed", False)
            else None
        )
        from dlrover_tpu.parallel import sdc as sdc_mod

        sdc_on = sdc_mod.enabled()
        dev_norms = None
        if sdc_on and not getattr(plan, "three_d", False):
            # SDC injection (site device.sdc, kind scale): resolved
            # ONCE at trace time into a per-lane scale vector baked
            # into the compiled step — lane ``inj.device`` multiplies
            # its LOCAL gradient by the finite corruption factor from
            # step ``inj.from_step`` on, exactly what a silently-bad
            # chip does. Baking means conviction must retire this
            # incarnation (the trainer halts and the master excludes
            # the chip from the next world) — which is the real
            # quarantine-drain model anyway.
            inj = sdc_mod.injection_plan(plan.total)
            if inj is not None:
                sv = (
                    jnp.ones((plan.total,), jnp.float32)
                    .at[inj.device]
                    .set(jnp.float32(inj.factor))
                )
                sv = jnp.where(
                    state.step + 1 >= inj.from_step,
                    sv,
                    jnp.ones((plan.total,), jnp.float32),
                )
                g_stacked = jax.tree_util.tree_map(
                    lambda g: g
                    * sv.reshape((plan.total,) + (1,) * (g.ndim - 1)),
                    g_stacked,
                )
            with jax.named_scope("scope/grad_sync"):
                grads, new_residual, gnorm, dev_norms = sync_grads(
                    g_stacked,
                    mesh,
                    plan,
                    residual=residual,
                    device_norms=True,
                )
        else:
            with jax.named_scope("scope/grad_sync"):
                grads, new_residual, gnorm = sync_grads(
                    g_stacked, mesh, plan, residual=residual
                )
        grads = jax.tree_util.tree_map(
            lambda g, sh: jax.lax.with_sharding_constraint(g, sh),
            grads,
            grad_sh,
        )
        if gnorm is None:
            # 3d plans hand the norm back (a per-chunk sum inside the
            # manual region would double-count tp-replicated leaves)
            gnorm = optax.global_norm(grads)
        if residual is None:
            new_residual = state.grad_residual
        return loss, aux, grads, gnorm, new_residual, dev_norms

    def gspmd_grads(state, tokens, targets):
        """The default path: XLA's implicit sync. Microbatch grads
        accumulate in fp32 regardless of param dtype (bf16 params
        used to lose low-order bits microbatch by microbatch), cast
        back to the param dtype ONCE after averaging."""
        if grad_accum > 1:
            xs, ys = _microbatches(tokens, targets)

            def body(carry, xy):
                g_acc, loss_acc, aux_acc = carry
                (loss, aux), g = grads_and_loss(
                    state.params, *xy, *_noise_step(state)
                )
                g_acc = jax.tree_util.tree_map(
                    lambda a, gg: a + gg.astype(jnp.float32), g_acc, g
                )
                aux_acc = jax.tree_util.tree_map(jnp.add, aux_acc, aux)
                return (g_acc, loss_acc + loss, aux_acc), None

            from dlrover_tpu.models.transformer import _zero_aux

            zeros_g = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32),
                state.params,
            )
            (g_sum, loss_sum, aux_sum), _ = jax.lax.scan(
                body, (zeros_g, jnp.float32(0.0), _zero_aux(cfg)), (xs, ys)
            )
            k = jnp.float32(grad_accum)
            grads = jax.tree_util.tree_map(
                lambda g, p: (g / k).astype(p.dtype),
                g_sum,
                state.params,
            )
            loss = loss_sum / k
            aux = jax.tree_util.tree_map(lambda a: a / k, aux_sum)
        else:
            (loss, aux), grads = grads_and_loss(
                state.params, tokens, targets, *_noise_step(state)
            )
        with jax.named_scope("scope/grad_norm"):
            gnorm = optax.global_norm(grads)
        return loss, aux, grads, gnorm, None

    from dlrover_tpu.ops.quantized_optim import in_place_entry

    # a transformation that can write a parameter where it lies
    # (``ops/quantized_optim.InPlaceTransformation``), where this step
    # may let it: else None, and the two lines below
    update_and_apply = in_place_entry(
        tx, devices=mesh.size, donate=donate,
        resident=not offload_opt_state,
    )

    def train_step(state: TrainState, tokens, targets):
        dev_norms = None
        if plan is not None and getattr(plan, "kind", "") == "ep":
            loss, aux, grads, gnorm, new_residual = ep_synced_grads(
                state, tokens, targets
            )
        elif plan is not None:
            loss, aux, grads, gnorm, new_residual, dev_norms = (
                synced_grads(state, tokens, targets)
            )
        else:
            loss, aux, grads, gnorm, _ = gspmd_grads(
                state, tokens, targets
            )
            new_residual = state.grad_residual
        opt_state = state.opt_state
        if offload_opt_state:
            from dlrover_tpu.ops.host_offload import fetch_tree

            opt_state = fetch_tree(opt_state, opt_sh)
        # stable names on the device (see models/transformer.py): the
        # optimizer pass here holds whatever the chain clips by too
        if update_and_apply is not None:
            with jax.named_scope("scope/optimizer"):
                new_params, new_opt = update_and_apply(
                    grads, opt_state, state.params
                )
        else:
            with jax.named_scope("scope/optimizer"):
                updates, new_opt = tx.update(
                    grads, opt_state, state.params
                )
            if offload_opt_state:
                from dlrover_tpu.ops.host_offload import offload_tree

                new_opt = offload_tree(new_opt, opt_sh)
            with jax.named_scope("scope/optimizer"):
                new_params = optax.apply_updates(state.params, updates)
        if "layer_load" in aux and cfg.router == "sigmoid":
            from dlrover_tpu.parallel.moe import move_router_bias

            new_params = move_router_bias(
                new_params, state.params, aux["layer_load"],
                cfg.router_bias_rate,
            )
        metrics = {"loss": loss, "grad_norm": gnorm}
        if dev_norms is not None:
            # SDC tier-1 fence input: each lane's LOCAL pre-sync grad
            # norm (a [plan.total] vector — consumers that report
            # scalars must pop it, same contract as moe_expert_load)
            metrics["sdc_device_norms"] = dev_norms
        if cfg.num_experts:
            metrics["moe_balance_loss"] = aux["balance"]
            metrics["moe_z_loss"] = aux["z"]
            # routing telemetry (ISSUE 13): each expert's share of
            # the assignments (a [num_experts] vector — consumers that
            # report scalars must pop it) and the capacity drop rate
            # (0 wherever every expert is local); the trainer's
            # CapacityRebalancer periodically turns these into
            # cfg.capacity_splits. forward() SUMS aux across layers,
            # so normalize by the MoE layer count to report true
            # per-layer rates/fractions
            from dlrover_tpu.models.config import num_moe_layers

            n_moe = max(num_moe_layers(cfg), 1)
            metrics["moe_expert_load"] = aux["load"] / n_moe
            metrics["moe_drop_rate"] = aux["drop"] / n_moe
        if cfg.ut_steps > 1:
            # a looped model's exits (``models/transformer.ut_exits``):
            # the mean entropy of the stopping distribution (nats), the
            # mean expected pass of stopping (from 1), and each pass's
            # mean NLL (a [ut_steps] vector: consumers that report
            # scalars must pop it, as ``moe_expert_load``)
            for name in ("ut_entropy", "ut_exit_step", "ut_exit_nll"):
                metrics[name] = aux[name]
        if cfg.objective:
            # a step's noise (``models/transformer.diffusion_noise``): the
            # share of the data tokens that were masked, and the mean of
            # the weight a position's cross-entropy carries (1 / t where
            # masked, 0 elsewhere: 1 in expectation)
            for name in ("diffusion_masked_share", "diffusion_mean_weight"):
                metrics[name] = aux[name]
        return (
            TrainState(
                step=state.step + 1,
                params=new_params,
                opt_state=new_opt,
                grad_residual=new_residual,
            ),
            metrics,
        )

    donate_argnums = ((0,) if donate else ()) + (
        (1, 2) if donate_inputs else ()
    )
    # params and optimizer state are pinned; the residual and the
    # metrics are left to the compiler (None = unspecified)
    return jax.jit(
        train_step,
        donate_argnums=donate_argnums,
        out_shardings=(st_sh, None),
    )


def fold_exit_report(metrics, stats) -> str:
    """Fold a reported step's own ``ut_entropy`` / ``ut_exit_step`` into
    ``PipelineStats.ut_*`` and say each pass's mean NLL for the log line;
    nothing and "" for a model that runs its layers once. Copies to the
    host of a step already waited for, as ``parallel/moe.
    fold_routing_report``."""
    if "ut_entropy" not in metrics:
        return ""
    entropy = float(metrics["ut_entropy"])
    stats.ut_reports += 1
    stats.ut_entropy_sum += entropy
    stats.ut_exit_step_sum += float(metrics["ut_exit_step"])
    each = ", ".join(f"{float(n):.4f}" for n in metrics["ut_exit_nll"])
    return f" ut_exit_nll=[{each}] ut_entropy={entropy:.4f}"


def fold_diffusion_report(metrics, stats) -> str:
    """Fold a reported step's own ``diffusion_masked_share`` /
    ``diffusion_mean_weight`` into ``PipelineStats.diffusion_*`` and say
    them for the log line; nothing and "" for a model trained by
    next-token prediction. As ``fold_exit_report``."""
    if "diffusion_masked_share" not in metrics:
        return ""
    masked = float(metrics["diffusion_masked_share"])
    weight = float(metrics["diffusion_mean_weight"])
    stats.diffusion_reports += 1
    stats.diffusion_masked_sum += masked
    stats.diffusion_weight_sum += weight
    return f" masked={masked:.4f} weight={weight:.4f}"


def shard_batch(batch, mesh):
    """Host numpy batch → global sharded jax.Array over (dp,fsdp)×sp."""
    sharding = batch_sharding(mesh)
    return jax.tree_util.tree_map(
        lambda x: jax.make_array_from_process_local_data(sharding, x),
        batch,
    )
