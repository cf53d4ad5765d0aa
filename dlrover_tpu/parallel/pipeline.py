"""GPipe-style pipeline parallelism over the ``pp`` mesh axis.

Parity: the reference's PiPPy-based pipe compiler
(atorch/atorch/modules/distributed_modules/compilers/pipe_compiler/
distributed_pippy_compiler.py:541, PipelineStage.py:989) traces the model
into per-stage graphs, places them on ranks and streams microbatches over
torch RPC, with DeepSpeed 3D as a second backend
(ds_3d_parallel_optimization.py). The TPU-native design needs none of that
machinery:

- per-stage layer parameters are **stacked on a leading axis sharded over
  ``pp``** (stage s owns rows [s]), so placement is a sharding, not a
  graph partitioner;
- the microbatch rotation runs inside ``jax.shard_map`` that is *manual
  over pp only* — dp/fsdp/tp stay GSPMD-auto inside the body, so ZeRO-3
  and megatron-TP sharding compose with PP without stage-local rewrites;
- activations hop stages via ``lax.ppermute`` over ICI;
- autodiff through the scan-of-ppermute yields the backward pipeline
  schedule for free (ppermute transposes to the reverse rotation).

Schedules:

- **GPipe** (``schedule="gpipe"``): M microbatch forwards scanned over the
  stage ring, reverse-mode AD gives the backward rotation; bubble fraction
  (P-1)/(M+P-1), activation footprint O(M) stage inputs per device (the
  scan carry is saved per tick).
- **1F1B** (``schedule="1f1b"``): the steady-state one-forward-one-backward
  schedule (PipeDream-flush, what Megatron/DeepSpeed run). Reverse-mode AD
  cannot produce it (it is not "forward then transpose"), so the backward
  is built manually: each tick every stage runs one microbatch forward
  AND one microbatch backward (``jax.vjp`` per stage, recomputing the
  stage forward from its saved *input* — remat at stage granularity), the
  last stage turns a microbatch's loss into d(loss)/dy the same tick its
  forward completes. Activation footprint is a ring buffer of 2P-1 stage
  inputs per device — **independent of M**, the property that lets real
  pipelines run M >> P microbatches to shrink the bubble.
- **Interleaved 1F1B** (``schedule="interleaved"``, Megatron virtual
  pipeline stages; ref StageInterleaver.py:16): each device owns
  ``virtual_stages`` non-contiguous layer chunks, shrinking the bubble
  by ~v at equal M for an O(vP) activation ring buffer — see
  ``pipeline_value_and_grad_1f1b``'s docstring for the tick algebra.

Layout contract: the embedding runs before the pipeline region and the
final-norm/LM-head after it, in plain GSPMD-auto land; only the L
transformer blocks are staged. ``cfg.num_layers`` must divide evenly into
``pp`` stages and all blocks must be homogeneous (no MoE interleave —
EP×PP composition is scoped out, as in the reference where MoE and PiPPy
pipelines are separate optimizations).
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from dlrover_tpu.models.config import LAYER_READS, TransformerConfig
from dlrover_tpu.models.train import TrainState, opt_state_shardings
from dlrover_tpu.models.transformer import (
    _attention_block,
    _mlp_block,
    embed_tokens,
    init_params,
    lm_head,
    logical_axes,
    recomputed,
    token_nll,
)
from dlrover_tpu.parallel.sharding_rules import (
    ShardingRules,
    apply_rules,
    default_lm_rules,
)

STAGE_AXES = ("stage", "layer_stack")  # leading axes of stacked stage params


def pipeline_rules(rules: Optional[ShardingRules] = None) -> ShardingRules:
    """Extend the LM rule table with the stage axes: "stage" → pp mesh
    axis, the intra-stage layer-stack axis replicated."""
    rules = rules or default_lm_rules()
    merged = dict(rules.rules)
    merged.setdefault("stage", "pp")
    merged.setdefault("chunk", None)  # virtual stages: per-device slots
    merged.setdefault("layer_stack", None)
    return ShardingRules(rules=merged)


def _microbatch_axes(mesh, mb: int) -> Tuple[str, ...]:
    """Mesh axes to shard the per-microbatch batch dim over: the largest
    prefix of ("dp", "fsdp") whose device product divides ``mb``.

    Constraining mb over axes that do NOT divide it (e.g. mb=2 over
    dp*fsdp=4) makes XLA pad-and-reshard every stage boundary — the
    "Involuntary full rematerialization" warnings the SPMD partitioner
    emits when it must replicate a tensor to move between such layouts.
    """
    axes = []
    n = 1
    for ax in ("dp", "fsdp"):
        sz = mesh.shape.get(ax, 1)
        if sz > 1 and mb % (n * sz) == 0:
            axes.append(ax)
            n *= sz
    return tuple(axes)


def _check_pipeline_cfg(
    cfg: TransformerConfig, pp: int, virtual: int = 1
) -> None:
    if set(cfg.layer_pattern) & set(LAYER_READS):
        raise ValueError(
            f"pipeline parallelism passes the residual stream alone from "
            f"stage to stage: the layers of layer_pattern "
            f"{cfg.layer_pattern!r} that read another layer's scan output "
            f"or keys and values ({sorted(LAYER_READS)}) could lie a stage "
            "above the layer they read, and nothing carries it there"
        )
    if cfg.objective:
        raise ValueError(
            f"pipeline parallelism feeds a stage the row of data and "
            f"scores the next token: objective {cfg.objective!r} feeds a "
            "row twice, its noised copy first, and scores the noised "
            "positions' own tokens under a weight a token, which no "
            "stage's schedule here does"
        )
    if cfg.ut_steps > 1:
        raise ValueError(
            f"pipeline parallelism sends a microbatch through the stages "
            f"once: a looped model (ut_steps {cfg.ut_steps}) would send "
            f"the stream through every stage {cfg.ut_steps} times over "
            "the stage's own weights, with the final norm and an exit "
            "between visits, and the schedule knows one visit"
        )
    if cfg.attn_window:
        raise ValueError(
            f"pipeline parallelism stacks all-alike attention + FFN "
            f"blocks: the window layers of layer_pattern "
            f"{cfg.layer_pattern!r} (attn_window {cfg.attn_window}) "
            "would run as full attention"
        )
    if cfg.num_experts:
        raise ValueError(
            "pipeline parallelism requires homogeneous blocks (MoE layers "
            "interleave a different tree structure); use ep without pp"
        )
    if cfg.scan_layers:
        raise ValueError(
            "pipeline parallelism has its own stage-stacked layout; set "
            "scan_layers=False (stages already scan their layer block)"
        )
    stages = pp * virtual
    if cfg.num_layers % stages != 0:
        what = (
            f"pp={pp} x virtual={virtual} = {stages} chunks"
            if virtual > 1
            else f"pp={pp} stages"
        )
        raise ValueError(
            f"num_layers={cfg.num_layers} must divide into {what}"
        )


def stack_pipeline_params(params: Any, pp: int, virtual: int = 1) -> Any:
    """{"embed","final_norm",("lm_head"),"layers":[L dicts]} →
    same dict with "layers" replaced by "stages".

    ``virtual=1``: leaves [pp, L/pp, ...] — device d owns the contiguous
    layer block d.
    ``virtual=v>1`` (interleaved schedules): leaves [pp, v, L/(v*pp), ...]
    — global stage s = q*pp + d lives at [d, q], i.e. device d owns v
    NON-contiguous layer chunks (Megatron virtual pipeline stages, ref
    StageInterleaver.py:16)."""
    layers = params["layers"]
    lc = len(layers) // (pp * virtual)
    stages = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs).reshape(
            virtual, pp, lc, *xs[0].shape
        ).swapaxes(0, 1)
        if virtual > 1
        else jnp.stack(xs).reshape(pp, lc, *xs[0].shape),
        *layers,
    )
    out = {k: v for k, v in params.items() if k != "layers"}
    out["stages"] = stages
    return out


def _dechunk_leaf(x, virtual: int):
    """One stacked-stage leaf back to global layer order [L, ...]:
    [pp, lc, ...] (``virtual=1``) or chunk-major [pp, v, lc, ...]
    (``virtual>1``, via stage-major [v, pp, lc, ...]). The SINGLE home
    of the interleaved-layout algebra — ``unstack_pipeline_params`` and
    ``pipeline_forward``'s eval restack both go through here."""
    if virtual > 1:
        x = x.swapaxes(0, 1)
    return x.reshape(-1, *x.shape[2 + (virtual > 1):])


def unstack_pipeline_params(
    pparams: Any, cfg: TransformerConfig, virtual: int = 1
) -> Any:
    """Inverse of ``stack_pipeline_params`` (for checkpoints / eval)."""
    stages = pparams["stages"]
    L = cfg.num_layers

    flat = jax.tree_util.tree_map(
        lambda x: _dechunk_leaf(x, virtual), stages
    )
    layers = [
        jax.tree_util.tree_map(lambda x: x[i], flat) for i in range(L)
    ]
    out = {k: v for k, v in pparams.items() if k != "stages"}
    out["layers"] = layers
    return out


def pipeline_logical_axes(
    cfg: TransformerConfig, pp: int, virtual: int = 1
) -> Any:
    """Logical-axis pytree congruent with ``stack_pipeline_params``'s
    output: per-layer axes prefixed with the (stage[, chunk], layer_stack)
    axes."""
    axes = logical_axes(cfg)
    layer0 = axes["layers"][0]
    prefix = (
        ("stage", "chunk", "layer_stack") if virtual > 1 else STAGE_AXES
    )

    def prefixed(t):
        return prefix + t

    stages = jax.tree_util.tree_map(
        prefixed,
        layer0,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(a is None or isinstance(a, str) for a in x),
    )
    out = {k: v for k, v in axes.items() if k != "layers"}
    out["stages"] = stages
    return out


def pipeline_param_shardings(
    cfg: TransformerConfig, mesh, pp: int, rules=None, virtual: int = 1
):
    return apply_rules(
        pipeline_logical_axes(cfg, pp, virtual), pipeline_rules(rules), mesh
    )


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def pipeline_forward(
    pparams: Any,
    tokens: jnp.ndarray,
    cfg: TransformerConfig,
    mesh,
    num_microbatches: int,
    virtual: int = 1,
) -> jnp.ndarray:
    """tokens [B,T] int32 → logits [B,T,vocab] fp32, staged over pp.

    B must divide by ``num_microbatches`` (and the microbatch by the dp
    sharding, as usual).

    ``virtual>1`` accepts params in the interleaved [pp, v, lc, ...]
    layout (stack_pipeline_params) and restacks them in-graph to the
    contiguous [pp, L/pp, ...] layout this forward schedule uses: the
    grad-free eval path doesn't need the interleaved bubble win, only
    layout compatibility with the training state. The restack is one
    GSPMD reshard over pp per eval compile — acceptable for eval.
    """
    pp = mesh.shape["pp"]
    M = num_microbatches
    _check_pipeline_cfg(cfg, pp, virtual)
    if virtual > 1:
        L = cfg.num_layers

        def to_contiguous(x):
            # global layer order, then contiguous stages [pp, L/pp, ...]
            flat = _dechunk_leaf(x, virtual)
            return flat.reshape(pp, L // pp, *flat.shape[1:])

        pparams = dict(pparams)
        pparams["stages"] = jax.tree_util.tree_map(
            to_contiguous, pparams["stages"]
        )
    if mesh.shape.get("sp", 1) > 1:
        raise ValueError("sp (ring attention) inside pp stages not supported")
    B, T = tokens.shape
    if B % M != 0:
        raise ValueError(f"batch {B} must divide into {M} microbatches")
    mb = B // M

    # embedding: before the pipeline region, plain GSPMD. Reshape the
    # token ids into microbatch layout FIRST and pin the layout, so the
    # [M, mb, T, D] activations are BORN in the spec the pipeline body
    # uses — never resharded at the region boundary
    mb_axes = _microbatch_axes(mesh, mb)
    tok_mb = lax.with_sharding_constraint(
        tokens.reshape(M, mb, T), NamedSharding(mesh, P(None, mb_axes))
    )
    x = embed_tokens(pparams, tok_mb, cfg)
    x = lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(None, mb_axes))
    )

    def block(x, layer):
        positions = jnp.broadcast_to(jnp.arange(T), x.shape[:2])
        x = _attention_block(x, layer, cfg, None, positions)
        x, _ = _mlp_block(x, layer, cfg, None)
        return x

    def stage_fn(stage_layers, x):
        """Apply this stage's L/pp stacked layers via scan."""

        def body(x, layer):
            y = block(x, layer)
            return y, None

        if cfg.remat:
            body = recomputed(body)
        x, _ = lax.scan(body, x, stage_layers)
        return x

    def pipelined(stages, x_mb):
        # manual over pp: stages arrive [1, L/pp, ...] — drop the stage dim
        stages_loc = jax.tree_util.tree_map(lambda a: a[0], stages)
        idx = lax.axis_index("pp")
        perm = [(i, (i + 1) % pp) for i in range(pp)]
        x_loc = lax.pcast(x_mb, ("pp",), to="varying")
        state = jnp.zeros_like(x_loc[0])
        outputs = jnp.zeros_like(x_loc)

        def tick(carry, t):
            state, outputs = carry
            inject = lax.dynamic_index_in_dim(
                x_loc, jnp.minimum(t, M - 1), 0, keepdims=False
            )
            cur = jnp.where(idx == 0, inject, state)
            out = stage_fn(stages_loc, cur)
            oi = t - (pp - 1)
            write = (idx == pp - 1) & (oi >= 0)
            upd = lax.dynamic_update_index_in_dim(
                outputs, out, jnp.clip(oi, 0, M - 1), 0
            )
            outputs = jnp.where(write, upd, outputs)
            if pp > 1:
                state = lax.ppermute(out, "pp", perm)
            return (state, outputs), None

        (state, outputs), _ = lax.scan(
            tick, (state, outputs), jnp.arange(M + pp - 1)
        )
        # new leading axis concatenated over pp → global [pp, M, mb, T, D]
        return outputs[None]

    outs = shard_map(
        pipelined,
        mesh=mesh,
        in_specs=(P("pp"), P()),
        out_specs=P("pp"),
        # manual over pp ONLY: dp/fsdp/tp stay GSPMD-auto inside the body
        # (without this, shard_map is manual over every mesh axis — stage
        # params would be all-gathered and each dp device would redo the
        # full batch)
        axis_names={"pp"},
    )(pparams["stages"], x)
    y = lax.with_sharding_constraint(
        outs[pp - 1], NamedSharding(mesh, P(None, mb_axes))
    ).reshape(B, T, cfg.model_dim)

    # final norm + head: after the pipeline region, plain GSPMD
    return lm_head(pparams, y, cfg)


def pipeline_loss_fn(
    pparams,
    tokens,
    targets,
    cfg: TransformerConfig,
    mesh,
    num_microbatches,
    virtual: int = 1,
) -> jnp.ndarray:
    logits = pipeline_forward(
        pparams, tokens, cfg, mesh, num_microbatches, virtual=virtual
    )
    return token_nll(logits, targets)


# ---------------------------------------------------------------------------
# 1F1B schedule (manual backward)
# ---------------------------------------------------------------------------
def schedule_occupancy(pp: int, M: int, virtual: int = 1):
    """Pure-Python occupancy model of the (interleaved) 1F1B tick clock —
    the same index algebra the compiled scan uses. Returns
    ``(n_ticks, busy_slots, total_slots)`` where each device contributes
    2 slots per tick (one forward, one backward) and a slot is busy when
    its decomposition lands on a real (microbatch, chunk) pair.

    Bubble fraction = 1 - busy/total = (v+1)(P-1)/(vM + (v+1)(P-1))
    — interleaving with v chunks divides the non-overlapped pipeline
    fill/drain by v relative to the work, the Megatron virtual-pipeline
    effect (bubble (P-1)/(vM+P-1) in their accounting, which counts the
    overlapped last-stage fwd+bwd tick once)."""
    v = virtual
    # v>1: microbatches enter in lane groups of P; a partial last group
    # still takes a full group's ticks (its empty lanes are bubbles)
    m_pad = M if v == 1 else -(-M // pp) * pp
    n_ticks = v * m_pad + (v + 1) * pp - 2
    busy = 0
    for d in range(pp):
        for t in range(n_ticks):
            u = t - d
            if u >= 0:
                i, r = u % pp, u // pp
                if (r // v) * pp + i < M:
                    busy += 1
            wb = t + d - 2 * (pp - 1)
            if wb >= 0:
                i, r = wb % pp, wb // pp
                q = (2 * v - 2 - r) % v
                g = (r - (2 * v - 2 - q)) // v
                if g >= 0 and g * pp + i < M:
                    busy += 1
    return n_ticks, busy, 2 * pp * n_ticks


def _shared_grads(cfg: TransformerConfig, ghead: Any, gemb: Any) -> Any:
    """Combine head/embed grads into the tree ``plan_for_pipeline``'s
    shared plan was built over (the non-stage keys of
    ``stack_pipeline_params``' output): {"embed", "final_norm"
    [, "lm_head"]}, with the tied-embedding head contribution folded
    into the embed leaf."""
    if cfg.tie_embeddings:
        embed = jax.tree_util.tree_map(
            jnp.add, gemb, ghead["embed"]
        )
        return {"embed": embed, "final_norm": ghead["final_norm"]}
    return {
        "embed": gemb,
        "final_norm": ghead["final_norm"],
        "lm_head": ghead["lm_head"],
    }


def pipeline_value_and_grad_1f1b(
    pparams: Any,
    tokens: jnp.ndarray,
    targets: jnp.ndarray,
    cfg: TransformerConfig,
    mesh,
    num_microbatches: int,
    virtual: int = 1,
    sync_plan=None,
) -> Tuple[jnp.ndarray, Any]:
    """(loss, grads) under the 1F1B schedule; grads congruent to pparams.

    ``sync_plan`` (a ``grad_sync.PPSyncPlan``, pp x dp meshes only):
    the explicit per-stage sync path — the region goes manual over
    (pp, dp), each dp rank runs the schedule on its ``mb/dp`` rows
    and accumulates LOCAL grads, and the moment the scan drains each
    stage's grads are bucket-synced over its dp sub-axis inside the
    region (``grad_sync.sync_local_tree``): independent per-stage
    collectives XLA schedules into the fill/drain bubble instead of
    GSPMD's post-drain monolithic all-reduce. Returns
    ``(loss, grads, grad_norm)`` in this mode (the norm falls out of
    the bucket walk).

    Tick clock (``virtual=1``): stage i runs forward of microbatch j at
    tick ``i + j`` and backward of microbatch j at tick ``2(P-1) - i + j``
    (so the last stage does fwd+bwd of the same microbatch in one tick,
    stage 0's backward lags its forward by 2(P-1) ticks — the classic
    1F1B picture). Both hops (activations forward, cotangents backward)
    are next-tick ``ppermute`` neighbours, so one scan over ``M + 2(P-1)``
    ticks runs the whole schedule. Stage inputs wait in a ring buffer of
    ``2P-1`` slots (max residency 2(P-1) ticks < 2P-1); the stage forward
    is recomputed inside ``jax.vjp`` at the backward tick, so nothing
    else is stored.

    **Interleaved 1F1B** (``virtual=v>1``, ref StageInterleaver.py:16 /
    Megatron virtual pipeline stages): device d owns v layer *chunks* —
    global stage s = q*P + d — so each microbatch rides the same P-device
    ring v times. The whole schedule stays one scan because every
    transition remains a single-tick ring hop: forward of (microbatch
    group g, lane i, chunk q) on device d fires at tick
    ``t = g*vP + q*P + i + d`` and its backward at
    ``t + (2v-2-2q)*P + 2(P-1-d)`` — both decompositions are unique per
    (device, tick), so each device runs exactly one chunk-forward and one
    chunk-backward per tick, picking its chunk by ``q = (u div P) mod v``.
    The chunk-(v-1)→chunk-q+1 wraparound rides the SAME ppermute as the
    stage hops (ring edge P-1 → 0). Per-tick work is 1/v of a ``virtual=1``
    stage, so the fill/drain bubble shrinks by ~v at equal microbatch
    count: bubble (v+1)(P-1) slot-pairs against vM of work (see
    ``schedule_occupancy``). Cost: the activation ring buffer grows to
    ``2vP-1`` *chunk* inputs (same bytes per entry), the known memory
    trade of interleaving.

    Only *token ids* ([M, mb, T] int32 — no model-dim factor) cross the
    shard_map boundary per microbatch: the embedding lookup runs inside
    the tick on stage 0 and its backward is a hand-written scatter-add
    into the embedding-grad accumulator (the gather's exact vjp, but
    touching only the mb*T gathered rows per tick instead of
    materializing a dense [vocab, D] cotangent to sum). So per-device
    activation state really is the O(P) ring buffer; nothing activation-
    sized scales with M.

    The loss head (final norm + vocab projection) and the embedding are
    evaluated inside the tick on every stage (SPMD lockstep — only the
    last/first stage's result is kept); the head costs one microbatch
    head per tick, the same order as the stage compute it overlaps with.
    """
    pp = mesh.shape["pp"]
    M = num_microbatches
    v = virtual
    _check_pipeline_cfg(cfg, pp, v)
    if mesh.shape.get("sp", 1) > 1:
        raise ValueError("sp (ring attention) inside pp stages not supported")
    B, T = tokens.shape
    if B % M != 0:
        raise ValueError(f"batch {B} must divide into {M} microbatches")
    mb = B // M
    dp = mesh.shape.get("dp", 1)
    local_dp = sync_plan is not None and dp > 1
    if local_dp and mb % dp:
        raise ValueError(
            f"explicit pp sync needs the microbatch ({mb}) to divide "
            f"over dp={dp} (each rank runs the schedule on its rows)"
        )
    mb_loc = mb // dp if local_dp else mb
    D = cfg.model_dim

    head_params = {"final_norm": pparams["final_norm"]}
    if cfg.tie_embeddings:
        head_params["embed"] = pparams["embed"]
    else:
        head_params["lm_head"] = pparams["lm_head"]

    emb_params = pparams["embed"]
    if mesh.shape.get("tp", 1) > 1:
        # PP×TP composition: the vocab-PARALLEL embedding gather /
        # scatter-add and head projection cannot be partitioned inside
        # the pp-manual scan — XLA's SPMD partitioner hits a subgroup
        # CHECK (spmd_partitioner_util.cc) trying to group the gather's
        # collective across tp while pp is manual. The persistent state
        # keeps its vocab→tp layout (shared with gpipe, whose embed/head
        # run OUTSIDE the shard_map region); here we pin a vocab-
        # replicated copy for the body — one tp all-gather of the
        # embed/head tables per step, amortized over all M microbatches.
        devocab = dict(pipeline_rules(None).rules)
        devocab["vocab"] = None
        devocab_rules = ShardingRules(rules=devocab)
        la = logical_axes(cfg)

        def _pin(tree, axes):
            return jax.tree_util.tree_map(
                lax.with_sharding_constraint,
                tree,
                apply_rules(axes, devocab_rules, mesh),
            )

        emb_params = _pin(emb_params, la["embed"])
        head_params = _pin(
            head_params, {k: la[k] for k in head_params}
        )

    mb_axes = _microbatch_axes(mesh, mb)
    tok = lax.with_sharding_constraint(
        tokens.reshape(M, mb, T),
        NamedSharding(mesh, P(None, mb_axes)),
    )
    tgt = lax.with_sharding_constraint(
        targets.reshape(M, mb, T),
        NamedSharding(mesh, P(None, mb_axes)),
    )

    def block(xx, layer):
        positions = jnp.broadcast_to(jnp.arange(T), xx.shape[:2])
        xx = _attention_block(xx, layer, cfg, None, positions)
        xx, _ = _mlp_block(xx, layer, cfg, None)
        return xx

    def stage_fn(stage_layers, xx):
        def body(xx, layer):
            return block(xx, layer), None

        if cfg.remat:
            body = recomputed(body)
        xx, _ = lax.scan(body, xx, stage_layers)
        return xx

    def head_loss(hp, y, t_mb):
        # /M so per-microbatch cotangents and head grads sum to the grads
        # of the mean-over-microbatches loss
        return token_nll(lm_head(hp, y, cfg), t_mb) / M

    # v>1: microbatches enter in lane groups of P; when M is not a
    # multiple of P the last (partial) group still occupies a full
    # group's ticks — without the pad, the final group's backward slots
    # would fall past the scan end and their gradient contributions
    # silently vanish. v=1 injects at rate 1 (j == t - d), no pad needed.
    m_pad = M if v == 1 else -(-M // pp) * pp
    n_ticks = v * m_pad + (v + 1) * pp - 2
    buf_n = 2 * v * pp - 1

    def pipelined(stages, head_p, emb_p, tok_all, tgt_all):
        stages_loc = jax.tree_util.tree_map(lambda a: a[0], stages)
        idx = lax.axis_index("pp")
        fwd_perm = [(i, (i + 1) % pp) for i in range(pp)]
        bwd_perm = [((i + 1) % pp, i) for i in range(pp)]

        def vary(a):
            return lax.pcast(
                a, ("pp", "dp") if local_dp else ("pp",), to="varying"
            )

        tok_loc = vary(tok_all)
        tgt_loc = vary(tgt_all)
        head_loc = jax.tree_util.tree_map(vary, head_p)
        emb_loc = jax.tree_util.tree_map(vary, emb_p)

        def chunk_of(tree, q_c):
            """Select chunk q's [lc, ...] slice of a [v, lc, ...] tree
            (identity when virtual == 1 — leaves carry no chunk axis)."""
            if v == 1:
                return tree
            return jax.tree_util.tree_map(
                lambda a: lax.dynamic_index_in_dim(
                    a, q_c, 0, keepdims=False
                ),
                tree,
            )

        act_dt = jnp.dtype(cfg.dtype)
        zeros_mb = vary(jnp.zeros((mb_loc, T, D), act_dt))
        carry0 = (
            zeros_mb,  # act: activation arriving from the previous stage
            zeros_mb,  # gin: cotangent arriving from the next stage
            vary(jnp.zeros((buf_n, mb_loc, T, D), act_dt)),
            jax.tree_util.tree_map(jnp.zeros_like, stages_loc),
            jax.tree_util.tree_map(jnp.zeros_like, head_loc),
            jax.tree_util.tree_map(jnp.zeros_like, emb_loc),
            vary(jnp.float32(0.0)),  # loss accumulator (last stage)
        )

        def tick(carry, t):
            act, gin, buf, gstage, ghead, gemb, loss_acc = carry
            last = idx == pp - 1

            # -- forward slot: unique (group g, lane i, chunk q) for this
            # (device, tick): u = g*vP + q*P + i
            u = t - idx
            i_f = u % pp
            r_f = u // pp
            q_f = r_f % v
            jf = (r_f // v) * pp + i_f
            fwd_on = (u >= 0) & (jf < M)
            jf_c = jnp.clip(jf, 0, M - 1)
            q_f_c = jnp.clip(q_f, 0, v - 1)
            tok_mb = lax.dynamic_index_in_dim(
                tok_loc, jf_c, 0, keepdims=False
            )
            inject = embed_tokens({"embed": emb_loc}, tok_mb, cfg)
            x_in = jnp.where(
                (idx == 0) & (q_f == 0), inject.astype(act_dt), act
            )
            y = stage_fn(chunk_of(stages_loc, q_f_c), x_in)
            buf = jnp.where(
                fwd_on,
                lax.dynamic_update_index_in_dim(buf, x_in, t % buf_n, 0),
                buf,
            )

            # -- global last stage (chunk v-1 on device P-1): loss ->
            # d(loss)/dy the same tick (the "1B" of this tick consumes it
            # below: jb == jf and q_b == v-1 there)
            t_mb = lax.dynamic_index_in_dim(
                tgt_loc, jf_c, 0, keepdims=False
            )
            loss_mb, (dhead, dy_head) = jax.value_and_grad(
                head_loss, argnums=(0, 1)
            )(head_loc, y, t_mb)
            loss_on = last & fwd_on & (q_f == v - 1)
            loss_w = loss_on.astype(jnp.float32)
            loss_acc = loss_acc + loss_mb * loss_w
            # mask by scalar multiply, not where-select: a 0/1 scale
            # fuses into the add (matters for the tied-embedding head
            # whose grads are [vocab, D]-dense)
            ghead = jax.tree_util.tree_map(
                lambda g, d: g + d * loss_w.astype(d.dtype), ghead, dhead
            )

            # -- backward slot: wb = g*vP + (2v-2-q)*P + i
            wb = t + idx - 2 * (pp - 1)
            i_b = wb % pp
            r_b = wb // pp
            q_b = (2 * v - 2 - r_b) % v
            g_b = (r_b - (2 * v - 2 - q_b)) // v
            jb = g_b * pp + i_b
            bwd_on = (wb >= 0) & (g_b >= 0) & (jb < M)
            jb_c = jnp.clip(jb, 0, M - 1)
            q_b_c = jnp.clip(q_b, 0, v - 1)
            # the forward of (jb, q_b) on this device ran at
            # t - (2v-2-2q_b)*P - 2(P-1-idx); its input sits at that
            # tick's ring-buffer slot
            t_f_saved = (
                t - (2 * v - 2 - 2 * q_b_c) * pp - 2 * (pp - 1 - idx)
            )
            x_saved = lax.dynamic_index_in_dim(
                buf, t_f_saved % buf_n, 0, keepdims=False
            )
            dy = jnp.where(
                last & (q_b == v - 1), dy_head.astype(x_saved.dtype), gin
            )
            chunk_b = chunk_of(stages_loc, q_b_c)
            _, svjp = jax.vjp(stage_fn, chunk_b, x_saved)
            dstage, dxi = svjp(dy)
            bwd_w = bwd_on.astype(jnp.float32)
            if v == 1:
                gstage = jax.tree_util.tree_map(
                    lambda g, d: g + d * bwd_w.astype(d.dtype),
                    gstage,
                    dstage,
                )
            else:
                # accumulate into chunk q_b's rows (a masked-off tick
                # writes back chunk + 0 — a no-op)
                gstage = jax.tree_util.tree_map(
                    lambda g, d: lax.dynamic_update_index_in_dim(
                        g,
                        lax.dynamic_index_in_dim(
                            g, q_b_c, 0, keepdims=False
                        )
                        + d * bwd_w.astype(d.dtype),
                        q_b_c,
                        0,
                    ),
                    gstage,
                    dstage,
                )

            # -- embedding backward (global stage 0 = chunk 0, device 0):
            # the gather's vjp is a scatter-add touching only the mb*T
            # gathered rows — never a dense [vocab, D] cotangent
            emb_w = ((idx == 0) & (q_b == 0) & bwd_on).astype(jnp.float32)
            tok_jb = lax.dynamic_index_in_dim(
                tok_loc, jb_c, 0, keepdims=False
            )
            contrib = dxi.astype(jnp.float32) * emb_w
            gtok = gemb["tokens"].at[tok_jb.reshape(-1)].add(
                contrib.reshape(-1, D).astype(gemb["tokens"].dtype)
            )
            new_gemb = dict(gemb)
            new_gemb["tokens"] = gtok
            if "positions" in gemb:
                new_gemb["positions"] = (
                    gemb["positions"]
                    .at[:T]
                    .add(contrib.sum(0).astype(gemb["positions"].dtype))
                )
            gemb = new_gemb

            # -- next-tick hops: activations one stage forward, cotangents
            # one stage back
            if pp > 1:
                act = lax.ppermute(y, "pp", fwd_perm)
                gin = lax.ppermute(dxi, "pp", bwd_perm)
            return (act, gin, buf, gstage, ghead, gemb, loss_acc), None

        (_, _, _, gstage, ghead, gemb, loss_acc), _ = lax.scan(
            tick, carry0, jnp.arange(n_ticks)
        )
        # only one stage holds each of these (masked zeros elsewhere), so
        # psum over pp is selection, not averaging
        loss_out = lax.psum(loss_acc, "pp")
        ghead_out = jax.tree_util.tree_map(
            lambda g: lax.psum(g, "pp"), ghead
        )
        gemb_out = jax.tree_util.tree_map(
            lambda g: lax.psum(g, "pp"), gemb
        )
        if local_dp:
            # the explicit per-stage sync, INSIDE the manual region:
            # this stage's dp sub-axis collectives are issued the
            # moment its grads are complete — independent ops the
            # scheduler packs into the drain bubble
            from dlrover_tpu.parallel.grad_sync import sync_local_tree

            shared = _shared_grads(cfg, ghead_out, gemb_out)
            gstage_s, ss_st = sync_local_tree(
                gstage, sync_plan.stage_plan
            )
            shared_s, ss_sh = sync_local_tree(
                shared, sync_plan.shared_plan
            )
            gnorm = jnp.sqrt(lax.psum(ss_st, "pp") + ss_sh)
            gstage_out = jax.tree_util.tree_map(
                lambda g: g[None], gstage_s
            )
            return (
                gstage_out,
                shared_s,
                lax.pmean(loss_out, "dp"),
                gnorm,
            )
        gstage_out = jax.tree_util.tree_map(lambda g: g[None], gstage)
        return gstage_out, ghead_out, gemb_out, loss_out

    if local_dp:
        gstage, shared, loss, gnorm = shard_map(
            pipelined,
            mesh=mesh,
            in_specs=(P("pp"), P(), P(), P(None, "dp"), P(None, "dp")),
            out_specs=(P("pp"), P(), P(), P()),
            axis_names={"pp", "dp"},
            check_vma=False,
        )(pparams["stages"], head_params, emb_params, tok, tgt)
        grads = dict(shared)
        grads["stages"] = gstage
        return loss, grads, gnorm
    gstage, ghead, gemb, loss = shard_map(
        pipelined,
        mesh=mesh,
        in_specs=(P("pp"), P(), P(), P(), P()),
        out_specs=(P("pp"), P(), P(), P()),
        axis_names={"pp"},
    )(pparams["stages"], head_params, emb_params, tok, tgt)

    if mesh.shape.get("tp", 1) > 1:
        # pin the grad OUTPUTS to the same vocab-replicated layout: the
        # optimizer downstream holds vocab→tp moments, and without this
        # boundary XLA propagates that layout back into the scan carry —
        # recreating exactly the unpartitionable gather/scatter inside
        # the loop that the input pin above avoided
        ghead = _pin(ghead, {k: la[k] for k in ghead})
        gemb = _pin(gemb, la["embed"])

    grads = {
        "stages": gstage,
        "final_norm": ghead["final_norm"],
        "embed": gemb,
    }
    if cfg.tie_embeddings:
        grads["embed"] = jax.tree_util.tree_map(
            jnp.add, grads["embed"], ghead["embed"]
        )
    else:
        grads["lm_head"] = ghead["lm_head"]
    return loss, grads


def pipeline_value_and_grad_gpipe_sync(
    pparams: Any,
    tokens: jnp.ndarray,
    targets: jnp.ndarray,
    cfg: TransformerConfig,
    mesh,
    num_microbatches: int,
    sync_plan,
) -> Tuple[jnp.ndarray, Any, jnp.ndarray]:
    """(loss, grads, grad_norm) under the GPipe schedule with the
    explicit per-stage dp sync (pp x dp meshes, ``PPSyncPlan``).

    The region is fully manual over (pp, dp): each dp rank runs the
    same M+P-1 tick rotation ``pipeline_forward`` uses — embedding
    and head INSIDE the region on its ``mb/dp`` rows — and
    reverse-mode AD through the scan-of-ppermute yields the backward
    rotation, producing per-rank LOCAL grads (no GSPMD dp psum).
    Each stage's grads are then bucket-synced over its dp sub-axis
    in the region (``grad_sync.sync_local_tree``): per-stage
    independent reduce-scatter/all-gather pairs the scheduler can
    start during the drain, instead of one post-drain monolithic
    all-reduce over the whole tree."""
    pp = mesh.shape["pp"]
    dp = mesh.shape.get("dp", 1)
    M = num_microbatches
    _check_pipeline_cfg(cfg, pp, 1)
    if mesh.shape.get("sp", 1) > 1:
        raise ValueError("sp (ring attention) inside pp stages not supported")
    B, T = tokens.shape
    if B % M != 0:
        raise ValueError(f"batch {B} must divide into {M} microbatches")
    mb = B // M
    if dp > 1 and mb % dp:
        raise ValueError(
            f"explicit pp sync needs the microbatch ({mb}) to divide "
            f"over dp={dp}"
        )
    mb_loc = mb // max(dp, 1)
    D = cfg.model_dim

    head_params = {"final_norm": pparams["final_norm"]}
    if cfg.tie_embeddings:
        head_params["embed"] = pparams["embed"]
    else:
        head_params["lm_head"] = pparams["lm_head"]
    emb_params = pparams["embed"]

    mb_axes = _microbatch_axes(mesh, mb)
    tok = lax.with_sharding_constraint(
        tokens.reshape(M, mb, T),
        NamedSharding(mesh, P(None, mb_axes)),
    )
    tgt = lax.with_sharding_constraint(
        targets.reshape(M, mb, T),
        NamedSharding(mesh, P(None, mb_axes)),
    )

    def block(xx, layer):
        positions = jnp.broadcast_to(jnp.arange(T), xx.shape[:2])
        xx = _attention_block(xx, layer, cfg, None, positions)
        xx, _ = _mlp_block(xx, layer, cfg, None)
        return xx

    def stage_fn(stage_layers, xx):
        def body(xx, layer):
            return block(xx, layer), None

        if cfg.remat:
            body = recomputed(body)
        xx, _ = lax.scan(body, xx, stage_layers)
        return xx

    def pipelined(stages, head_p, emb_p, tok_all, tgt_all):
        from dlrover_tpu.parallel.grad_sync import sync_local_tree

        stages_loc = jax.tree_util.tree_map(lambda a: a[0], stages)
        idx = lax.axis_index("pp")
        perm = [(i, (i + 1) % pp) for i in range(pp)]

        def vary(a):
            return lax.pcast(a, ("pp", "dp"), to="varying")

        tok_loc = vary(tok_all)
        tgt_loc = vary(tgt_all)
        head_loc = jax.tree_util.tree_map(vary, head_p)
        emb_loc = jax.tree_util.tree_map(vary, emb_p)
        act_dt = jnp.dtype(cfg.dtype)
        last = idx == pp - 1

        def local_loss(stages_l, head_l, emb_l):
            x_all = embed_tokens(
                {"embed": emb_l}, tok_loc, cfg
            ).astype(act_dt)  # [M, mb_loc, T, D]
            carry0 = (
                jnp.zeros((mb_loc, T, D), act_dt),
                jnp.zeros((M, mb_loc, T, D), act_dt),
            )

            def tick(carry, t):
                st, outputs = carry
                inject = lax.dynamic_index_in_dim(
                    x_all, jnp.minimum(t, M - 1), 0, keepdims=False
                )
                cur = jnp.where(idx == 0, inject, st)
                out = stage_fn(stages_l, cur)
                oi = t - (pp - 1)
                write = last & (oi >= 0)
                upd = lax.dynamic_update_index_in_dim(
                    outputs, out, jnp.clip(oi, 0, M - 1), 0
                )
                outputs = jnp.where(write, upd, outputs)
                if pp > 1:
                    st = lax.ppermute(out, "pp", perm)
                else:
                    st = out
                return (st, outputs), None

            (_, outputs), _ = lax.scan(
                tick, carry0, jnp.arange(M + pp - 1)
            )
            y = outputs.reshape(M * mb_loc, T, D)
            t_flat = tgt_loc.reshape(M * mb_loc, T)
            loss_local = token_nll(lm_head(head_l, y, cfg), t_flat)
            # only the last stage's outputs are real. The psum that
            # shares the scalar happens OUTSIDE the AD below: psum
            # transposes to psum, which would hand every rank a
            # pp-scaled cotangent; seeding ct=1 on each rank's MASKED
            # local loss is the correct seed (non-last ranks' zeros
            # contribute nothing, and their params' influence arrives
            # through the ppermute transpose)
            return loss_local * last.astype(jnp.float32)

        loss_l, (dstage, dhead, demb) = jax.value_and_grad(
            local_loss, argnums=(0, 1, 2)
        )(stages_loc, head_loc, emb_loc)
        loss = lax.psum(loss_l, "pp")  # selection, not averaging
        # head grads live only on the last stage, embed-gather grads
        # only on stage 0 (masked zeros elsewhere): psum = selection
        dhead = jax.tree_util.tree_map(
            lambda g: lax.psum(g, "pp"), dhead
        )
        demb = jax.tree_util.tree_map(
            lambda g: lax.psum(g, "pp"), demb
        )
        shared = _shared_grads(cfg, dhead, demb)
        gstage_s, ss_st = sync_local_tree(dstage, sync_plan.stage_plan)
        shared_s, ss_sh = sync_local_tree(
            shared, sync_plan.shared_plan
        )
        gnorm = jnp.sqrt(lax.psum(ss_st, "pp") + ss_sh)
        gstage_out = jax.tree_util.tree_map(
            lambda g: g[None], gstage_s
        )
        return gstage_out, shared_s, lax.pmean(loss, "dp"), gnorm

    gstage, shared, loss, gnorm = shard_map(
        pipelined,
        mesh=mesh,
        in_specs=(P("pp"), P(), P(), P(None, "dp"), P(None, "dp")),
        out_specs=(P("pp"), P(), P(), P()),
        axis_names={"pp", "dp"},
        check_vma=False,
    )(pparams["stages"], head_params, emb_params, tok, tgt)
    grads = dict(shared)
    grads["stages"] = gstage
    return loss, grads, gnorm


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------
def pipeline_state_shardings(
    cfg: TransformerConfig, mesh, tx, rules=None, virtual: int = 1
) -> TrainState:
    pp = mesh.shape["pp"]
    p_sh = pipeline_param_shardings(cfg, mesh, pp, rules, virtual)
    replicated = NamedSharding(mesh, P())
    params_shape = jax.eval_shape(
        lambda: stack_pipeline_params(
            init_params(jax.random.PRNGKey(0), cfg), pp, virtual
        )
    )
    opt_sh = opt_state_shardings(params_shape, p_sh, tx, mesh)
    return TrainState(step=replicated, params=p_sh, opt_state=opt_sh)


def init_pipeline_state(
    key, cfg: TransformerConfig, mesh, tx, rules=None, virtual: int = 1
) -> Tuple[TrainState, TrainState]:
    """Initialize stacked pipeline params/opt state directly into their
    shardings (stage s's rows materialize on stage s's devices)."""
    pp = mesh.shape["pp"]
    _check_pipeline_cfg(cfg, pp, virtual)
    sh = pipeline_state_shardings(cfg, mesh, tx, rules, virtual)

    def _init(key):
        return stack_pipeline_params(init_params(key, cfg), pp, virtual)

    params = jax.jit(_init, out_shardings=sh.params)(key)
    opt_state = jax.jit(tx.init, out_shardings=sh.opt_state)(params)
    step = jax.device_put(jnp.zeros((), jnp.int32), sh.step)
    return TrainState(step=step, params=params, opt_state=opt_state), sh


def build_pipeline_train_step(
    cfg: TransformerConfig,
    mesh,
    tx,
    num_microbatches: int,
    rules: Optional[ShardingRules] = None,
    donate: bool = True,
    schedule: str = "gpipe",
    virtual_stages: int = 2,
    comm_overlap: bool = False,
    grad_bucket_mb: int = 4,
    grad_slices: int = 1,
):
    """jitted (state, tokens, targets) → (state, metrics) over pp.

    ``schedule``: "gpipe" (AD backward, O(M) activation footprint),
    "1f1b" (manual backward, O(P) footprint), or "interleaved"
    (1F1B with ``virtual_stages`` chunks per device — smaller bubble,
    O(vP) footprint; state must come from
    ``init_pipeline_state(..., virtual=virtual_stages)``).

    ``comm_overlap``: the explicit per-stage gradient sync for
    pp x dp meshes (``grad_sync.plan_for_pipeline``) — each stage's
    dp sync runs as independent bucketed collectives scheduled into
    the pipeline bubble instead of GSPMD's post-drain monolithic
    all-reduce; all three schedules are covered. Meshes that don't
    qualify (pp composed with fsdp/tp/sp/ep, or dp=1) fall back to
    the GSPMD schedule with a once-per-mesh log naming the axes.
    ``grad_slices`` threads a hybrid dp axis's DCN slice count
    (two-level dp legs)."""
    import optax

    if schedule not in ("gpipe", "1f1b", "interleaved"):
        raise ValueError(f"unknown pipeline schedule {schedule!r}")
    virtual = virtual_stages if schedule == "interleaved" else 1
    if schedule == "interleaved" and virtual < 2:
        raise ValueError("interleaved schedule needs virtual_stages >= 2")

    sync_plan = None
    if comm_overlap:
        from dlrover_tpu.parallel.grad_sync import (
            note_gspmd_fallback,
            plan_for_pipeline,
        )

        from dlrover_tpu.parallel.grad_sync import fallback_reason

        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        sync_plan = plan_for_pipeline(
            cfg,
            sizes,
            grad_bucket_mb=grad_bucket_mb,
            slices=grad_slices,
            schedule=schedule,
            virtual=virtual,
        )
        if sync_plan is None:
            # the mesh may QUALIFY (kind "pp") while the MODEL cannot
            # pipeline at this degree — fallback_reason is empty then,
            # so name the actual cause instead of logging a reasonless
            # fallback for a "supported" mesh
            reason = fallback_reason(sizes) or (
                f"num_layers={cfg.num_layers} does not divide into "
                f"pp={sizes.get('pp')} x virtual={virtual} stages "
                f"(or the model cannot pipeline at all)"
            )
            note_gspmd_fallback(sizes, reason=reason)

    from dlrover_tpu.ops.quantized_optim import in_place_entry

    # as ``models/train.build_train_step``: None on any mesh of stages
    update_and_apply = in_place_entry(
        tx, devices=mesh.size, donate=donate
    )

    def train_step(state: TrainState, tokens, targets):
        gnorm = None
        if sync_plan is not None:
            if schedule in ("1f1b", "interleaved"):
                loss, grads, gnorm = pipeline_value_and_grad_1f1b(
                    state.params, tokens, targets, cfg, mesh,
                    num_microbatches, virtual=virtual,
                    sync_plan=sync_plan,
                )
            else:
                loss, grads, gnorm = pipeline_value_and_grad_gpipe_sync(
                    state.params, tokens, targets, cfg, mesh,
                    num_microbatches, sync_plan,
                )
        elif schedule in ("1f1b", "interleaved"):
            loss, grads = pipeline_value_and_grad_1f1b(
                state.params, tokens, targets, cfg, mesh,
                num_microbatches, virtual=virtual,
            )
        else:

            def lf(p):
                return pipeline_loss_fn(
                    p, tokens, targets, cfg, mesh, num_microbatches
                )

            loss, grads = jax.value_and_grad(lf)(state.params)
        if update_and_apply is not None:
            new_params, new_opt = update_and_apply(
                grads, state.opt_state, state.params
            )
        else:
            updates, new_opt = tx.update(
                grads, state.opt_state, state.params
            )
            new_params = optax.apply_updates(state.params, updates)
        return (
            TrainState(
                step=state.step + 1, params=new_params, opt_state=new_opt
            ),
            {
                "loss": loss,
                "grad_norm": (
                    gnorm
                    if gnorm is not None
                    else optax.global_norm(grads)
                ),
            },
        )

    donate_argnums = (0,) if donate else ()
    return jax.jit(train_step, donate_argnums=donate_argnums)
