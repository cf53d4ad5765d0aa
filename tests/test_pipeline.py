"""Pipeline parallelism numerics: pp>1 must match the single-device model.

Parity: the reference validates its PiPPy pipe compiler against unpiped
execution (atorch pipe tests); here the contract is exact-math equality
(fp32 tiny config) between the GPipe-staged model and the plain forward.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import (
    build_train_step,
    init_params,
    init_sharded_state,
    loss_fn,
    shard_batch,
    tiny,
)
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.pipeline import (
    build_pipeline_train_step,
    init_pipeline_state,
    pipeline_forward,
    pipeline_loss_fn,
    stack_pipeline_params,
    unstack_pipeline_params,
)


# the pipeline's partial-manual shard_map (manual over pp, GSPMD-auto
# over dp/fsdp/tp inside the body) needs SPMD PartitionId support that
# old jaxlibs reject at run time ("UNIMPLEMENTED: PartitionId
# instruction is not supported for SPMD partitioning"); gate every
# device-executing pp test on the version instead of paying minutes of
# compile just to watch the backend refuse
pp_needs_modern_xla = pytest.mark.skipif(
    jax.__version_info__ < (0, 5, 0),
    reason="pp partial-manual shard_map needs PartitionId SPMD support",
)

def _batch(cfg, batch=8, seq=16, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    y = rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    return x, y


def test_stack_roundtrip():
    cfg = tiny(num_layers=4)
    params = init_params(jax.random.PRNGKey(0), cfg)
    stacked = stack_pipeline_params(params, 2)
    rt = unstack_pipeline_params(stacked, cfg)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(a, b), params, rt
    )


@pp_needs_modern_xla
@pytest.mark.parametrize("pp,mb", [(2, 4), (4, 2), (2, 8)])
def test_pipeline_forward_matches_plain(pp, mb):
    from dlrover_tpu.models.transformer import forward

    cfg = tiny(num_layers=4)
    mesh = build_mesh(MeshConfig(pp=pp, dp=8 // pp))
    params = init_params(jax.random.PRNGKey(0), cfg)
    x, _ = _batch(cfg)

    ref_logits, _ = jax.jit(lambda p, t: forward(p, t, cfg))(params, x)
    stacked = stack_pipeline_params(params, pp)
    got = jax.jit(
        lambda p, t: pipeline_forward(p, t, cfg, mesh, num_microbatches=mb)
    )(stacked, x)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref_logits), rtol=2e-5, atol=2e-5
    )


@pp_needs_modern_xla
def test_pipeline_forward_virtual_layout_parity():
    """pipeline_forward(virtual=2) must read the interleaved [pp, v, lc]
    param layout correctly (in-graph restack to contiguous stages) —
    this is the eval path for interleaved-trained states (ADVICE r3:
    eval used to scan the chunked layout as [pp, L/pp])."""
    from dlrover_tpu.models.transformer import forward

    cfg = tiny(num_layers=4)
    mesh = build_mesh(MeshConfig(pp=2, dp=4))
    params = init_params(jax.random.PRNGKey(0), cfg)
    x, _ = _batch(cfg)

    ref_logits, _ = jax.jit(lambda p, t: forward(p, t, cfg))(params, x)
    stacked = stack_pipeline_params(params, 2, virtual=2)
    got = jax.jit(
        lambda p, t: pipeline_forward(
            p, t, cfg, mesh, num_microbatches=4, virtual=2
        )
    )(stacked, x)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref_logits), rtol=2e-5, atol=2e-5
    )


@pp_needs_modern_xla
def test_pipeline_grads_match_plain():
    cfg = tiny(num_layers=4)
    pp, mb = 2, 4
    mesh = build_mesh(MeshConfig(pp=pp, dp=4))
    params = init_params(jax.random.PRNGKey(0), cfg)
    x, y = _batch(cfg)

    ref_loss, ref_grads = jax.jit(
        jax.value_and_grad(lambda p: loss_fn(p, x, y, cfg))
    )(params)
    stacked = stack_pipeline_params(params, pp)
    pl_loss, pl_grads = jax.jit(
        jax.value_and_grad(
            lambda p: pipeline_loss_fn(p, x, y, cfg, mesh, mb)
        )
    )(stacked)
    np.testing.assert_allclose(
        float(pl_loss), float(ref_loss), rtol=1e-5, atol=1e-6
    )
    ref_grads_stacked = stack_pipeline_params(ref_grads, pp)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        ),
        pl_grads,
        ref_grads_stacked,
    )


@pp_needs_modern_xla
def test_pipeline_training_matches_plain():
    """A few optimizer steps staged over pp=2 track the unpiped loss."""
    cfg = tiny(num_layers=2)
    pp, mb = 2, 4
    mesh = build_mesh(MeshConfig(pp=pp, dp=2, fsdp=2))
    tx = optax.adamw(1e-2)

    ref_mesh = build_mesh(MeshConfig(dp=8))
    ref_state, _ = init_sharded_state(jax.random.PRNGKey(0), cfg, mesh=ref_mesh, tx=tx)
    ref_step = build_train_step(cfg, ref_mesh, tx, donate=False)

    state, _ = init_pipeline_state(jax.random.PRNGKey(0), cfg, mesh, tx)
    step_fn = build_pipeline_train_step(cfg, mesh, tx, mb, donate=False)

    x, y = _batch(cfg)
    bx = shard_batch({"x": x, "y": y}, ref_mesh)
    losses_ref, losses_pp = [], []
    for _ in range(3):
        ref_state, m_ref = ref_step(ref_state, bx["x"], bx["y"])
        state, m_pp = step_fn(state, x, y)
        losses_ref.append(float(m_ref["loss"]))
        losses_pp.append(float(m_pp["loss"]))
    np.testing.assert_allclose(losses_pp, losses_ref, rtol=1e-4, atol=1e-5)
    assert losses_pp[-1] < losses_pp[0]


@pp_needs_modern_xla
@pytest.mark.parametrize("pp,mb", [(2, 4), (4, 4)])
def test_1f1b_grads_match_plain(pp, mb):
    """The manual 1F1B backward must produce the same gradients as AD on
    the unpiped model (fp32 tiny config => tight tolerance)."""
    from dlrover_tpu.parallel.pipeline import pipeline_value_and_grad_1f1b

    cfg = tiny(num_layers=4)
    mesh = build_mesh(MeshConfig(pp=pp, dp=8 // pp))
    params = init_params(jax.random.PRNGKey(0), cfg)
    x, y = _batch(cfg)

    ref_loss, ref_grads = jax.jit(
        jax.value_and_grad(lambda p: loss_fn(p, x, y, cfg))
    )(params)
    stacked = stack_pipeline_params(params, pp)
    loss, grads = jax.jit(
        lambda p: pipeline_value_and_grad_1f1b(p, x, y, cfg, mesh, mb)
    )(stacked)
    np.testing.assert_allclose(
        float(loss), float(ref_loss), rtol=1e-5, atol=1e-6
    )
    ref_grads_stacked = stack_pipeline_params(ref_grads, pp)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        ),
        grads,
        ref_grads_stacked,
    )


@pp_needs_modern_xla
def test_1f1b_grads_tied_embeddings():
    """Tied-embedding configs route head grads back into the embedding
    table (two contributions summed)."""
    from dlrover_tpu.parallel.pipeline import pipeline_value_and_grad_1f1b

    cfg = tiny(num_layers=2, tie_embeddings=True, rope=False)
    pp, mb = 2, 2
    mesh = build_mesh(MeshConfig(pp=pp, dp=4))
    params = init_params(jax.random.PRNGKey(1), cfg)
    x, y = _batch(cfg)

    ref_loss, ref_grads = jax.jit(
        jax.value_and_grad(lambda p: loss_fn(p, x, y, cfg))
    )(params)
    stacked = stack_pipeline_params(params, pp)
    loss, grads = jax.jit(
        lambda p: pipeline_value_and_grad_1f1b(p, x, y, cfg, mesh, mb)
    )(stacked)
    np.testing.assert_allclose(
        float(loss), float(ref_loss), rtol=1e-5, atol=1e-6
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        ),
        grads,
        stack_pipeline_params(ref_grads, pp),
    )


@pp_needs_modern_xla
def test_1f1b_training_matches_gpipe():
    """Both schedules drive identical optimizer trajectories."""
    cfg = tiny(num_layers=2)
    pp, mb = 2, 4
    mesh = build_mesh(MeshConfig(pp=pp, dp=2, fsdp=2))
    tx = optax.adamw(1e-2)

    s_g, _ = init_pipeline_state(jax.random.PRNGKey(0), cfg, mesh, tx)
    s_1, _ = init_pipeline_state(jax.random.PRNGKey(0), cfg, mesh, tx)
    step_g = build_pipeline_train_step(
        cfg, mesh, tx, mb, donate=False, schedule="gpipe"
    )
    step_1 = build_pipeline_train_step(
        cfg, mesh, tx, mb, donate=False, schedule="1f1b"
    )
    x, y = _batch(cfg)
    for _ in range(3):
        s_g, m_g = step_g(s_g, x, y)
        s_1, m_1 = step_1(s_1, x, y)
        np.testing.assert_allclose(
            float(m_1["loss"]), float(m_g["loss"]), rtol=1e-5, atol=1e-6
        )
    # 3 AdamW steps amplify last-ulp grad differences through m/rsqrt(v)
    # for elements whose momentum crosses zero; the strict checks are the
    # per-step loss equality above and the one-step grad tests
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-2, atol=1e-3
        ),
        s_1.params,
        s_g.params,
    )


@pp_needs_modern_xla
@pytest.mark.parametrize(
    "schedule,v", [("gpipe", 1), ("1f1b", 1), ("interleaved", 2)]
)
def test_pipeline_composes_with_tp(schedule, v):
    """True 3D parallelism: pp×tp×dp on one mesh (review r3 missing#2,
    the repo's answer to the reference's DS-3D
    ds_3d_parallel_optimization.py). The pipeline body is manual over pp
    ONLY — tp must stay GSPMD-auto inside the stages. Proof obligations:
    (a) stage params are REALLY tp-sharded (not silently replicated),
    (b) the sharded 3D trajectory exactly tracks the dense dp8 one."""
    cfg = tiny(num_layers=4)
    mesh = build_mesh(MeshConfig(pp=2, tp=2, dp=2))
    tx = optax.adamw(1e-2)

    state, shardings = init_pipeline_state(
        jax.random.PRNGKey(0), cfg, mesh, tx, virtual=v
    )
    # (a) attention heads sharded over tp on every stage
    wq_spec = shardings.params["stages"]["attn"]["wq"].spec
    assert "tp" in tuple(wq_spec), wq_spec
    wq_shard = state.params["stages"]["attn"]["wq"].sharding
    assert not wq_shard.is_fully_replicated

    step = build_pipeline_train_step(
        cfg, mesh, tx, num_microbatches=4, donate=False,
        schedule=schedule, virtual_stages=v,
    )

    ref_mesh = build_mesh(MeshConfig(dp=8))
    ref_state, _ = init_sharded_state(
        jax.random.PRNGKey(0), cfg, mesh=ref_mesh, tx=tx
    )
    ref_step = build_train_step(cfg, ref_mesh, tx, donate=False)

    x, y = _batch(cfg)
    bx = shard_batch({"x": x, "y": y}, ref_mesh)
    for _ in range(3):
        ref_state, m_ref = ref_step(ref_state, bx["x"], bx["y"])
        state, m = step(state, x, y)
        # (b) fp32 exact-math tolerance: 3D sharding must not change
        # the numbers, only the layout
        np.testing.assert_allclose(
            float(m["loss"]), float(m_ref["loss"]), rtol=1e-5, atol=1e-6
        )


def test_pipeline_rejects_bad_configs():
    cfg = tiny(num_layers=3)
    mesh = build_mesh(MeshConfig(pp=2, dp=4))
    params = stack_pipeline_params(
        init_params(jax.random.PRNGKey(0), tiny(num_layers=4)), 2
    )
    x, _ = _batch(cfg)
    with pytest.raises(ValueError):
        pipeline_forward(params, x, cfg, mesh, 4)
    with pytest.raises(ValueError):
        pipeline_forward(
            params, x, tiny(num_layers=4, num_experts=2), mesh, 4
        )


@pp_needs_modern_xla
def test_pp_bytes_accessed_does_not_blow_up():
    """The pipeline region boundaries carry explicit sharding constraints
    (embedding output born in microbatch layout, divisibility-aware
    microbatch axes) precisely so the SPMD partitioner never falls back to
    "involuntary full rematerialization" — which would show up as a
    bytes-accessed blowup of the pp step vs the pp=1 step."""
    cfg = tiny(num_layers=4)
    tx = optax.adamw(1e-3)
    x, y = _batch(cfg, batch=8, seq=16)

    def compiled_bytes(step, state):
        c = step.lower(state, x, y).compile()
        return float(c.cost_analysis().get("bytes accessed", 0.0))

    mesh1 = build_mesh(MeshConfig(dp=8))
    s1, _ = init_sharded_state(jax.random.PRNGKey(0), cfg, mesh1, tx)
    b1 = compiled_bytes(build_train_step(cfg, mesh1, tx, donate=False), s1)

    mesh2 = build_mesh(MeshConfig(pp=2, dp=2, fsdp=2))
    s2, _ = init_pipeline_state(jax.random.PRNGKey(0), cfg, mesh2, tx)
    b2 = compiled_bytes(
        build_pipeline_train_step(
            cfg, mesh2, tx, num_microbatches=4, donate=False
        ),
        s2,
    )
    assert b1 > 0 and b2 > 0
    # microbatched pipelining re-reads stage params once per microbatch,
    # so some multiple is expected; a full-remat fallback (replicating
    # [B,T,D] activations at every boundary) is an order of magnitude
    assert b2 < 6 * b1, (b1, b2)


@pp_needs_modern_xla
@pytest.mark.parametrize("pp,v,mb", [(2, 2, 4), (2, 3, 6), (4, 2, 8)])
def test_interleaved_grads_match_plain(pp, v, mb):
    """Interleaved 1F1B (v virtual chunks per device) must produce the
    same loss and gradients as AD on the unpiped model."""
    from dlrover_tpu.parallel.pipeline import pipeline_value_and_grad_1f1b

    cfg = tiny(num_layers=pp * v)
    mesh = build_mesh(MeshConfig(pp=pp, dp=8 // pp))
    params = init_params(jax.random.PRNGKey(0), cfg)
    x, y = _batch(cfg, batch=mb * 2)

    ref_loss, ref_grads = jax.jit(
        jax.value_and_grad(lambda p: loss_fn(p, x, y, cfg))
    )(params)
    stacked = stack_pipeline_params(params, pp, virtual=v)
    loss, grads = jax.jit(
        lambda p: pipeline_value_and_grad_1f1b(
            p, x, y, cfg, mesh, mb, virtual=v
        )
    )(stacked)
    np.testing.assert_allclose(
        float(loss), float(ref_loss), rtol=1e-5, atol=1e-6
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        ),
        grads,
        stack_pipeline_params(ref_grads, pp, virtual=v),
    )


def test_interleaved_stack_roundtrip():
    cfg = tiny(num_layers=8)
    params = init_params(jax.random.PRNGKey(0), cfg)
    stacked = stack_pipeline_params(params, 2, virtual=2)
    # chunk layout: [pp, v, lc]; global stage s = q*pp + d
    wq0 = params["layers"][0]["attn"]["wq"]      # stage 0 -> [d=0, q=0]
    wq3 = params["layers"][5]["attn"]["wq"]      # layer 5: stage 2=d0q1? lc=2
    np.testing.assert_array_equal(
        np.asarray(stacked["stages"]["attn"]["wq"][0, 0, 0]), np.asarray(wq0)
    )
    # layer 5 -> global stage 5//2=2 -> d=0, q=1, slot 1
    np.testing.assert_array_equal(
        np.asarray(stacked["stages"]["attn"]["wq"][0, 1, 1]), np.asarray(wq3)
    )
    rt = unstack_pipeline_params(stacked, cfg, virtual=2)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(a, b), params, rt
    )


@pp_needs_modern_xla
def test_interleaved_training_step():
    """End-to-end train step with schedule='interleaved' on a pp*dp*fsdp
    mesh, including optimizer update over the chunked param layout."""
    cfg = tiny(num_layers=4)
    mesh = build_mesh(MeshConfig(pp=2, dp=2, fsdp=2))
    tx = optax.adamw(1e-3)
    state, _ = init_pipeline_state(
        jax.random.PRNGKey(0), cfg, mesh, tx, virtual=2
    )
    step = build_pipeline_train_step(
        cfg, mesh, tx, num_microbatches=4, schedule="interleaved",
        virtual_stages=2,
    )
    x, y = _batch(cfg)
    losses = []
    for _ in range(3):
        state, m = step(state, x, y)
        losses.append(float(m["loss"]))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]


def test_interleaved_schedule_smaller_bubble():
    """At M == P, interleaving v chunks must strictly reduce the idle
    (bubble) fraction vs plain 1F1B — the whole point of virtual stages
    (bubble (v+1)(P-1) slot-pairs against vM of work)."""
    from dlrover_tpu.parallel.pipeline import schedule_occupancy

    P = M = 4
    fracs = []
    for v in (1, 2, 4):
        n_ticks, busy, total = schedule_occupancy(P, M, virtual=v)
        # every unit of work appears exactly once: vM fwd + vM bwd per dev
        assert busy == 2 * v * M * P, (v, busy)
        fracs.append(1 - busy / total)
    assert fracs[1] < fracs[0]
    assert fracs[2] < fracs[1]


@pp_needs_modern_xla
def test_interleaved_partial_microbatch_group():
    """M not a multiple of P: the final (partial) lane group's backward
    slots must still run — without the tick-count pad their gradient
    contributions silently vanish (loss would still match!)."""
    from dlrover_tpu.parallel.pipeline import pipeline_value_and_grad_1f1b

    cfg = tiny(num_layers=4)
    pp, v, M = 2, 2, 3
    mesh = build_mesh(MeshConfig(pp=pp, dp=4))
    params = init_params(jax.random.PRNGKey(0), cfg)
    x, y = _batch(cfg, batch=6)

    ref_loss, ref_grads = jax.jit(
        jax.value_and_grad(lambda p: loss_fn(p, x, y, cfg))
    )(params)
    loss, grads = jax.jit(
        lambda p: pipeline_value_and_grad_1f1b(
            p, x, y, cfg, mesh, M, virtual=v
        )
    )(stack_pipeline_params(params, pp, virtual=v))
    np.testing.assert_allclose(
        float(loss), float(ref_loss), rtol=1e-5, atol=1e-6
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        ),
        grads,
        stack_pipeline_params(ref_grads, pp, virtual=v),
    )
