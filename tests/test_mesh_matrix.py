"""ISSUE 13 — finishing the mesh matrix: explicit bucketed sync for
pp x dp (bubble-scheduled per-stage), dp x ep (manual all-to-all region
+ capacity rebalance), composed dp x fsdp x tp (3D), and the
micro-batch rebalance alternative to idling surplus ranks.

Tier-1 keeps the unit-sync + HLO-structure + pricing tests; the full
parity A/Bs ride the slow tier per the PR-8 budget convention.
"""

import re
from dataclasses import replace as dc_replace

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import tiny
from dlrover_tpu.models.train import (
    build_train_step,
    init_sharded_state,
    pad_batch_rows,
    pad_row_weights,
    shard_batch,
)
from dlrover_tpu.parallel.grad_sync import (
    EPSyncPlan,
    PPSyncPlan,
    fallback_reason,
    plan_for_mesh,
    plan_for_pipeline,
    resolve_plan,
    resolve_sync_mode,
    sync_grads,
)
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh


def _fp32_tiny(**kw):
    return dc_replace(
        tiny(), dtype="float32", param_dtype="float32", **kw
    )


def _batch(cfg, batch=8, seq=16, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)


# -- the gate ---------------------------------------------------------------
class TestMeshMatrixGate:
    def test_new_kinds_resolve(self):
        m = resolve_sync_mode({"pp": 2, "dp": 4})
        assert m is not None and m.kind == "pp" and m.pp == 2
        m = resolve_sync_mode({"dp": 2, "ep": 2})
        assert m is not None and m.kind == "ep" and m.ep == 2
        m = resolve_sync_mode({"dp": 2, "fsdp": 2, "tp": 2})
        assert m is not None and m.kind == "3d" and m.model_shard == 2

    def test_fsdp_sp_without_tp_falls_back_gracefully(self):
        """Review regression: dp x fsdp x sp (tp=1) has no param dim
        for the 3d region to localize — it must fall back to GSPMD
        (pre-ISSUE-13 behavior), not crash plan construction."""
        from dlrover_tpu.accel.strategy import Strategy

        sizes = {"dp": 2, "fsdp": 2, "sp": 2}
        assert resolve_sync_mode(sizes) is None
        assert "sp shards no params" in fallback_reason(sizes)
        assert resolve_plan(
            tiny(num_layers=1),
            Strategy(
                mesh=MeshConfig(dp=2, fsdp=2, sp=2), comm_overlap=True
            ),
        ) is None  # and no ValueError
        # 4D with tp still qualifies (sp rides as a manual bystander)
        m = resolve_sync_mode({"dp": 2, "fsdp": 2, "tp": 2, "sp": 2})
        assert m is not None and m.kind == "3d"

    def test_fallback_reason_names_exact_axes(self):
        """Satellite bug fix: the remaining fallbacks must name the
        axes that disqualified them, not say 'unsupported mesh'."""
        r = fallback_reason({"pp": 2, "ep": 2, "dp": 2})
        assert "pp x ep" in r
        r = fallback_reason({"pp": 2, "tp": 2, "fsdp": 2, "dp": 2})
        assert "pp x" in r and "fsdp" in r and "tp" in r
        r = fallback_reason({"ep": 2, "tp": 2, "dp": 2})
        assert "ep x tp" in r
        # a qualifying mesh has no reason
        assert fallback_reason({"dp": 2, "ep": 2}) == ""

    def test_fallback_dedup_keys_on_full_axis_dict(self, monkeypatch):
        """Two meshes sharing the >1 axes but differing in the full
        dict must BOTH log (the dedup keys on the whole axis dict)."""
        from dlrover_tpu.parallel import grad_sync

        monkeypatch.setattr(
            grad_sync, "_GSPMD_FALLBACK_LOGGED", set()
        )
        calls = []
        monkeypatch.setattr(
            "dlrover_tpu.common.log.default_logger.info",
            lambda msg, *a, **k: calls.append(str(msg)),
        )
        grad_sync.note_gspmd_fallback({"pp": 2, "ep": 2, "dp": 2})
        grad_sync.note_gspmd_fallback({"pp": 2, "ep": 2, "dp": 4})
        grad_sync.note_gspmd_fallback({"pp": 2, "ep": 2, "dp": 2})
        assert len(calls) == 2  # third is the dup of the first
        assert all("pp x ep" in c for c in calls)


# -- 3D (dp x fsdp x tp) -----------------------------------------------------
class Test3DSync:
    def test_unit_sync_is_exact_mean(self):
        cfg = _fp32_tiny(num_layers=1)
        mesh = build_mesh(
            MeshConfig(dp=2, fsdp=2, tp=2), devices=jax.devices()[:8]
        )
        plan = plan_for_mesh(cfg, mesh, grad_bucket_mb=1)
        assert plan is not None and plan.three_d
        from dlrover_tpu.models.transformer import init_params

        shapes = jax.eval_shape(
            lambda: init_params(jax.random.PRNGKey(0), cfg)
        )
        rng = np.random.default_rng(0)
        leaves, treedef = jax.tree_util.tree_flatten(shapes)
        stacked = [
            rng.standard_normal((4,) + tuple(l.shape)).astype(
                np.float32
            )
            for l in leaves
        ]
        tree = jax.tree_util.tree_unflatten(
            treedef, [jnp.asarray(a) for a in stacked]
        )
        synced, res, gnorm = jax.jit(
            lambda t: sync_grads(t, mesh, plan)
        )(tree)
        assert res is None and gnorm is None  # caller computes norm
        for a, s in zip(stacked, jax.tree_util.tree_leaves(synced)):
            np.testing.assert_allclose(
                np.asarray(s), a.mean(axis=0), atol=2e-6
            )

    def test_wire_bytes_tp_adds_no_dp_leg_bytes(self):
        """Acceptance: the 3D plan's wire bytes are <= the PR-8
        dp x fsdp plan's — tp only shrinks the payload to 1/tp."""
        cfg = _fp32_tiny(num_layers=1)
        mesh3 = build_mesh(
            MeshConfig(dp=2, fsdp=2, tp=2), devices=jax.devices()[:8]
        )
        mesh2 = build_mesh(
            MeshConfig(dp=2, fsdp=2), devices=jax.devices()[:4]
        )
        p3 = plan_for_mesh(cfg, mesh3, grad_bucket_mb=64)
        p2 = plan_for_mesh(cfg, mesh2, grad_bucket_mb=64)
        assert p3.explicit_wire_bytes() <= p2.explicit_wire_bytes()
        # and still strictly below ITS own monolithic fallback
        assert p3.explicit_wire_bytes() < p3.gspmd_allreduce_bytes()

    def test_hlo_zero_rs_count_unchanged_when_tp_added(self):
        """Acceptance HLO structure: per bucket, the 3D step carries
        the SAME reduce-scatter count as the dp x fsdp (ZeRO) step —
        the fsdp scatter leg plus the dp RS leg, nothing more."""
        cfg = _fp32_tiny(num_layers=1)
        tx = optax.adamw(1e-2)
        x = _batch(cfg)

        def rs_per_bucket(mc, n):
            mesh = build_mesh(mc, devices=jax.devices()[:n])
            state, _ = init_sharded_state(
                jax.random.PRNGKey(0), cfg, mesh, tx
            )
            step = build_train_step(
                cfg, mesh, tx, donate=False, comm_overlap=True,
                grad_bucket_mb=64,
            )
            b = shard_batch({"x": x, "y": x}, mesh)
            txt = step.lower(state, b["x"], b["y"]).as_text()
            plan = plan_for_mesh(cfg, mesh, grad_bucket_mb=64)
            n_rs = len(re.findall(r"reduce.scatter", txt))
            return n_rs / plan.num_buckets

        assert rs_per_bucket(
            MeshConfig(dp=2, fsdp=2, tp=2), 8
        ) == rs_per_bucket(MeshConfig(dp=2, fsdp=2), 4)

    # tier-1 twin: test_unit_sync_is_exact_mean
    @pytest.mark.slow
    def test_train_step_parity_with_gspmd(self):
        cfg = _fp32_tiny()
        tx = optax.adamw(1e-2)
        x = _batch(cfg, batch=8, seq=32)

        def run(comm_overlap):
            mesh = build_mesh(
                MeshConfig(dp=2, fsdp=2, tp=2),
                devices=jax.devices()[:8],
            )
            state, _ = init_sharded_state(
                jax.random.PRNGKey(0), cfg, mesh, tx
            )
            step = build_train_step(
                cfg, mesh, tx, donate=False,
                comm_overlap=comm_overlap, grad_bucket_mb=1,
            )
            b = shard_batch({"x": x, "y": x}, mesh)
            for _ in range(4):
                state, m = step(state, b["x"], b["y"])
            return float(m["loss"])

        # 1e-5 gate on tp-containing meshes (the PR-8 modes stay
        # bitwise; the tp matmul partitioning differs inside vs
        # outside the manual region)
        assert abs(run(False) - run(True)) < 1e-5


# -- pp x dp (bubble-scheduled per-stage sync) -------------------------------
class TestPPSync:
    def test_plan_structure(self):
        cfg = _fp32_tiny()  # 2 layers / pp=2 -> 1 layer per stage
        plan = plan_for_pipeline(cfg, {"pp": 2, "dp": 4})
        assert isinstance(plan, PPSyncPlan)
        assert plan.pp == 2 and plan.dp == 4
        assert plan.stage_plan.num_buckets >= 1
        assert plan.shared_plan.num_buckets >= 1
        assert plan.compress == "none"
        # strategy-level resolve returns the same shape of plan
        from dlrover_tpu.accel.strategy import Strategy

        p2 = resolve_plan(
            cfg,
            Strategy(
                mesh=MeshConfig(pp=2, dp=4), comm_overlap=True
            ),
        )
        assert isinstance(p2, PPSyncPlan)

    def test_plan_rejects_unpipelineable_model(self):
        assert plan_for_pipeline(
            tiny(num_layers=1), {"pp": 2, "dp": 4}
        ) is None

    def test_hlo_per_stage_rs_with_stage_local_groups(self):
        """Acceptance HLO structure: one RS/AG pair per bucket whose
        replica groups stay WITHIN a stage's dp sub-axis (size dp, no
        cross-stage barrier mixing stages into one collective)."""
        cfg = _fp32_tiny()
        tx = optax.adamw(1e-2)
        mesh = build_mesh(MeshConfig(pp=2, dp=4))
        from dlrover_tpu.parallel.pipeline import (
            build_pipeline_train_step,
            init_pipeline_state,
        )

        state, _ = init_pipeline_state(
            jax.random.PRNGKey(0), cfg, mesh, tx
        )
        step = build_pipeline_train_step(
            cfg, mesh, tx, 2, donate=False, schedule="gpipe",
            comm_overlap=True, grad_bucket_mb=64,
        )
        x = jnp.asarray(_batch(cfg))
        txt = step.lower(state, x, x).as_text()
        plan = plan_for_pipeline(cfg, {"pp": 2, "dp": 4})
        n_rs = len(re.findall(r"reduce.scatter", txt))
        assert n_rs == plan.num_buckets
        # every RS keeps stage-local dp groups: 4 ranks per group
        for groups in re.findall(
            r"reduce.scatter[^\n]*replica_groups=\{(\{[^}]*\}[^}]*)\}",
            txt,
        ):
            for g in re.findall(r"\{([0-9, ]+)\}", groups):
                assert len(g.split(",")) == 4, groups

    # tier-1 keeps the plan's structure and the lowered groups
    # (test_hlo_per_stage_rs_with_stage_local_groups)
    @pytest.mark.slow
    @pytest.mark.parametrize("sched", ["gpipe", "1f1b", "interleaved"])
    def test_parity_with_plain_dp_reference(self, sched):
        """The explicit pp step (fully-manual region — it RUNS on this
        jaxlib where the partial-manual GSPMD pipeline needs
        PartitionId support) matches a plain dp=8 reference step over
        4 optimizer steps."""
        from dlrover_tpu.models.train import TrainState
        from dlrover_tpu.models.transformer import init_params
        from dlrover_tpu.parallel.pipeline import (
            build_pipeline_train_step,
            pipeline_state_shardings,
            stack_pipeline_params,
        )

        cfg = _fp32_tiny(num_layers=4)
        tx = optax.adamw(1e-2)
        x = _batch(cfg, batch=8, seq=32)
        params0 = init_params(jax.random.PRNGKey(0), cfg)

        mesh_ref = build_mesh(MeshConfig(dp=8))
        state_r = TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params0,
            opt_state=tx.init(params0),
        )
        step_r = build_train_step(cfg, mesh_ref, tx, donate=False)
        b = shard_batch({"x": x, "y": x}, mesh_ref)
        for _ in range(4):
            state_r, mr = step_r(state_r, b["x"], b["y"])

        mesh = build_mesh(MeshConfig(pp=2, dp=4))
        virtual = 2 if sched == "interleaved" else 1
        sh = pipeline_state_shardings(cfg, mesh, tx, virtual=virtual)
        stacked = jax.device_put(
            stack_pipeline_params(params0, 2, virtual), sh.params
        )
        state = TrainState(
            step=jnp.zeros((), jnp.int32),
            params=stacked,
            opt_state=jax.device_put(tx.init(stacked), sh.opt_state),
        )
        step = build_pipeline_train_step(
            cfg, mesh, tx, 2, donate=False, schedule=sched,
            comm_overlap=True, grad_bucket_mb=1,
        )
        xj = jnp.asarray(x)
        for _ in range(4):
            state, m = step(state, xj, xj)
        assert abs(float(m["loss"]) - float(mr["loss"])) < 1e-5
        assert abs(
            float(m["grad_norm"]) - float(mr["grad_norm"])
        ) < 1e-4


# -- dp x ep ----------------------------------------------------------------
class TestEPSync:
    def test_plan_structure(self):
        cfg = _fp32_tiny(num_experts=2)
        mesh = build_mesh(
            MeshConfig(dp=2, ep=2), devices=jax.devices()[:4]
        )
        plan = plan_for_mesh(cfg, mesh, grad_bucket_mb=1)
        assert isinstance(plan, EPSyncPlan)
        assert plan.ep == 2 and plan.dp == 2
        # the expert FFN leaves (w_up/w_down and, the tiny model being
        # SwiGLU, w_gate of its one moe layer) are ep-local; the router
        # and dense layers are not
        assert len(plan.expert_leaf_ids) == 3
        assert all(d == 0 for d in plan.expert_leaf_dims)
        # per-device wire: expert leaves at 1/ep
        assert plan.raw_bytes < plan.expert_plan.raw_bytes * 2 + (
            plan.dense_plan.raw_bytes + 1
        )

    def test_hlo_two_alltoalls_per_layer_each_way(self):
        """Acceptance HLO structure: the explicit ep train step runs
        exactly 2 dispatch/combine all-to-alls per MoE layer in the
        forward and their 2 transposes in the backward."""
        cfg = _fp32_tiny(num_experts=2)
        tx = optax.adamw(1e-2)
        mesh = build_mesh(
            MeshConfig(dp=2, ep=2), devices=jax.devices()[:4]
        )
        state, _ = init_sharded_state(
            jax.random.PRNGKey(0), cfg, mesh, tx
        )
        step = build_train_step(
            cfg, mesh, tx, donate=False, comm_overlap=True,
            grad_bucket_mb=1,
        )
        x = _batch(cfg)
        b = shard_batch({"x": x, "y": x}, mesh)
        txt = step.lower(state, b["x"], b["y"]).as_text()
        n_moe = sum(
            1
            for i in range(cfg.num_layers)
            if i % cfg.moe_every == cfg.moe_every - 1
        )
        assert len(re.findall(r"all.to.all", txt)) == 4 * n_moe

    def test_grad_accum_gate_is_shared(self):
        """Review regression: the ep+grad_accum exclusion must hold at
        the STRATEGY gate too (resolve_plan), or the trainer reports
        an explicit path the step never runs."""
        from dlrover_tpu.accel.strategy import Strategy

        cfg = _fp32_tiny(num_experts=2)
        s = Strategy(
            mesh=MeshConfig(dp=2, ep=2), comm_overlap=True,
            grad_accum=2,
        )
        assert resolve_plan(cfg, s) is None
        assert resolve_plan(
            cfg, dc_replace(s, grad_accum=1)
        ) is not None

    # tier-1 twin: test_hlo_two_alltoalls_per_layer_each_way
    @pytest.mark.slow
    def test_train_step_parity_with_gspmd(self):
        cfg = _fp32_tiny(num_experts=2)
        tx = optax.adamw(1e-2)
        x = _batch(cfg, batch=8, seq=32)

        def run(comm_overlap):
            mesh = build_mesh(
                MeshConfig(dp=2, ep=2), devices=jax.devices()[:4]
            )
            state, _ = init_sharded_state(
                jax.random.PRNGKey(0), cfg, mesh, tx
            )
            step = build_train_step(
                cfg, mesh, tx, donate=False,
                comm_overlap=comm_overlap, grad_bucket_mb=1,
            )
            b = shard_batch({"x": x, "y": x}, mesh)
            for _ in range(4):
                state, m = step(state, b["x"], b["y"])
            return float(m["loss"])

        assert abs(run(False) - run(True)) < 1e-5


# -- capacity rebalancing ----------------------------------------------------
class TestCapacityRebalance:
    def _skewed_logits(self, T=512, E=4, seed=0):
        """Zipf-ish routing: expert 0 gets ~55% of the tokens."""
        rng = np.random.default_rng(seed)
        logits = rng.standard_normal((T, E)).astype(np.float32)
        logits[:, 0] += 1.5
        return jnp.asarray(logits)

    def _drop_rate(self, logits, capacity, expert_caps=None):
        from dlrover_tpu.parallel.moe import topk_gating

        E = logits.shape[1]
        _, _, _, _, stats = topk_gating(
            logits, E, capacity, k=1,
            expert_caps=(
                jnp.asarray(expert_caps, jnp.float32)
                if expert_caps is not None
                else None
            ),
            return_stats=True,
        )
        return float(stats["drop"])

    def test_rebalanced_caps_reduce_overflow_drops(self):
        """Acceptance: on a skewed workload the re-split capacity
        drops strictly fewer tokens than the static uniform split."""
        from dlrover_tpu.parallel.moe import CapacityRebalancer

        T, E = 512, 4
        logits = self._skewed_logits(T, E)
        base = int(1.25 * T / E)
        static_drop = self._drop_rate(logits, base)
        reb = CapacityRebalancer(E, capacity_factor=1.25, ema=0.0)
        from dlrover_tpu.parallel.moe import topk_gating

        _, _, _, _, stats = topk_gating(
            logits, E, base, k=1, return_stats=True
        )
        reb.observe(np.asarray(stats["load"]))
        caps = reb.splits(T)
        reb_drop = self._drop_rate(logits, max(caps), caps)
        assert static_drop > 0  # the skew actually overflows
        assert reb_drop < static_drop

    def test_splits_conserve_budget_and_clamp(self):
        from dlrover_tpu.parallel.moe import CapacityRebalancer

        reb = CapacityRebalancer(4, capacity_factor=1.0, ema=0.0)
        reb.observe([0.97, 0.01, 0.01, 0.01])
        caps = reb.splits(64)
        base = 16
        assert max(caps) <= int(np.ceil(2.0 * base))  # boost clamp
        assert min(caps) >= max(1, round(0.25 * base))  # floor clamp

    def test_expert_caps_flow_through_config(self):
        """cfg.capacity_splits reaches the gating: with starved caps
        the drop rate rises vs the uniform default."""
        cfg = _fp32_tiny(num_experts=2, capacity_splits=(1, 1))
        mesh = build_mesh(
            MeshConfig(dp=2, ep=2), devices=jax.devices()[:4]
        )
        tx = optax.adamw(1e-2)
        state, _ = init_sharded_state(
            jax.random.PRNGKey(0), cfg, mesh, tx
        )
        step = build_train_step(cfg, mesh, tx, donate=False)
        x = _batch(cfg)
        b = shard_batch({"x": x, "y": x}, mesh)
        _, m = step(state, b["x"], b["y"])
        assert float(m["moe_drop_rate"]) > 0.5  # caps of 1 starve
        assert np.asarray(m["moe_expert_load"]).shape == (2,)


# -- dry-runner pricing (satellite: PR-6-style model sensitivity) ------------
class TestMeshMatrixPricing:
    def _exposed(self, s, cfg):
        from dlrover_tpu.accel.dry_runner import (
            DryRunReport,
            _analytic_estimate,
            _comm_estimate,
        )

        r = DryRunReport(strategy=s, ok=True)
        _analytic_estimate(r, cfg, 8, 16, None)
        _comm_estimate(r, cfg, 8, 16, None)
        return r.comm_exposed_s

    def test_ep_alltoall_priced_from_link_model(self, monkeypatch):
        """Halving the ICI rate inflates the MoE all-to-all term —
        the ep pricing is model-driven, not constant-driven (the PR-6
        sensitivity property), and fallback-vs-explicit pricing still
        diverges on the grad-sync term."""
        from dlrover_tpu.accel.strategy import Strategy
        from dlrover_tpu.parallel import topology

        cfg = tiny(num_layers=2, num_experts=2)
        s = Strategy(mesh=MeshConfig(dp=2, ep=2), comm_overlap=True)
        fp = topology.device_fingerprint()

        def with_rate(ici_gbps):
            topology.set_link_model(
                topology.LinkModel(
                    ici_gbps=ici_gbps, source="measured",
                    fingerprint=fp,
                )
            )
            return self._exposed(s, cfg)

        try:
            fast, slow = with_rate(200.0), with_rate(1.0)
        finally:
            topology.reset_link_model()
        assert slow > fast > 0

    def test_pp_bubble_absorbs_wire_vs_fallback(self):
        """The explicit pp strategy's exposed comm is strictly below
        its GSPMD fallback twin's: the per-stage sync rides the
        fill/drain bubble, the monolithic post-drain all-reduce is
        fully exposed."""
        from dlrover_tpu.accel.strategy import Strategy

        cfg = tiny(num_layers=2)
        s = Strategy(
            mesh=MeshConfig(pp=2, dp=4), num_microbatches=2,
            comm_overlap=True,
        )
        explicit = self._exposed(s, cfg)
        fallback = self._exposed(
            dc_replace(s, comm_overlap=False), cfg
        )
        assert explicit < fallback


# -- micro-batch rebalance ---------------------------------------------------
class TestMicroBatchRebalance:
    def test_pad_row_weights_mean_identity(self):
        w = pad_row_weights(6, 8)
        nll = np.arange(8.0)
        # weighted mean over padded rows == plain mean over real rows
        assert abs(
            float((w * nll).mean()) - float(nll[:6].mean())
        ) < 1e-6
        assert (w[6:] == 0).all()

    def test_pad_batch_rows(self):
        x = np.ones((6, 4), np.int32)
        xp = pad_batch_rows(x, 9)
        assert xp.shape == (9, 4)
        assert (xp[6:] == 0).all()

    def test_padded_step_matches_unpadded_gradients(self):
        """dp6 on 16 real + 2 pad rows trains identically to dp4 on
        the 16 real rows (the pads carry loss weight 0)."""
        cfg = _fp32_tiny(num_layers=1)
        tx = optax.adamw(1e-2)
        x = _batch(cfg, batch=16)

        mesh4 = build_mesh(MeshConfig(dp=4), devices=jax.devices()[:4])
        s4, _ = init_sharded_state(
            jax.random.PRNGKey(0), cfg, mesh4, tx
        )
        step4 = build_train_step(cfg, mesh4, tx, donate=False)
        b4 = shard_batch({"x": x, "y": x}, mesh4)
        for _ in range(2):
            s4, m4 = step4(s4, b4["x"], b4["y"])

        mesh6 = build_mesh(MeshConfig(dp=6), devices=jax.devices()[:6])
        s6, _ = init_sharded_state(
            jax.random.PRNGKey(0), cfg, mesh6, tx
        )
        step6 = build_train_step(
            cfg, mesh6, tx, donate=False, batch_pad=2,
            comm_overlap=True, grad_bucket_mb=1,
        )
        xp = pad_batch_rows(x, 18)
        b6 = shard_batch({"x": xp, "y": xp}, mesh6)
        for _ in range(2):
            s6, m6 = step6(s6, b6["x"], b6["y"])
        # not bitwise: dp4-GSPMD vs dp6-explicit group reductions
        # differently — but the pads contribute exactly nothing
        assert abs(float(m4["loss"]) - float(m6["loss"])) < 1e-5

    def test_pricing_prefers_fewer_rows_per_rank(self):
        """The dry-runner compares the world-dependent terms: 3 rows
        on 6 ranks beats 4 rows on 4 ranks once the row term is
        calibrated to real step seconds."""
        from dlrover_tpu.accel.strategy import Strategy
        from dlrover_tpu.accel.dry_runner import (
            price_rebalance_options,
        )

        cfg = _fp32_tiny(num_layers=1)
        idle = Strategy(mesh=MeshConfig(dp=4), comm_overlap=True)
        reb = Strategy(
            mesh=MeshConfig(dp=6), comm_overlap=True, batch_pad=2
        )
        cur = Strategy(mesh=MeshConfig(dp=8), comm_overlap=True)
        idle_s, reb_s = price_rebalance_options(
            cfg, 16, 32, idle, reb,
            measured_step_s=5e-3, current_strategy=cur,
        )
        assert reb_s < idle_s

    def test_strategy_for_picks_rebalance(self):
        """ElasticTrainer._strategy_for on a 6-of-8 count returns a
        rebalanced all-ranks strategy when the pricing favors it
        (exercised without building a trainer — the method only
        touches cfg/strategy state)."""
        from dlrover_tpu.accel.strategy import Strategy
        from dlrover_tpu.trainer.elastic.trainer import (
            ElasticTrainer,
            TrainerConfig,
        )

        class _Fake:
            tcfg = TrainerConfig(batch_size=16, seq_len=32)
            _model_cfg = _fp32_tiny(num_layers=1)
            _step_time_sum = 5e-3
            _step_time_n = 1

            class accel:
                strategy = Strategy(
                    mesh=MeshConfig(dp=8), comm_overlap=True
                )

        fake = _Fake()
        fake._strategy_for_exact = (
            lambda n: ElasticTrainer._strategy_for_exact(fake, n)
        )
        fake._rebalanced_strategy_for = (
            lambda n: ElasticTrainer._rebalanced_strategy_for(fake, n)
        )
        out = ElasticTrainer._strategy_for(fake, 6)
        assert out.mesh.num_devices == 6
        assert out.batch_pad == 2
        # and with the knob off, the old idle-ranks degrade wins
        fake.tcfg = dc_replace(fake.tcfg, mb_rebalance=False)
        out = ElasticTrainer._strategy_for(fake, 6)
        assert out.mesh.num_devices == 4 and out.batch_pad == 0

    def test_eval_batches_trim_instead_of_pad(self):
        """Review regression: the eval loss takes no row weights, so
        a rebalanced strategy must TRIM eval batches to the largest
        shardable count (unbiased) rather than feeding zero-pad rows
        into the mean NLL."""
        from dlrover_tpu.accel.strategy import Strategy
        from dlrover_tpu.trainer.elastic.trainer import ElasticTrainer

        class _Fake:
            class accel:
                strategy = Strategy(
                    mesh=MeshConfig(dp=6), batch_pad=2
                )

        fake = _Fake()
        batch = {
            "x": np.ones((16, 4), np.int32),
            "y": np.ones((16, 4), np.int32),
        }
        seen = {}

        def _shard(b, mesh):
            seen.update(b)
            return b

        import dlrover_tpu.trainer.elastic.trainer as tmod

        orig = tmod.shard_batch
        tmod.shard_batch = _shard
        try:
            fake.mesh = None
            ElasticTrainer._device_batch(fake, batch, for_eval=True)
            assert seen["x"].shape[0] == 12  # 16 -> 12 (divides 6)
            seen.clear()
            ElasticTrainer._device_batch(fake, batch)
            assert seen["x"].shape[0] == 18  # padded for training
        finally:
            tmod.shard_batch = orig

    def test_moe_models_refuse_batch_pad(self):
        """Pad rows would flow through the router and shift the
        balance/z aux losses even at loss weight 0 — MoE models keep
        the idle-ranks degradation (the step builder refuses, the
        trainer's rebalance candidate opts out)."""
        cfg = _fp32_tiny(num_experts=2)
        mesh = build_mesh(
            MeshConfig(dp=2, ep=2), devices=jax.devices()[:4]
        )
        with pytest.raises(ValueError, match="gating aux"):
            build_train_step(
                cfg, mesh, optax.adamw(1e-2), donate=False,
                batch_pad=2,
            )

    def test_strategy_serialization_roundtrips_batch_pad(self):
        from dlrover_tpu.accel.strategy import Strategy

        s = Strategy(mesh=MeshConfig(dp=6), batch_pad=2)
        assert Strategy.from_json(s.to_json()).batch_pad == 2
        assert "mbpad2" in s.describe()
