"""Hybrid-mesh overlap sync (ISSUE 8): the explicit bucketed gradient
sync extended beyond pure-DP meshes — ZeRO-style reduce-scatter into
the fsdp shard layout on dp x fsdp, bucketed dp-axis sync under the
GSPMD tp/sp submesh on dp x tp, int8+error-feedback and two-level
ICI/DCN composing on the dp axis, and the mode-aware cost model."""

import re
from dataclasses import replace as dc_replace

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.accel.strategy import Strategy
from dlrover_tpu.models import tiny
from dlrover_tpu.models.train import (
    build_train_step,
    init_sharded_state,
    shard_batch,
)
from dlrover_tpu.parallel.grad_sync import (
    ensure_residual,
    plan_buckets,
    plan_for_mesh,
    resolve_plan,
    resolve_sync_mode,
    sync_grads,
    zero_residual,
)
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh


def _fp32_tiny(**kw):
    return dc_replace(
        tiny(num_layers=1), dtype="float32", param_dtype="float32", **kw
    )


def _batch(cfg, batch=8, seq=16, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)


# -- the gate ---------------------------------------------------------------
class TestSyncModeGate:
    def test_kinds(self):
        assert resolve_sync_mode({"dp": 4}).kind == "dp"
        m = resolve_sync_mode({"dp": 2, "fsdp": 2})
        assert m.kind == "zero" and m.fsdp == 2 and m.dp == 2
        # pure fsdp is the classic ZeRO case (dp may be 1)
        assert resolve_sync_mode({"fsdp": 4}).kind == "zero"
        m = resolve_sync_mode({"dp": 2, "tp": 2})
        assert m.kind == "tp" and m.auto_axes == ("tp",)
        assert m.model_shard == 2
        m = resolve_sync_mode({"dp": 2, "sp": 2})
        assert m.kind == "tp" and m.auto_axes == ("sp",)
        # sp shards activations, not params: grads are replicated
        # over sp, so it must NOT discount the wire payload
        assert m.model_shard == 1

    def test_unsupported_meshes(self):
        assert resolve_sync_mode({"dp": 1}) is None
        assert resolve_sync_mode({"tp": 4}) is None  # no data axis
        # ISSUE 13: pp x dp, dp x ep and 3D now resolve (see
        # tests/test_mesh_matrix.py); the remaining exotica stay GSPMD
        assert resolve_sync_mode({"pp": 2, "dp": 1}) is None
        assert resolve_sync_mode({"ep": 2, "dp": 1}) is None
        assert resolve_sync_mode({"dp": 2, "pp": 2, "ep": 2}) is None
        assert resolve_sync_mode({"dp": 2, "ep": 2, "fsdp": 2}) is None
        assert resolve_sync_mode({"dp": 2, "pp": 2, "tp": 2}) is None

    def test_tp_plan_forces_compress_off(self):
        s = Strategy(
            mesh=MeshConfig(dp=2, tp=2),
            comm_overlap=True,
            grad_compress="int8",
        )
        plan = resolve_plan(tiny(num_layers=1), s)
        assert plan is not None and plan.compress == "none"

    def test_tp_plan_forces_flat_dp(self):
        """A hybrid dp axis on a tp mesh must NOT plan two-level: the
        tp path syncs with one flat psum per bucket, so a two-level
        plan would mis-size auto buckets and break the legs probe."""
        s = Strategy(
            mesh=MeshConfig(
                dp=4, tp=2, dcn_axes=("dp",), slices=2
            ),
            comm_overlap=True,
        )
        plan = resolve_plan(tiny(num_layers=1), s)
        assert plan is not None and not plan.two_level

    def test_plan_buckets_rejects_bad_combos(self):
        shapes = [jax.ShapeDtypeStruct((16,), jnp.float32)]
        with pytest.raises(ValueError, match="fsdp leg"):
            plan_buckets(shapes, dp=2, auto_axes=("tp",), fsdp=2)
        with pytest.raises(ValueError, match="int8"):
            plan_buckets(
                shapes, dp=2, auto_axes=("tp",), compress="int8"
            )
        # the fully-manual 3d kind composes fsdp with auto tp, but
        # demands the localized-leaf metadata
        with pytest.raises(ValueError, match="3d plan needs"):
            plan_buckets(
                shapes, dp=2, auto_axes=("tp",), fsdp=2, kind="3d"
            )


# -- wire accounting --------------------------------------------------------
class TestWireAccounting:
    def _zero_plan(self, dp=2, fsdp=2, compress="none", slices=1):
        shapes = [jax.ShapeDtypeStruct((4096,), jnp.float32)] * 4
        return plan_buckets(
            shapes, dp=dp, fsdp=fsdp, compress=compress,
            slices=slices, bucket_bytes=1 << 20,
        )

    def test_zero_strictly_below_gspmd_allreduce(self):
        for dp, fsdp in [(1, 4), (2, 2), (4, 2)]:
            plan = self._zero_plan(dp=dp, fsdp=fsdp)
            assert 0 < plan.explicit_wire_bytes() < (
                plan.gspmd_allreduce_bytes()
            ), (dp, fsdp)

    def test_pure_fsdp_is_half_the_allreduce(self):
        # the classic ZeRO claim: RS alone is half of RS+AG
        plan = self._zero_plan(dp=1, fsdp=4)
        assert plan.explicit_wire_bytes() == (
            plan.gspmd_allreduce_bytes() // 2
        )

    def test_padding_covers_both_scatter_stages(self):
        shapes = [jax.ShapeDtypeStruct((101,), jnp.float32)]
        plan = plan_buckets(shapes, dp=3, fsdp=2)
        assert plan.buckets[0].padded % 6 == 0

    def test_zero_int8_residual_covers_the_chunk(self):
        plan = self._zero_plan(dp=2, fsdp=2, compress="int8")
        b = plan.buckets[0]
        assert plan.shard_elems(b) == b.padded // 2
        # two-level narrows it to the slice-local DCN shard of the
        # chunk
        plan2 = self._zero_plan(
            dp=4, fsdp=2, compress="int8", slices=2
        )
        b2 = plan2.buckets[0]
        assert plan2.shard_elems(b2) == b2.padded // 2 // 2

    def test_tp_plan_divides_by_model_shard(self):
        shapes = [jax.ShapeDtypeStruct((4096,), jnp.float32)]
        flat = plan_buckets(shapes, dp=2)
        tp = plan_buckets(
            shapes, dp=2, auto_axes=("tp",), model_shard=2
        )
        assert tp.explicit_wire_bytes() * 2 == flat.explicit_wire_bytes()
        assert tp.gspmd_allreduce_bytes() * 2 == (
            flat.gspmd_allreduce_bytes()
        )


# -- unit-level sync numerics ----------------------------------------------
class TestZeroSyncGrads:
    def _stacked(self, mesh, plan, tree):
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh = NamedSharding(mesh, P(plan.stack_axes))
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(a, sh), tree
        )

    def test_fp32_zero_sync_is_exact_mean(self):
        mesh = build_mesh(
            MeshConfig(dp=2, fsdp=2), devices=jax.devices()[:4]
        )
        rng = np.random.default_rng(0)
        tree = {
            "w": rng.standard_normal((4, 64, 3)).astype(np.float32),
            "b": rng.standard_normal((4, 37)).astype(np.float32),
        }
        shapes = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), tree
        )
        plan = plan_buckets(shapes, dp=2, fsdp=2, bucket_bytes=256)
        assert plan.num_buckets > 1
        stacked = self._stacked(mesh, plan, tree)
        synced, res, gnorm = jax.jit(
            lambda t: sync_grads(t, mesh, plan)
        )(stacked)
        ref = jax.tree_util.tree_map(lambda a: a.mean(axis=0), tree)
        for k in ("w", "b"):
            np.testing.assert_allclose(
                np.asarray(synced[k]), ref[k], atol=1e-6
            )
        assert res is None
        ref_norm = float(
            np.sqrt(sum(float((ref[k] ** 2).sum()) for k in ref))
        )
        assert abs(float(gnorm) - ref_norm) < 1e-4

    def test_zero_int8_error_bounded_and_residual_carries(self):
        mesh = build_mesh(
            MeshConfig(dp=2, fsdp=2), devices=jax.devices()[:4]
        )
        rng = np.random.default_rng(1)
        tree = {"w": rng.standard_normal((4, 500)).astype(np.float32)}
        shapes = {"w": jax.ShapeDtypeStruct((500,), jnp.float32)}
        plan = plan_buckets(
            shapes, dp=2, fsdp=2, bucket_bytes=1 << 20,
            compress="int8",
        )
        stacked = self._stacked(mesh, plan, tree)
        res0 = zero_residual(plan, mesh)
        assert all(r.shape[0] == 4 for r in res0)
        synced, res1, _ = jax.jit(
            lambda t, r: sync_grads(t, mesh, plan, residual=r)
        )(stacked, res0)
        ref = tree["w"].mean(axis=0)
        # the int8 leg quantizes the fsdp chunk (a partial sum over 2
        # devices): per-device rounding <= scale/2; the dp-mean keeps
        # the bound but the chunk magnitudes are ~2x a single grad
        scale = 2 * np.abs(tree["w"]).max() / 127.0
        assert float(
            np.abs(np.asarray(synced["w"]) - ref).max()
        ) <= scale / 2 + 1e-6
        assert res1 is not None and len(res1) == plan.num_buckets
        assert float(np.abs(np.asarray(res1[0])).max()) > 0

    def test_tp_mode_sync_is_exact_mean(self):
        mesh = build_mesh(
            MeshConfig(dp=2, tp=2), devices=jax.devices()[:4]
        )
        rng = np.random.default_rng(2)
        tree = {"w": rng.standard_normal((2, 96)).astype(np.float32)}
        shapes = {"w": jax.ShapeDtypeStruct((96,), jnp.float32)}
        plan = plan_buckets(
            shapes, dp=2, auto_axes=("tp",), model_shard=2,
            bucket_bytes=1 << 20,
        )
        stacked = self._stacked(mesh, plan, tree)
        synced, res, _ = jax.jit(
            lambda t: sync_grads(t, mesh, plan)
        )(stacked)
        np.testing.assert_allclose(
            np.asarray(synced["w"]), tree["w"].mean(axis=0), atol=1e-6
        )
        assert res is None


# -- train-step integration -------------------------------------------------
class TestHybridTrainStep:
    def _run(self, mc, devs, steps=4, **kw):
        cfg = _fp32_tiny()
        tx = optax.adamw(1e-2)
        mesh = build_mesh(mc, devices=jax.devices()[:devs])
        state, _ = init_sharded_state(
            jax.random.PRNGKey(0), cfg, mesh, tx
        )
        x = _batch(cfg)
        b = shard_batch({"x": x, "y": x}, mesh)
        step = build_train_step(cfg, mesh, tx, donate=False, **kw)
        if kw.get("grad_compress") == "int8":
            plan = plan_for_mesh(
                cfg, mesh, grad_compress="int8",
                grad_bucket_mb=kw.get("grad_bucket_mb", 1),
                slices=kw.get("grad_slices", 1),
            )
            state = ensure_residual(state, plan, mesh)
        for _ in range(steps):
            state, m = step(state, b["x"], b["y"])
        return float(m["loss"]), float(m["grad_norm"]), state

    @pytest.mark.slow  # ~12s: two full compiles for bitwise parity
    def test_fsdp_explicit_is_bitwise_gspmd(self):
        """The acceptance gate in test form: the ZeRO schedule is the
        same math in the same grouping GSPMD uses (RS over fsdp, then
        the dp reduction), so fp32 losses match BITWISE."""
        mc = MeshConfig(dp=2, fsdp=2)
        l0, g0, _ = self._run(mc, 4)
        l1, g1, _ = self._run(
            mc, 4, comm_overlap=True, grad_bucket_mb=1
        )
        assert l0 == l1
        assert abs(g0 - g1) < 1e-4

    # slow tier (budget): tier-1 keeps the tp path covered by the
    # unit-level sync test (test_tp_mode_sync_is_exact_mean) + the
    # lower-only HLO structure check
    @pytest.mark.slow
    def test_tp_explicit_matches_gspmd(self):
        """dp x tp: the sync itself is the same psum in the same
        order, but the partitioner makes different matmul splits
        inside vs outside the partial-manual region, so parity is
        float-noise-tight rather than bitwise (measured ~1e-7)."""
        mc = MeshConfig(dp=2, tp=2)
        l0, g0, s0 = self._run(mc, 4)
        l1, g1, s1 = self._run(
            mc, 4, comm_overlap=True, grad_bucket_mb=1
        )
        assert abs(l0 - l1) < 1e-5
        assert abs(g0 - g1) < 1e-4
        for a, c in zip(
            jax.tree_util.tree_leaves(s0.params),
            jax.tree_util.tree_leaves(s1.params),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(c), atol=1e-5
            )

    # slow tier (budget): int8-on-zero-plans stays tier-1-covered by
    # TestZeroSyncGrads (quantization error bound + residual shapes)
    @pytest.mark.slow
    def test_fsdp_int8_error_feedback_convergence(self):
        mc = MeshConfig(dp=2, fsdp=2)
        l0, _, _ = self._run(mc, 4, steps=12)
        l8, _, s8 = self._run(
            mc, 4, steps=12, comm_overlap=True,
            grad_compress="int8", grad_bucket_mb=1,
        )
        assert abs(l8 - l0) < 0.05
        assert s8.grad_residual is not None
        assert any(
            float(jnp.sum(jnp.abs(r))) > 0 for r in s8.grad_residual
        )

    def test_hlo_structure(self):
        """ZeRO: two reduce-scatters per bucket (fsdp shard leg + dp
        leg), no monolithic all-reduce. tp: one all-reduce per bucket
        (the bucketed psum), no reduce-scatter."""
        cfg = _fp32_tiny()
        tx = optax.adamw(1e-2)
        x = _batch(cfg)

        def lower(mc):
            mesh = build_mesh(mc, devices=jax.devices()[:4])
            state, _ = init_sharded_state(
                jax.random.PRNGKey(0), cfg, mesh, tx
            )
            b = shard_batch({"x": x, "y": x}, mesh)
            step = build_train_step(
                cfg, mesh, tx, donate=False, comm_overlap=True,
                grad_bucket_mb=1,
            )
            plan = plan_for_mesh(cfg, mesh, grad_bucket_mb=1)
            return step.lower(state, b["x"], b["y"]).as_text(), plan

        txt, plan = lower(MeshConfig(dp=2, fsdp=2))
        assert len(re.findall(r"reduce_scatter", txt)) == (
            2 * plan.num_buckets
        )
        assert len(re.findall(r"all_reduce", txt)) == 0
        txt, plan = lower(MeshConfig(dp=2, tp=2))
        assert len(re.findall(r"all_reduce", txt)) == plan.num_buckets
        assert len(re.findall(r"reduce_scatter", txt)) == 0

    @pytest.mark.slow
    def test_two_level_composes_with_zero(self):
        """8-device dp4(2-slice) x fsdp2: the two-level ICI/DCN dp
        legs ride the fsdp chunk; fp32 stays bitwise with GSPMD and
        int8+EF tracks the baseline."""
        mc = MeshConfig(dp=4, fsdp=2, dcn_axes=("dp",), slices=2)
        l0, _, _ = self._run(mc, 8)
        l1, _, _ = self._run(
            mc, 8, comm_overlap=True, grad_bucket_mb=1, grad_slices=2
        )
        assert l0 == l1
        l8, _, _ = self._run(
            mc, 8, comm_overlap=True, grad_compress="int8",
            grad_bucket_mb=1, grad_slices=2,
        )
        assert abs(l8 - l0) < 0.05

    @pytest.mark.slow
    def test_fsdp_grad_accum_syncs_once(self):
        """One sync per optimizer step under grad_accum on the ZeRO
        path too: reduce-scatter count stays 2 x buckets, none inside
        the scan."""
        cfg = _fp32_tiny()
        tx = optax.adamw(1e-2)
        mesh = build_mesh(
            MeshConfig(dp=2, fsdp=2), devices=jax.devices()[:4]
        )
        state, _ = init_sharded_state(
            jax.random.PRNGKey(0), cfg, mesh, tx
        )
        x = _batch(cfg)
        b = shard_batch({"x": x, "y": x}, mesh)
        plan = plan_for_mesh(cfg, mesh, grad_bucket_mb=1)
        step = build_train_step(
            cfg, mesh, tx, donate=False, comm_overlap=True,
            grad_bucket_mb=1, grad_accum=2,
        )
        txt = step.lower(state, b["x"], b["y"]).as_text()
        assert len(re.findall(r"reduce_scatter", txt)) == (
            2 * plan.num_buckets
        )


# -- cost model -------------------------------------------------------------
class TestHybridCommCost:
    def test_comm_time_orders_sanely(self):
        from dlrover_tpu.parallel.grad_sync import (
            comm_time_per_device_s,
        )

        nbytes = 100 << 20
        gspmd = comm_time_per_device_s(
            nbytes, Strategy(mesh=MeshConfig(dp=2, fsdp=2))
        )
        zero = comm_time_per_device_s(
            nbytes,
            Strategy(mesh=MeshConfig(dp=2, fsdp=2), comm_overlap=True),
        )
        tp = comm_time_per_device_s(
            nbytes,
            Strategy(mesh=MeshConfig(dp=2, tp=2), comm_overlap=True),
        )
        tp_gspmd = comm_time_per_device_s(
            nbytes, Strategy(mesh=MeshConfig(dp=2, tp=2))
        )
        assert 0 < zero < gspmd
        # the tp sync only moves the 1/tp model shard per device
        assert 0 < tp < tp_gspmd

    def test_whole_dcn_axis_bills_at_dcn_rate(self):
        """An axis listed whole in dcn_axes must price its explicit
        legs at the DCN rate, not silently inherit ICI (the docstring
        contract the zero/tp branches must honor too)."""
        from dlrover_tpu.parallel import topology
        from dlrover_tpu.parallel.grad_sync import (
            comm_time_per_device_s,
        )

        model = topology.LinkModel(ici_gbps=90.0, dcn_gbps=1.0)
        nbytes = 100 << 20
        ici_fsdp = comm_time_per_device_s(
            nbytes,
            Strategy(mesh=MeshConfig(dp=2, fsdp=2), comm_overlap=True),
            link_model=model,
        )
        dcn_fsdp = comm_time_per_device_s(
            nbytes,
            Strategy(
                mesh=MeshConfig(dp=2, fsdp=2, dcn_axes=("fsdp",)),
                comm_overlap=True,
            ),
            link_model=model,
        )
        assert dcn_fsdp > 10 * ici_fsdp
        ici_tp = comm_time_per_device_s(
            nbytes,
            Strategy(mesh=MeshConfig(dp=2, tp=2), comm_overlap=True),
            link_model=model,
        )
        dcn_tp = comm_time_per_device_s(
            nbytes,
            Strategy(
                mesh=MeshConfig(dp=2, tp=2, dcn_axes=("dp",)),
                comm_overlap=True,
            ),
            link_model=model,
        )
        assert dcn_tp > 10 * ici_tp

    def test_tp_compress_request_prices_uncompressed(self):
        """plan_for_mesh forces int8 off on tp plans; the cost model
        must agree (same one-gate rule as the step builder)."""
        from dlrover_tpu.parallel.grad_sync import (
            comm_bytes_per_device,
        )

        plain = comm_bytes_per_device(
            1 << 20,
            Strategy(mesh=MeshConfig(dp=2, tp=2), comm_overlap=True),
        )
        compressed = comm_bytes_per_device(
            1 << 20,
            Strategy(
                mesh=MeshConfig(dp=2, tp=2),
                comm_overlap=True,
                grad_compress="int8",
            ),
        )
        assert compressed == plain


# -- a trainer on a dp x tp mesh (slow: a trainer and two more worlds) ------
@pytest.mark.slow
class TestHybridTrainer:
    def test_trainer_takes_the_explicit_path_and_resizes_warm(self):
        """A trainer on dp2 x tp2 resolves the explicit schedule (no
        silent GSPMD fallback), grows to dp4 x tp2 and comes back through
        the compile cache."""
        from dlrover_tpu.trainer.elastic.trainer import (
            ElasticTrainer,
            TrainerConfig,
        )

        class _Tokens:
            data = np.random.default_rng(0).integers(
                0, 256, (128, 33), dtype=np.int32
            )

            def __len__(self):
                return len(self.data)

            def __getitem__(self, i):
                return {"x": self.data[i][:-1], "y": self.data[i][1:]}

        trainer = ElasticTrainer(
            model_cfg=tiny(num_layers=1),
            tx=optax.adamw(1e-2),
            dataset=_Tokens(),
            trainer_cfg=TrainerConfig(
                batch_size=8, seq_len=32, report_metrics=False,
                log_interval=1000, prefetch=2, donation_aware=False,
                speculative_compile=False, comm_overlap=True,
            ),
            strategy=Strategy(mesh=MeshConfig(dp=2, tp=2), dtype="float32"),
            devices=jax.devices()[:4],
        )
        try:
            assert trainer.pipeline_stats.grad_sync_path == "explicit"
            trainer.train(num_steps=2)
            trainer.resize(8)  # dp4 x tp2: never compiled
            trainer.train(num_steps=4)
            warm = trainer.resize(4)  # back to dp2 x tp2
            trainer.train(num_steps=6)
            assert warm["compile_cache_hit"] is True
            assert trainer.pipeline_stats.grad_sync_path == "explicit"
        finally:
            trainer.close()


# -- fallback visibility ----------------------------------------------------
class TestFallbackVisibility:
    def test_note_gspmd_fallback_logs_once_per_mesh(self, monkeypatch):
        from dlrover_tpu.common import log as log_mod
        from dlrover_tpu.parallel import grad_sync

        sizes = {"dp": 2, "pp": 3, "tp": 5}  # unique key for the test
        grad_sync._GSPMD_FALLBACK_LOGGED.discard(
            tuple(sorted((k, int(v)) for k, v in sizes.items()))
        )
        msgs = []
        monkeypatch.setattr(
            log_mod.default_logger,
            "info",
            lambda m, *a, **k: msgs.append(str(m)),
        )
        grad_sync.note_gspmd_fallback(sizes)
        grad_sync.note_gspmd_fallback(sizes)
        hits = [m for m in msgs if "GSPMD default" in m]
        assert len(hits) == 1
        assert "'pp': 3" in hits[0]

    def test_pipeline_stats_carry_the_path(self):
        from dlrover_tpu.accel.profiler import PipelineStats

        st = PipelineStats(grad_sync_path="explicit")
        d = st.as_dict()
        assert d["grad_sync_path"] == "explicit"
        assert d["grad_sync_explicit"] == 1
        assert "grad sync [explicit]" in st.summary()
        st2 = PipelineStats(grad_sync_path="gspmd")
        assert st2.as_dict()["grad_sync_explicit"] == 0
        assert PipelineStats().as_dict()["grad_sync_explicit"] is None
