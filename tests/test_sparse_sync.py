"""Sparse DCN gradient sync (ISSUE 18): EF-composed block top-k on the
two-level sync's slow (cross-slice) leg, the ``grad_compress="auto"``
policy that picks a mode per mesh from the measured ICI:DCN ratio, the
gate that keeps model-sharded plans uncompressed, and the
observed rail-rate EWMA that folds realized striped-transfer throughput
back into the link-cost model."""

import json
import os
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
from dlrover_tpu.obs.metrics import MetricsRegistry
from dlrover_tpu.parallel import grad_sync as gs
from dlrover_tpu.parallel import topology
from dlrover_tpu.parallel.grad_sync import (
    AUTO_TOPK_DENSITY,
    TOPK_BLOCK,
    ensure_residual,
    export_compress_metrics,
    plan_buckets,
    plan_for_mesh,
    resolve_auto_compress,
    resolve_plan,
    sync_grads,
    zero_residual,
)
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.topology import LinkModel


def _fp32_tiny(**kw):
    return dc_replace(
        tiny(num_layers=1), dtype="float32", param_dtype="float32", **kw
    )


def _batch(cfg, batch=8, seq=16, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)


@pytest.fixture
def tmp_topo_cache(tmp_path, monkeypatch):
    """Isolated topology cache dir + pristine module state on both
    sides — observed rail rates overlay ``get_link_model`` globally,
    so leaking one across tests would silently reprice everything."""
    monkeypatch.setenv("DLROVER_TPU_TOPOLOGY_CACHE", str(tmp_path))
    topology.reset_link_model()
    yield str(tmp_path)
    topology.reset_link_model()


# -- the block top-k mask ---------------------------------------------------
class TestTopkMask:
    def test_keeps_exactly_k_blocks(self):
        x = jnp.asarray(
            np.random.default_rng(0).standard_normal(1000),
            jnp.float32,
        )
        m = gs._topk_block_mask(x, 0.25, 100)  # 10 blocks -> k=2,
        # last block is 100 wide padded view of no extra elems
        m = np.asarray(m)
        assert m.shape == (1000,)
        blocks = m.reshape(10, 100)
        per_block = blocks.max(axis=1)
        assert per_block.sum() == 2  # round(10 * 0.25) = 2
        # blocks are kept or dropped whole
        assert set(np.unique(blocks)) <= {0.0, 1.0}
        assert all(len(np.unique(b)) == 1 for b in blocks)

    def test_density_one_is_all_ones(self):
        x = jnp.ones((500,), jnp.float32)
        m = gs._topk_block_mask(x, 1.0, TOPK_BLOCK)
        assert np.asarray(m).min() == 1.0

    def test_k_floor_is_one_block(self):
        x = jnp.asarray(np.arange(512, dtype=np.float32))
        m = np.asarray(gs._topk_block_mask(x, 1e-6, 256))
        # k clamps to 1: the higher-|sum| (second) block survives
        assert m[:256].max() == 0.0 and m[256:].min() == 1.0

    def test_ragged_tail_is_padded_not_dropped(self):
        # 300 elems, block 256 -> 2 blocks, the 44-wide tail competes
        x = np.zeros(300, np.float32)
        x[256:] = 100.0  # tail block wins on |sum|
        m = np.asarray(
            gs._topk_block_mask(jnp.asarray(x), 0.5, 256)
        )
        assert m[256:].min() == 1.0 and m[:256].max() == 0.0


# -- plan accounting --------------------------------------------------------
class TestSparsePlanAccounting:
    def _plans(self, density=0.25):
        shapes = [jax.ShapeDtypeStruct((65536,), jnp.float32)] * 2
        kw = dict(dp=4, slices=2, bucket_bytes=1 << 20)
        dense = plan_buckets(shapes, compress="int8", **kw)
        sparse = plan_buckets(
            shapes, compress="int8_topk", topk_density=density, **kw
        )
        return dense, sparse

    def test_dcn_bytes_shrink_with_density(self):
        dense, sparse = self._plans(0.25)
        assert sparse.sparse and not dense.sparse
        assert sparse.compressed and sparse.compress == "int8_topk"
        ratio = sparse.dcn_bytes_twolevel() / dense.dcn_bytes_twolevel()
        # density 0.25 of int8 blocks + 4B/block indices: well under
        # half the dense int8 DCN payload
        assert ratio <= 0.5, ratio
        assert 0.0 < sparse.dcn_density <= 0.3

    def test_density_one_matches_int8_accounting(self):
        dense, sparse = self._plans(1.0)
        assert sparse.dcn_density == 1.0
        # k == nblk ships every block; the only extra wire is the
        # 4B/block index stream
        assert sparse.dcn_bytes_twolevel() >= dense.dcn_bytes_twolevel()

    def test_describe_names_density(self):
        _, sparse = self._plans(0.25)
        assert "density" in sparse.describe()

    def test_wire_bytes_ordering(self):
        shapes = [jax.ShapeDtypeStruct((65536,), jnp.float32)] * 2
        kw = dict(dp=4, slices=2, bucket_bytes=1 << 20)
        fp32 = plan_buckets(shapes, **kw)
        int8 = plan_buckets(shapes, compress="int8", **kw)
        topk = plan_buckets(
            shapes, compress="int8_topk", topk_density=0.25, **kw
        )
        # payload view: the sparse DCN shard (k int8 blocks + indices)
        # undercuts the dense int8 shard
        assert topk.wire_bytes < int8.wire_bytes
        # ring-adjusted per-device view orders all three
        assert (
            topk.explicit_wire_bytes()
            < int8.explicit_wire_bytes()
            < fp32.explicit_wire_bytes()
        )

    def test_plan_buckets_rejects_bad_combos(self):
        shapes = [jax.ShapeDtypeStruct((1024,), jnp.float32)]
        with pytest.raises(ValueError, match="single-slice"):
            plan_buckets(shapes, dp=4, compress="int8_topk")
        with pytest.raises(ValueError, match="density"):
            plan_buckets(
                shapes, dp=4, slices=2, compress="int8_topk",
                topk_density=0.0,
            )
        with pytest.raises(ValueError, match="auto"):
            plan_buckets(shapes, dp=4, compress="auto")

    def test_plan_for_mode_downgrades_topk_without_slices(self):
        # one slice has no DCN shard leg: the request degrades to
        # plain int8 instead of planning an unreachable sparse leg
        plan = plan_for_mesh(
            _fp32_tiny(),
            build_mesh(MeshConfig(dp=4), devices=jax.devices()[:4]),
            grad_compress="int8_topk",
            grad_bucket_mb=1,
        )
        assert plan is not None and plan.compress == "int8"


# -- the auto policy --------------------------------------------------------
class TestAutoCompressPolicy:
    def _model(self, ici, dcn):
        return LinkModel(ici_gbps=ici, dcn_gbps=dcn, source="measured")

    def test_ratio_thresholds(self):
        assert (
            resolve_auto_compress(
                slices=2, link_model=self._model(90.0, 12.5)
            )
            == "int8_topk"  # ratio 7.2 >= 4
        )
        assert (
            resolve_auto_compress(
                slices=2, link_model=self._model(90.0, 30.0)
            )
            == "int8"  # ratio 3 in [2, 4)
        )
        assert (
            resolve_auto_compress(
                slices=2, link_model=self._model(90.0, 80.0)
            )
            == "none"  # near parity
        )

    def test_model_sharded_and_flat_cases(self):
        assert (
            resolve_auto_compress(
                slices=2, auto_axes=("tp",),
                link_model=self._model(90.0, 12.5),
            )
            == "none"
        )
        # whole-DCN flat ring: int8 the whole payload, never topk
        assert (
            resolve_auto_compress(
                whole_dcn=True, link_model=self._model(90.0, 12.5)
            )
            == "int8"
        )
        # pure ICI: wire is cheap, EF noise is not free
        assert (
            resolve_auto_compress(link_model=self._model(90.0, 12.5))
            == "none"
        )

    def test_observed_rates_steer_the_policy(self, tmp_topo_cache):
        # fallback ratio 7.2 -> topk; an observed healthy DCN (EWMA
        # from real stripes) flips the same mesh to int8
        assert resolve_auto_compress(slices=2) == "int8_topk"
        topology.observe_rail_rate("peer", 45.0)
        assert resolve_auto_compress(slices=2) == "int8"

    def test_resolve_plan_resolves_auto(self, tmp_topo_cache):
        s = Strategy(
            mesh=MeshConfig(dp=4, dcn_axes=("dp",), slices=2),
            comm_overlap=True,
            grad_compress="auto",
        )
        plan = resolve_plan(_fp32_tiny(), s)
        # fallback constants: ICI:DCN = 7.2 -> sparse
        assert plan is not None and plan.compress == "int8_topk"
        assert plan.topk_density == s.grad_topk_density

    def test_auto_opt_name_registered(self):
        from dlrover_tpu.accel.opt_lib import apply_optimizations

        cfg = _fp32_tiny()
        s = Strategy(opts=("grad_compress_auto",))
        assert s.resolved_grad_compress() == "auto"
        assert s.resolved_comm_overlap()
        _, s2 = apply_optimizations(cfg, s, s.opts)
        assert s2.grad_compress == "auto" and s2.comm_overlap


# -- model-sharded plans never compress --------------------------------------
class TestModelShardedCompressGate:
    def test_tp_compress_forced_off_and_logs_once(self, monkeypatch):
        from dlrover_tpu.common import log as log_mod

        monkeypatch.setattr(
            gs, "_MODEL_SHARD_COMPRESS_LOGGED", False
        )
        msgs = []
        monkeypatch.setattr(
            log_mod.default_logger,
            "info",
            lambda m, *a, **k: msgs.append(str(m)),
        )
        s = Strategy(
            mesh=MeshConfig(dp=2, tp=2),
            comm_overlap=True,
            grad_compress="int8",
        )
        cfg = _fp32_tiny()
        p1 = resolve_plan(cfg, s)
        p2 = resolve_plan(cfg, s)
        assert p1.compress == "none" and p2.compress == "none"
        hits = [m for m in msgs if "int8 compression is not" in m]
        assert len(hits) == 1  # once per process, not per plan

    def test_3d_stays_off(self, monkeypatch):
        monkeypatch.setattr(
            gs, "_MODEL_SHARD_COMPRESS_LOGGED", False
        )
        s = Strategy(
            mesh=MeshConfig(dp=2, fsdp=2, tp=2),
            comm_overlap=True,
            grad_compress="int8",
        )
        plan = resolve_plan(_fp32_tiny(), s)
        # _sync_grads_3d is fully manual and carries no residual
        assert plan is not None and plan.compress == "none"


# -- sync numerics ----------------------------------------------------------
class TestSparseSyncNumerics:
    def _mesh(self):
        return build_mesh(
            MeshConfig(dp=4, dcn_axes=("dp",), slices=2),
            devices=jax.devices()[:4],
        )

    def _stacked(self, mesh, plan, tree):
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh = NamedSharding(mesh, P(plan.stack_axes))
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(a, sh), tree
        )

    def _sync(self, mesh, plan, tree):
        stacked = self._stacked(mesh, plan, tree)
        res0 = zero_residual(plan, mesh)
        return jax.jit(
            lambda t, r: sync_grads(t, mesh, plan, residual=r)
        )(stacked, res0)

    def test_density_one_is_bitwise_int8(self):
        """The acceptance gate in unit form: at density 1.0 the mask
        is all-ones and ``xx * 1.0`` is IEEE-exact, so scale, quantized
        payload, psum and residual reproduce the dense int8 two-level
        path bit for bit."""
        mesh = self._mesh()
        rng = np.random.default_rng(3)
        tree = {"w": rng.standard_normal((4, 4000)).astype(np.float32)}
        shapes = {"w": jax.ShapeDtypeStruct((4000,), jnp.float32)}
        kw = dict(dp=4, slices=2, bucket_bytes=1 << 20)
        p8 = plan_buckets(shapes, compress="int8", **kw)
        pk = plan_buckets(
            shapes, compress="int8_topk", topk_density=1.0, **kw
        )
        s8, r8, g8 = self._sync(mesh, p8, tree)
        sk, rk, gk = self._sync(mesh, pk, tree)
        assert np.asarray(s8["w"]).tobytes() == np.asarray(
            sk["w"]
        ).tobytes()
        assert np.asarray(r8[0]).tobytes() == np.asarray(
            rk[0]
        ).tobytes()
        assert float(g8) == float(gk)

    def test_sparse_residual_carries_unshipped_mass(self):
        """EF composition: at density 0.25 the residual absorbs the
        dropped blocks (magnitudes ~the gradient itself), not just the
        int8 rounding error — its norm dwarfs the dense-int8
        residual's."""
        mesh = self._mesh()
        rng = np.random.default_rng(4)
        tree = {"w": rng.standard_normal((4, 4096)).astype(np.float32)}
        shapes = {"w": jax.ShapeDtypeStruct((4096,), jnp.float32)}
        kw = dict(dp=4, slices=2, bucket_bytes=1 << 20)
        dense = plan_buckets(shapes, compress="int8", **kw)
        sparse = plan_buckets(
            shapes, compress="int8_topk", topk_density=0.25, **kw
        )
        _, rd, _ = self._sync(mesh, dense, tree)
        _, rs, _ = self._sync(mesh, sparse, tree)
        nd = float(np.linalg.norm(np.asarray(rd[0])))
        ns = float(np.linalg.norm(np.asarray(rs[0])))
        assert ns > 5 * nd

    @pytest.mark.slow  # ~15s: two full train-loop compiles
    def test_topk_converges_with_dense_twolevel(self):
        """ISSUE 18 acceptance: density 0.25 on the DCN leg with EF
        lands within GRAD_SYNC_LOSS_GATE of the dense two-level loss
        on the toy task. EF delays 3/4 of every sync's cross-slice
        mass, so early steps lag hard (gap ~1.45 at step 8) and the
        residual drains it back over time: measured gap 0.031 at step
        48, 0.017 at 56, 0.006 at 80 — the gate sits at 56 with ~3x
        margin, past the EF catch-up knee."""
        cfg = _fp32_tiny()
        tx = optax.adamw(1e-2)
        mc = MeshConfig(dp=4, dcn_axes=("dp",), slices=2)
        mesh = build_mesh(mc, devices=jax.devices()[:4])
        x = _batch(cfg)
        b = shard_batch({"x": x, "y": x}, mesh)

        def run(**kw):
            state, _ = init_sharded_state(
                jax.random.PRNGKey(0), cfg, mesh, tx
            )
            step = build_train_step(
                cfg, mesh, tx, donate=False, comm_overlap=True,
                grad_bucket_mb=1, grad_slices=2, **kw,
            )
            plan = plan_for_mesh(
                cfg, mesh, grad_bucket_mb=1, slices=2,
                grad_compress=kw.get("grad_compress", "none"),
                grad_topk_density=kw.get("grad_topk_density", 0.25),
            )
            state = ensure_residual(state, plan, mesh)
            for _ in range(56):
                state, m = step(state, b["x"], b["y"])
            return float(m["loss"])

        l_dense = run()
        l_topk = run(
            grad_compress="int8_topk", grad_topk_density=0.25
        )
        assert abs(l_topk - l_dense) <= 0.05, (l_topk, l_dense)


# -- compress metrics -------------------------------------------------------
class TestCompressMetrics:
    def test_sparse_plan_gauges(self):
        shapes = [jax.ShapeDtypeStruct((65536,), jnp.float32)]
        plan = plan_buckets(
            shapes, dp=4, slices=2, compress="int8_topk",
            topk_density=0.25, bucket_bytes=1 << 20,
        )
        reg = MetricsRegistry()
        export_compress_metrics(plan, reg)
        assert reg.gauge("dlrover_grad_compress_mode").value == 2.0
        d = reg.gauge("dlrover_grad_sync_dcn_density").value
        assert 0.0 < d <= 0.3

    def test_none_plan_reports_uncompressed(self):
        reg = MetricsRegistry()
        export_compress_metrics(None, reg)
        assert reg.gauge("dlrover_grad_compress_mode").value == 0.0
        assert reg.gauge("dlrover_grad_sync_dcn_density").value == 1.0


# -- observed rail rates ----------------------------------------------------
class TestObservedRailRates:
    def test_ewma_fold(self, tmp_topo_cache):
        topology.observe_rail_rate("peer", 20.0)
        topology.observe_rail_rate("peer", 10.0)
        rates = topology.get_rail_rates()
        assert abs(rates.gbps["peer"] - (0.7 * 20 + 0.3 * 10)) < 1e-9
        assert rates.samples["peer"] == 2

    def test_get_link_model_prefers_observed(self, tmp_topo_cache):
        base = topology.get_link_model()
        assert base.dcn_gbps == topology.FALLBACK_DCN_GBPS
        topology.observe_rail_rate("peer", 33.0)
        m = topology.get_link_model()
        assert m.dcn_gbps == 33.0
        # and only the observed leg moved
        assert m.ici_gbps == base.ici_gbps
        assert m.host_d2h_gbps == base.host_d2h_gbps
        assert (
            topology.rail_link_gbps(m, "peer") == 33.0
        )  # stripe shares reprice too

    def test_cache_round_trip_survives_reset(self, tmp_topo_cache):
        topology.observe_rail_rate("h2d", 17.5)
        fp = topology.device_fingerprint()
        path = topology.rail_rates_path(fp)
        assert os.path.exists(path)
        payload = json.load(open(path))
        assert payload["fingerprint"] == fp
        # cold process: memo + current dropped, disk read back
        topology.reset_link_model()
        assert topology.get_link_model().host_h2d_gbps == 17.5

    def test_fingerprint_mismatch_rejected(self, tmp_topo_cache):
        topology.observe_rail_rate("peer", 40.0)
        fp = topology.device_fingerprint()
        path = topology.rail_rates_path(fp)
        bad = json.load(open(path))
        bad["fingerprint"] = "someone-elses-world"
        with open(path, "w") as f:
            json.dump(bad, f)
        topology.reset_link_model()
        assert topology.load_rail_rates(fp) is None
        assert (
            topology.get_link_model().dcn_gbps
            == topology.FALLBACK_DCN_GBPS
        )

    def test_read_only_cache_dir_tolerated(self, tmp_topo_cache):
        os.chmod(tmp_topo_cache, 0o500)
        try:
            topology.reset_link_model()
            topology.observe_rail_rate("peer", 5.0)
            # the fold survives process-locally even when persist fails
            assert topology.get_link_model().dcn_gbps == 5.0
        finally:
            os.chmod(tmp_topo_cache, 0o700)

    def test_unknown_rail_ignored(self, tmp_topo_cache):
        topology.observe_rail_rate("ici9", 99.0)
        topology.observe_rail_rate("peer", -1.0)
        assert topology.get_rail_rates() is None

    def test_metrics_exported(self, tmp_topo_cache):
        reg = MetricsRegistry()
        rates = topology.observe_rail_rate("peer", 21.0)
        topology.export_rail_rate_metrics(rates, reg)
        g = reg.gauge(
            "dlrover_link_observed_gbps", labelnames=("rail",)
        )
        assert g.labels("peer").value == 21.0

    def test_reset_link_model_clears_observed(self, tmp_topo_cache):
        topology.observe_rail_rate("peer", 50.0)
        topology.reset_link_model()
        os.remove(
            topology.rail_rates_path(topology.device_fingerprint())
        )
        topology.reset_link_model()
        assert (
            topology.get_link_model().dcn_gbps
            == topology.FALLBACK_DCN_GBPS
        )


class TestStripeFoldsObservedRates:
    def _stripe(self, a, nbytes=32 << 20, rails=None):
        from dlrover_tpu.parallel.transfer_sched import StripedTransfer

        src = bytearray(nbytes)
        dst = bytearray(nbytes)

        def mover(rail, off, ln):
            dst[off:off + ln] = src[off:off + ln]

        st = StripedTransfer(
            a, direction="d2h", chunk_bytes=4 << 20,
            ignore_window=True, rails=rails,
        )
        return st.run(mover, payload=src)

    def test_production_rails_fold(self, tmp_topo_cache):
        from dlrover_tpu.parallel.transfer_sched import TransferArbiter

        a = TransferArbiter()
        # production-style rails: priced from the LinkModel, no
        # explicit gbps override
        a.register_rail("host_d2h", direction="d2h")
        a.register_rail("dcn", direction="peer")
        rep = self._stripe(a)
        assert rep.rail_seconds and all(
            v > 0 for v in rep.rail_seconds.values()
        )
        rates = topology.get_rail_rates()
        assert rates is not None and "peer" in rates.gbps
        assert os.path.exists(
            topology.rail_rates_path(topology.device_fingerprint())
        )

    def test_emulated_rails_do_not_fold(self, tmp_topo_cache):
        from dlrover_tpu.parallel.transfer_sched import TransferArbiter

        a = TransferArbiter()
        # an explicit gbps override marks an emulated rail (tests)
        # — its realized rate measures the emulation, not a
        # physical link, and must never reprice the model
        a.register_rail("railA", direction="d2h", gbps=2.0)
        a.register_rail("railB", direction="peer", gbps=1.0)
        self._stripe(a, rails=["railA", "railB"])
        assert topology.get_rail_rates() is None


# -- durable atomic_write_json (satellite) -----------------------------------
class TestDurableAtomicWrite:
    def test_durable_fsyncs_before_rename(self, tmp_path, monkeypatch):
        from dlrover_tpu.agent import monitor

        calls = []
        real_fsync = os.fsync
        monkeypatch.setattr(
            os, "fsync", lambda fd: (calls.append(fd), real_fsync(fd))
        )
        p = str(tmp_path / "a.json")
        monitor.atomic_write_json(p, {"x": 1})
        assert calls == []  # default path stays fsync-free
        monitor.atomic_write_json(p, {"x": 2}, durable=True)
        assert len(calls) == 1
        assert json.load(open(p)) == {"x": 2}
