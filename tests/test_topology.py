"""Measured link-cost model (parallel/topology.py) + the consumers it
feeds: the hybrid/emulated mesh layout, two-level multi-slice gradient
sync, per-link dry-runner pricing, and heterogeneous per-slice data
weighting in the elastic sampler."""

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
from dlrover_tpu.parallel import topology
from dlrover_tpu.parallel.grad_sync import (
    comm_time_per_device_s,
    measure_sync_legs_ms,
    measured_overlap_pct,
    plan_buckets,
    plan_for_mesh,
    resolve_bucket_bytes,
    resolve_plan,
    sync_grads,
    zero_residual,
)
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.trainer.elastic.sampler import ElasticDistributedSampler


@pytest.fixture(autouse=True)
def _isolated_topology(tmp_path, monkeypatch):
    """Every test gets a private probe-cache dir and a clean in-process
    memo — the module-level memo and ~/.cache must not leak between
    tests (or into them from the trainer suites)."""
    monkeypatch.setenv("DLROVER_TPU_TOPOLOGY_CACHE", str(tmp_path))
    topology.reset_link_model()
    yield
    topology.reset_link_model()


def _fp32_tiny(**kw):
    return dc_replace(
        tiny(num_layers=1), dtype="float32", param_dtype="float32", **kw
    )


def _hybrid_mesh(dp=8, slices=2, **kw):
    cfg = MeshConfig(dp=dp, dcn_axes=("dp",), slices=slices, **kw)
    return cfg, build_mesh(cfg, devices=jax.devices()[: cfg.num_devices])


# -- LinkModel ---------------------------------------------------------------
class TestLinkModel:
    def test_fallback_reproduces_historical_constant(self):
        """The documented fallback must price ICI exactly like the old
        hardcoded dry-runner constant (_SEC_PER_ICI_BYTE = 1/9e10)."""
        m = topology.fallback_link_model()
        assert m.sec_per_ici_byte() == pytest.approx(1 / 9e10)
        assert m.ordering_ok  # ici >= dcn >= host

    def test_pricing_accessors(self):
        m = topology.LinkModel(
            ici_gbps=100.0, dcn_gbps=10.0,
            host_d2h_gbps=5.0, host_h2d_gbps=4.0,
        )
        assert m.sec_per_ici_byte() == pytest.approx(1e-11)
        assert m.sec_per_dcn_byte() == pytest.approx(1e-10)
        assert m.sec_per_host_byte() == pytest.approx(1 / 5e9)
        assert m.sec_per_host_byte(h2d=True) == pytest.approx(1 / 4e9)

    def test_axis_gbps_falls_back_to_bottleneck(self):
        m = topology.LinkModel(
            ici_gbps=80.0, ici_axis_gbps=(("dp", 90.0), ("tp", 80.0))
        )
        assert m.axis_gbps("dp") == 90.0
        assert m.axis_gbps("fsdp") == 80.0  # unprobed axis -> min

    def test_ordering_invariant(self):
        bad = topology.LinkModel(ici_gbps=5.0, dcn_gbps=50.0)
        assert not bad.ordering_ok

    def test_json_roundtrip(self):
        m = topology.LinkModel(
            ici_gbps=123.4, dcn_gbps=45.6, ici_axis_gbps=(("dp", 123.4),),
            source="measured", fingerprint="abc123", probed_at=1.5,
        )
        back = topology.LinkModel.from_json(m.to_json())
        assert back == m

    def test_describe_mentions_source(self):
        assert "fallback-cpu" in topology.fallback_link_model(
            source="fallback-cpu"
        ).describe()


# -- fingerprint + cache -----------------------------------------------------
class TestFingerprintCache:
    def test_fingerprint_stable_and_device_count_sensitive(self):
        devs = jax.devices()
        assert topology.device_fingerprint(devs) == (
            topology.device_fingerprint(devs)
        )
        assert topology.device_fingerprint(devs) != (
            topology.device_fingerprint(devs[:4])
        )

    def test_save_load_roundtrip(self, tmp_path):
        fp = topology.device_fingerprint()
        m = topology.LinkModel(
            ici_gbps=77.0, source="measured", fingerprint=fp
        )
        path = topology.save_cache(m)
        assert path and os.path.exists(path)
        assert str(tmp_path) in path  # honored the env override
        assert topology.load_cached(fp) == m

    def test_stale_fingerprint_rejected(self):
        m = topology.LinkModel(source="measured", fingerprint="worldA")
        topology.save_cache(m)
        # a cache file copied across device worlds must not load
        wrong = topology.cache_path("worldB")
        os.makedirs(os.path.dirname(wrong), exist_ok=True)
        with open(topology.cache_path("worldA")) as f:
            blob = f.read()
        with open(wrong, "w") as f:
            f.write(blob)
        assert topology.load_cached("worldB") is None
        assert topology.load_cached("worldA") == m

    def test_corrupt_cache_returns_none(self):
        p = topology.cache_path("junk")
        os.makedirs(os.path.dirname(p), exist_ok=True)
        with open(p, "w") as f:
            f.write("{not json")
        assert topology.load_cached("junk") is None

    def test_save_failure_is_tolerated(self, monkeypatch):
        monkeypatch.setenv(
            "DLROVER_TPU_TOPOLOGY_CACHE", "/proc/definitely-readonly"
        )
        assert topology.save_cache(
            topology.fallback_link_model("fp")
        ) is None  # no raise


# -- probe -------------------------------------------------------------------
class TestProbe:
    def test_cpu_backend_falls_back_and_persists(self):
        m = topology.probe_link_model()
        assert m.source == "fallback-cpu"
        assert m.fingerprint == topology.device_fingerprint()
        assert m.ici_gbps == topology.FALLBACK_ICI_GBPS
        # persisted: a warm restart's get_link_model finds it on disk
        topology.reset_link_model()
        assert topology.get_link_model().source == "fallback-cpu"

    def test_warm_probe_skips_measurement(self, monkeypatch):
        first = topology.probe_link_model()

        def boom(*a, **k):  # pragma: no cover - must not run
            raise AssertionError("re-probed despite warm cache")

        monkeypatch.setattr(topology, "_time_allreduce", boom)
        again = topology.probe_link_model(measure_on_cpu=True)
        assert again == first  # cache hit, no measurement

    def test_force_reprobes(self):
        topology.probe_link_model()
        forced = topology.probe_link_model(
            force=True, measure_on_cpu=True,
            mesh_config=MeshConfig(dp=2), devices=jax.devices()[:2],
            probe_mb=1,
        )
        assert forced.source == "measured"

    def test_measured_probe_on_virtual_backend(self):
        """measure_on_cpu exercises the real measurement machinery:
        per-axis collective timing + host-link timing produce positive
        bandwidths and a per-axis entry for dp."""
        m = topology.probe_link_model(
            mesh_config=MeshConfig(dp=2),
            devices=jax.devices()[:2],
            force=True, measure_on_cpu=True, probe_mb=1,
        )
        assert m.source == "measured"
        assert m.ici_gbps > 0
        assert dict(m.ici_axis_gbps).get("dp", 0) > 0
        assert m.host_d2h_gbps > 0 and m.host_h2d_gbps > 0

    def test_hybrid_probe_measures_dcn_leg(self):
        """A hybrid dp axis (2 slices) probes BOTH leg classes: the
        slice-local ICI groups and the cross-slice DCN groups."""
        m = topology.probe_link_model(
            mesh_config=MeshConfig(dp=4, dcn_axes=("dp",), slices=2),
            devices=jax.devices()[:4],
            force=True, measure_on_cpu=True, probe_mb=1,
        )
        assert m.source == "measured"
        assert dict(m.ici_axis_gbps).get("dp", 0) > 0
        assert m.dcn_gbps > 0


# -- process accessor + fallback logging ------------------------------------
class TestGetSetModel:
    def test_get_without_cache_is_fallback(self):
        m = topology.get_link_model()
        assert m.source == "fallback"

    def test_get_loads_persisted_probe(self):
        fp = topology.device_fingerprint()
        topology.save_cache(
            topology.LinkModel(
                ici_gbps=55.0, source="measured", fingerprint=fp
            )
        )
        topology.reset_link_model()
        got = topology.get_link_model()
        assert got.source == "measured" and got.ici_gbps == 55.0

    def test_get_falls_back_to_process_current_model(self):
        """Consumers that cannot name the exact device subset (the
        dry-runner, the auto bucket sizer call get_link_model() with
        no devices) must still see the model the trainer probed for a
        resized subset — not silently fall back to constants because
        the all-devices fingerprint differs."""
        m = topology.LinkModel(
            ici_gbps=33.0, source="measured", fingerprint="subset-fp"
        )
        topology.set_link_model(m)
        got = topology.get_link_model()  # all-devices fp != subset-fp
        assert got.ici_gbps == 33.0 and got.source == "measured"

    def test_set_link_model_installs(self):
        m = topology.LinkModel(
            ici_gbps=42.0, source="measured",
            fingerprint=topology.device_fingerprint(),
        )
        topology.set_link_model(m)
        assert topology.get_link_model().ici_gbps == 42.0

    def test_note_fallback_use_logs_once(self, monkeypatch):
        calls = []
        monkeypatch.setattr(
            topology.logger, "info", lambda msg, *a: calls.append(msg)
        )
        fb = topology.fallback_link_model()
        topology.note_fallback_use(fb)
        topology.note_fallback_use(fb)
        assert len(calls) == 1

    def test_note_fallback_use_silent_for_measured(self, monkeypatch):
        calls = []
        monkeypatch.setattr(
            topology.logger, "info", lambda msg, *a: calls.append(msg)
        )
        topology.note_fallback_use(
            topology.LinkModel(source="measured")
        )
        assert not calls

    def test_export_link_metrics(self):
        from dlrover_tpu.obs.metrics import MetricsRegistry

        reg = MetricsRegistry()
        topology.export_link_metrics(
            topology.LinkModel(
                ici_gbps=90.0, dcn_gbps=12.5, source="measured"
            ),
            registry=reg,
        )
        flat = reg.scalars()
        assert flat["dlrover_link_ici_gbps"] == 90.0
        assert flat["dlrover_link_dcn_gbps"] == 12.5
        assert flat["dlrover_link_model_measured"] == 1.0


# -- bucket sizing -----------------------------------------------------------
class TestBucketSizing:
    def test_slower_link_gets_smaller_buckets(self):
        m = topology.LinkModel(ici_gbps=90.0, dcn_gbps=12.5)
        ici = topology.bucket_bytes_for(m, "ici")
        dcn = topology.bucket_bytes_for(m, "dcn")
        assert dcn < ici
        # 2 ms at the DCN rate, exactly; the fat ICI target clamps
        assert dcn == int(12.5e9 * 2e-3)
        assert ici == topology._BUCKET_MAX_BYTES

    def test_clamped_to_sane_range(self):
        tiny_bw = topology.LinkModel(ici_gbps=1e-6, dcn_gbps=1e-6)
        huge_bw = topology.LinkModel(ici_gbps=1e6, dcn_gbps=1e6)
        assert topology.bucket_bytes_for(tiny_bw, "ici") == (
            topology._BUCKET_MIN_BYTES
        )
        assert topology.bucket_bytes_for(huge_bw, "ici") == (
            topology._BUCKET_MAX_BYTES
        )

    def test_unknown_link_raises(self):
        with pytest.raises(ValueError):
            topology.bucket_bytes_for(topology.LinkModel(), "pcie5")

    def test_resolve_explicit_mb_wins(self):
        assert resolve_bucket_bytes(4) == 4 << 20

    def test_auto_bucket_opt_registration(self):
        from dlrover_tpu.accel.opt_lib import (
            apply_optimizations,
            registered_optimizations,
        )

        assert "auto_bucket" in registered_optimizations()
        _, s = apply_optimizations(
            tiny(num_layers=1),
            Strategy(mesh=MeshConfig(dp=2)),
            ("auto_bucket",),
        )
        # auto sizing implies the explicit sync path
        assert s.comm_overlap and s.grad_bucket_mb == 0

    def test_resolve_auto_prices_from_model(self):
        m = topology.LinkModel(ici_gbps=8.0)  # 2ms -> 16 MiB exactly
        assert resolve_bucket_bytes(0, link_model=m) == int(8e9 * 2e-3)

    def test_resolve_auto_scales_dcn_shard_back_up(self):
        """Two-level: only 1/dp_ici of a bucket crosses DCN, so the
        full-bucket target scales up by dp_ici (x4 again under int8,
        whose DCN shard ships 1 byte/elem) — then clamps."""
        m = topology.LinkModel(dcn_gbps=1.0)  # 2ms -> 2e6 B dcn payload
        base = resolve_bucket_bytes(
            0, dp=8, slices=2, link_model=m
        )
        assert base == int(1e9 * 2e-3) * 4  # x dp_ici=4
        int8 = resolve_bucket_bytes(
            0, dp=8, slices=2, compress="int8", link_model=m
        )
        assert int8 == base * 4  # int8 DCN shard: 1 byte/elem
        # a fat enough target clamps at the 64 MiB ceiling
        wide = topology.LinkModel(dcn_gbps=100.0)
        assert resolve_bucket_bytes(
            0, dp=8, slices=2, compress="int8", link_model=wide
        ) == topology._BUCKET_MAX_BYTES


# -- heterogeneous slice weighting ------------------------------------------
class TestSliceWeights:
    def test_proportional_to_throughput(self):
        w = topology.slice_throughput_weights([1.0, 2.0])
        assert w[0] == pytest.approx(2 * w[1])  # 2x faster -> 2x data
        assert sum(w) == pytest.approx(1.0)

    def test_bad_entries_get_mean_throughput(self):
        w = topology.slice_throughput_weights([1.0, 0.0, -3.0])
        assert sum(w) == pytest.approx(1.0)
        assert w[1] == w[2] == pytest.approx(w[0])

    def test_all_bad_is_equal_split(self):
        assert topology.slice_throughput_weights([0, 0]) == [0.5, 0.5]

    def test_empty(self):
        assert topology.slice_throughput_weights([]) == []


# -- emulated hybrid mesh layout (satellite: mesh.py non-hybrid-util path) ---
class TestEmulatedHybridLayout:
    def _strides(self, mesh):
        """Device-id stride of each size>1 axis of the emulated mesh
        (virtual CPU device ids enumerate 0..n-1 in jax.devices()
        order, so strides read physical adjacency directly)."""
        ids = np.vectorize(lambda d: d.id)(mesh.devices)
        strides = {}
        for ax, name in enumerate(mesh.axis_names):
            if ids.shape[ax] <= 1:
                continue
            strides[name] = int(
                abs(np.take(ids, 1, ax) - np.take(ids, 0, ax)).max()
            )
        return ids, strides

    def test_whole_dcn_axis_gets_largest_stride(self):
        cfg = MeshConfig(dp=2, tp=4, dcn_axes=("dp",))
        mesh = build_mesh(cfg, devices=jax.devices())
        ids, strides = self._strides(mesh)
        assert strides["dp"] == 4  # outermost
        assert strides["tp"] == 1  # slice-local, adjacent
        # each "slice" (fixed dp coord) is one contiguous id run
        dp_ax = mesh.axis_names.index("dp")
        for d in range(2):
            block = np.sort(np.take(ids, d, axis=dp_ax).flatten())
            assert block.tolist() == list(range(d * 4, d * 4 + 4))

    def test_non_dp_dcn_axis_is_outermost_too(self):
        cfg = MeshConfig(dp=2, tp=2, pp=2, dcn_axes=("pp",))
        mesh = build_mesh(cfg, devices=jax.devices())
        _, strides = self._strides(mesh)
        assert strides["pp"] > strides["dp"]
        assert strides["pp"] > strides["tp"]

    def test_hybrid_dp_axis_is_slice_major(self):
        """dp=8 over 2 slices: dp coordinate d = slice*4 + intra-slice
        rank, so each slice's 4 devices are ICI-adjacent (contiguous
        ids) and the slice boundary is the largest stride."""
        cfg, mesh = _hybrid_mesh(dp=8, slices=2)
        ids = np.vectorize(lambda d: d.id)(mesh.devices).flatten()
        assert ids.tolist() == list(range(8))  # slice-major enumeration
        for s in range(2):
            block = ids[s * 4:(s + 1) * 4]
            assert block.max() - block.min() == 3  # ICI-adjacent run

    def test_hybrid_dp_with_tp_keeps_slices_contiguous(self):
        """dp=4 (2 slices) x tp=2: all 4 devices of one slice (2 dp
        ranks x 2 tp ranks) are one contiguous id block, the tp (pure
        ICI) stride is smallest, and the slice factor's stride is the
        largest."""
        cfg = MeshConfig(dp=4, tp=2, dcn_axes=("dp",), slices=2)
        mesh = build_mesh(cfg, devices=jax.devices())
        ids = np.vectorize(lambda d: d.id)(mesh.devices)
        dp_ax = mesh.axis_names.index("dp")
        per = 2  # dp ranks per slice
        for s in range(2):
            block = np.sort(
                np.take(
                    ids, range(s * per, (s + 1) * per), axis=dp_ax
                ).flatten()
            )
            assert block.tolist() == list(range(s * 4, s * 4 + 4))
        # strides: slice factor 4 > intra-slice dp 2 > tp 1
        flatids = np.moveaxis(
            ids, dp_ax, 0
        ).reshape(4, 2)  # (dp coord, tp coord)
        assert flatids[2, 0] - flatids[0, 0] == 4  # slice boundary
        assert flatids[1, 0] - flatids[0, 0] == 2  # intra-slice dp
        assert flatids[0, 1] - flatids[0, 0] == 1  # tp innermost

    def test_slices_validation(self):
        with pytest.raises(ValueError):  # dp not in dcn_axes
            build_mesh(
                MeshConfig(dp=8, slices=2), devices=jax.devices()
            )
        with pytest.raises(ValueError):  # slices does not divide dp
            build_mesh(
                MeshConfig(dp=8, dcn_axes=("dp",), slices=3),
                devices=jax.devices(),
            )

    def test_dp_slices_edge_cases(self):
        assert MeshConfig(dp=8).dp_slices() == 1
        assert MeshConfig(
            dp=8, dcn_axes=("dp",), slices=2
        ).dp_slices() == 2
        # slices == dp is the whole-axis-DCN case: no ICI level
        assert MeshConfig(
            dp=8, dcn_axes=("dp",), slices=8
        ).dp_slices() == 1
        # no dcn_axes declared -> not hybrid regardless of slices
        assert MeshConfig(dp=8, slices=2).dp_slices() == 1

    def test_strategy_json_roundtrip_keeps_slices(self):
        s = Strategy(
            mesh=MeshConfig(dp=8, dcn_axes=("dp",), slices=2)
        )
        back = Strategy.from_json(s.to_json())
        assert back.mesh.slices == 2
        assert back.mesh.dp_slices() == 2
        assert "2slice" in s.describe()


# -- two-level plan accounting ----------------------------------------------
class TestTwoLevelPlan:
    def _plan(self, slices=2, compress="none", n=4096, dp=8):
        shapes = {"w": jax.ShapeDtypeStruct((n,), jnp.float32)}
        return plan_buckets(
            shapes, dp=dp, bucket_bytes=1 << 20,
            compress=compress, slices=slices,
        )

    def test_two_level_flag_and_shard_elems(self):
        p = self._plan()
        assert p.two_level and p.dp_ici == 4
        b = p.buckets[0]
        assert p.shard_elems(b) == b.padded // 4
        flat = self._plan(slices=1)
        assert not flat.two_level
        assert flat.shard_elems(flat.buckets[0]) == (
            flat.buckets[0].padded
        )

    def test_dcn_bytes_two_level_beats_flat(self):
        for slices in (2, 4):
            p = self._plan(slices=slices)
            assert 0 < p.dcn_bytes_twolevel() < p.dcn_bytes_flat()
        # int8 shrinks the DCN leg by ~4x again
        p8 = self._plan(compress="int8")
        assert p8.dcn_bytes_twolevel() < self._plan().dcn_bytes_twolevel()

    def test_int8_two_level_wire_counts_fp32_ici_legs(self):
        p = self._plan(compress="int8")
        b = p.buckets[0]
        expected = b.padded * 4 + b.padded // p.dp_ici * 1 + 4
        assert p.wire_bytes == expected

    def test_slices_must_divide_dp(self):
        shapes = {"w": jax.ShapeDtypeStruct((64,), jnp.float32)}
        with pytest.raises(ValueError):
            plan_buckets(shapes, dp=8, slices=3)

    def test_describe_mentions_two_level(self):
        assert "two-level" in self._plan().describe()

    def test_plan_for_mesh_threads_slices(self):
        cfg, mesh = _hybrid_mesh(dp=8, slices=2)
        plan = plan_for_mesh(
            _fp32_tiny(), mesh, grad_bucket_mb=1, slices=2
        )
        assert plan is not None and plan.two_level

    def test_resolve_plan_picks_up_mesh_slices(self):
        s = Strategy(
            mesh=MeshConfig(dp=8, dcn_axes=("dp",), slices=2),
            comm_overlap=True,
        )
        plan = resolve_plan(_fp32_tiny(), s)
        assert plan is not None and plan.slices == 2


# -- two-level sync numerics -------------------------------------------------
class TestTwoLevelSync:
    def _stacked(self, mesh, tree):
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh = NamedSharding(mesh, P(("dp",)))
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(a, sh), tree
        )

    def test_fp32_two_level_is_exact_mean(self):
        _, mesh = _hybrid_mesh(dp=8, slices=2)
        rng = np.random.default_rng(0)
        tree = {
            "w": rng.standard_normal((8, 64, 3)).astype(np.float32),
            "b": rng.standard_normal((8, 37)).astype(np.float32),
        }
        shapes = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), tree
        )
        plan = plan_buckets(shapes, dp=8, bucket_bytes=512, slices=2)
        assert plan.num_buckets > 1 and plan.two_level
        synced, res, gnorm = jax.jit(
            lambda t: sync_grads(t, mesh, plan)
        )(self._stacked(mesh, tree))
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

    def test_int8_two_level_error_bounded_residual_is_shard(self):
        _, mesh = _hybrid_mesh(dp=8, slices=2)
        rng = np.random.default_rng(1)
        tree = {"w": rng.standard_normal((8, 512)).astype(np.float32)}
        shapes = {"w": jax.ShapeDtypeStruct((512,), jnp.float32)}
        plan = plan_buckets(
            shapes, dp=8, bucket_bytes=1 << 20,
            compress="int8", slices=2,
        )
        res0 = zero_residual(plan, mesh)
        # EF state covers exactly what the DCN leg quantizes: the
        # slice-local shard, not the full padded bucket
        assert res0[0].shape == (8, plan.buckets[0].padded // 4)
        synced, res1, _ = jax.jit(
            lambda t, r: sync_grads(t, mesh, plan, residual=r)
        )(self._stacked(mesh, tree), res0)
        ref = tree["w"].mean(axis=0)
        # only the slice-SUMMED shard is quantized (values up to 4x a
        # single grad), so the bound uses the slice-sum magnitude
        scale = np.abs(
            tree["w"].reshape(2, 4, -1).sum(axis=1)
        ).max() / 127.0
        assert float(
            np.abs(np.asarray(synced["w"]) - ref).max()
        ) <= scale / 2 + 1e-6
        assert res1 is not None
        assert float(np.abs(np.asarray(res1[0])).max()) > 0

    @pytest.mark.slow  # two full train-step compiles (~4.5s);
    # sync-level parity stays tier-1 (test_fp32_two_level_is_exact_mean)
    def test_two_level_train_step_matches_gspmd_bitwise(self):
        """The acceptance check: on an emulated 2-slice mesh the
        two-level fp32 schedule is the same math as GSPMD's monolithic
        all-reduce — identical loss and params."""
        cfg = _fp32_tiny()
        _, mesh = _hybrid_mesh(dp=8, slices=2)
        tx = optax.adamw(1e-2)
        state, _ = init_sharded_state(
            jax.random.PRNGKey(0), cfg, mesh, tx
        )
        rng = np.random.default_rng(0)
        x = rng.integers(0, cfg.vocab_size, (8, 16)).astype(np.int32)
        b = shard_batch({"x": x, "y": x}, mesh)
        base = build_train_step(cfg, mesh, tx, donate=False)
        two = build_train_step(
            cfg, mesh, tx, donate=False,
            comm_overlap=True, grad_slices=2,
        )
        s0, m0 = base(state, b["x"], b["y"])
        s1, m1 = two(state, b["x"], b["y"])
        assert abs(float(m0["loss"]) - float(m1["loss"])) < 1e-5
        for a, c in zip(
            jax.tree_util.tree_leaves(s0.params),
            jax.tree_util.tree_leaves(s1.params),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(c), atol=1e-5
            )

    def test_measure_sync_legs(self):
        _, mesh = _hybrid_mesh(dp=8, slices=2)
        shapes = {"w": jax.ShapeDtypeStruct((256,), jnp.float32)}
        plan = plan_buckets(
            shapes, dp=8, bucket_bytes=1 << 20, slices=2
        )
        ici, dcn = measure_sync_legs_ms(plan, mesh, iters=1)
        assert ici > 0 and dcn >= 0
        flat = plan_buckets(shapes, dp=8, bucket_bytes=1 << 20)
        ici_f, dcn_f = measure_sync_legs_ms(flat, mesh, iters=1)
        assert ici_f > 0 and dcn_f == 0.0  # flat is all-ICI


# -- measured overlap --------------------------------------------------------
class TestMeasuredOverlap:
    def test_fully_hidden(self):
        assert measured_overlap_pct(10.0, 50.0, 50.0) == 100.0

    def test_fully_exposed(self):
        assert measured_overlap_pct(10.0, 60.0, 50.0) == 0.0

    def test_clamps_noise(self):
        # step got FASTER with sync (noise) -> exposed clamps to 0
        assert measured_overlap_pct(10.0, 48.0, 50.0) == 100.0
        # exposed above the standalone roofline clamps to standalone
        assert measured_overlap_pct(10.0, 80.0, 50.0) == 0.0

    def test_none_without_standalone(self):
        assert measured_overlap_pct(None, 50.0, 40.0) is None
        assert measured_overlap_pct(0.0, 50.0, 40.0) is None


# -- per-link comm pricing (dry_runner satellite) ----------------------------
class TestCommTimePricing:
    def test_single_device_free(self):
        assert comm_time_per_device_s(
            1e6, Strategy(mesh=MeshConfig(dp=1))
        ) == 0.0

    def test_flat_ici_matches_ring_formula(self):
        m = topology.LinkModel(ici_gbps=90.0, ici_lat_s=0.0)
        s = Strategy(mesh=MeshConfig(dp=4), comm_overlap=True)
        got = comm_time_per_device_s(8e6, s, link_model=m)
        assert got == pytest.approx(2 * 3 / 4 * 8e6 / 90e9)

    def test_whole_dcn_axis_prices_at_dcn_rate(self):
        m = topology.LinkModel(ici_gbps=90.0, dcn_gbps=9.0)
        ici = comm_time_per_device_s(
            8e6, Strategy(mesh=MeshConfig(dp=4)), link_model=m
        )
        dcn = comm_time_per_device_s(
            8e6,
            Strategy(mesh=MeshConfig(dp=4, dcn_axes=("dp",))),
            link_model=m,
        )
        assert dcn > ici * 5  # ~10x bandwidth gap, latency aside

    def test_two_level_beats_flat_dcn_ring(self):
        """The schedule the tentpole exists for: a hybrid dp axis
        prices its DCN leg at 1/dp_ici of the payload, so the total is
        far below the whole-ring-over-DCN worst case."""
        m = topology.LinkModel(ici_gbps=90.0, dcn_gbps=9.0)
        flat_dcn = comm_time_per_device_s(
            8e6,
            Strategy(
                mesh=MeshConfig(dp=8, dcn_axes=("dp",)),
                comm_overlap=True,
            ),
            link_model=m,
        )
        two_level = comm_time_per_device_s(
            8e6,
            Strategy(
                mesh=MeshConfig(dp=8, dcn_axes=("dp",), slices=2),
                comm_overlap=True,
            ),
            link_model=m,
        )
        assert two_level < flat_dcn

    def test_gspmd_hybrid_not_billed_at_two_level_cost(self):
        """comm_overlap off on a hybrid mesh runs GSPMD's monolithic
        all-reduce — the flat ring over DCN, priced as such, not at
        the two-level schedule it never gets."""
        m = topology.LinkModel(ici_gbps=90.0, dcn_gbps=9.0)
        hybrid = MeshConfig(dp=8, dcn_axes=("dp",), slices=2)
        on = comm_time_per_device_s(
            8e6, Strategy(mesh=hybrid, comm_overlap=True), link_model=m
        )
        off = comm_time_per_device_s(
            8e6, Strategy(mesh=hybrid), link_model=m
        )
        assert off > on

    def test_int8_compresses_the_dcn_shard(self):
        s = Strategy(
            mesh=MeshConfig(dp=8, dcn_axes=("dp",), slices=2),
            comm_overlap=True,
        )
        m = topology.LinkModel(ici_gbps=90.0, dcn_gbps=9.0)
        fp32 = comm_time_per_device_s(8e6, s, link_model=m)
        int8 = comm_time_per_device_s(
            8e6, s, link_model=m, compress="int8"
        )
        assert int8 < fp32

    def test_comm_estimate_prices_from_installed_model(self):
        """est_step_s reacts to the LinkModel: halving the DCN rate
        inflates the exposed comm seconds of a DCN-crossing strategy —
        the estimate is model-driven, not constant-driven."""
        from dlrover_tpu.accel.dry_runner import (
            DryRunReport,
            _comm_estimate,
        )

        s = Strategy(
            mesh=MeshConfig(dp=8, dcn_axes=("dp",), slices=2),
            comm_overlap=True,
        )
        fp = topology.device_fingerprint()

        def estimate(dcn_gbps):
            topology.set_link_model(
                topology.LinkModel(
                    ici_gbps=90.0, dcn_gbps=dcn_gbps,
                    source="measured", fingerprint=fp,
                )
            )
            r = DryRunReport(strategy=s, ok=True)
            _comm_estimate(r, tiny(num_layers=1), 8, 16, None)
            return r.comm_exposed_s

        fast, slow = estimate(100.0), estimate(1.0)
        assert slow > fast > 0


# -- heterogeneous shard dealing (sampler) -----------------------------------
class TestSamplerWeighting:
    def _ranks(self, n, reps, weights=None, **kw):
        out = []
        for r in range(reps):
            s = ElasticDistributedSampler(
                n, num_replicas=reps, rank=r, shuffle=False, **kw
            )
            if weights is not None:
                s.set_throughput_weights(weights)
            out.append(s)
        return out

    def test_exactly_once_coverage(self):
        samplers = self._ranks(64, 4, weights=[4.0, 2.0, 1.0, 1.0])
        seen = []
        for s in samplers:
            seen.extend(list(s))
        assert sorted(seen) == list(range(64))  # no dup, no loss

    def test_proportional_shares(self):
        samplers = self._ranks(64, 4, weights=[4.0, 2.0, 1.0, 1.0])
        counts = [len(list(s)) for s in samplers]
        assert counts == [32, 16, 8, 8]

    def test_len_matches_actual_yields(self):
        for s in self._ranks(100, 4, weights=[3.0, 1.0, 1.0, 1.0]):
            n = len(s)
            assert n == len(list(s))

    def test_interleaves_instead_of_clumping(self):
        """Smooth WRR: a 3:1 split deals ~3 of every 4 consecutive
        positions to the heavy rank, not one long prefix run."""
        (heavy, light) = self._ranks(80, 2, weights=[3.0, 1.0])
        got = list(heavy)[:12]
        # the heavy rank never owns more than 3 consecutive positions
        diffs = np.diff(got)
        assert diffs.max() <= 4

    def test_none_restores_round_robin(self):
        a, b = self._ranks(16, 2, weights=[9.0, 1.0])
        a.set_throughput_weights(None)
        b.set_throughput_weights(None)
        assert list(a) == list(range(0, 16, 2))
        assert list(b) == list(range(1, 16, 2))

    def test_resume_mid_epoch_stays_exactly_once(self):
        w = [2.0, 1.0]
        a, b = self._ranks(60, 2, weights=w)
        it = iter(a)
        first_a = [next(it) for _ in range(6)]
        state = a.state_dict()
        # restore into a fresh sampler (restart) and drain the rest
        a2 = ElasticDistributedSampler(
            60, num_replicas=2, rank=0, shuffle=False
        )
        a2.load_state_dict(state)
        a2.set_throughput_weights(w)
        rest_a = list(a2)
        all_b = list(b)
        seen = sorted(first_a + rest_a + all_b)
        assert seen == list(range(60))

    def test_validation(self):
        s = ElasticDistributedSampler(16, num_replicas=2, rank=0)
        with pytest.raises(ValueError):
            s.set_throughput_weights([1.0])  # wrong length
        with pytest.raises(ValueError):
            s.set_throughput_weights([1.0, -1.0])  # non-positive

    def test_rewound_completed_equal_mode(self):
        s = ElasticDistributedSampler(64, num_replicas=2, rank=0)
        # historical arithmetic: owned samples x num_replicas
        assert s.rewound_completed(20, 3) == 14
        # negative borrow (previous-epoch rollover) preserved
        assert s.rewound_completed(2, 3) == -4

    def test_rewound_completed_weighted_replays_exactly(self):
        """Rewinding N owned samples under weighted dealing must land
        the cursor where re-iterating yields exactly those N samples
        again (the prefetch-rewind exactly-once contract)."""
        w = [3.0, 1.0]
        s = ElasticDistributedSampler(
            64, num_replicas=2, rank=0, shuffle=False
        )
        s.set_throughput_weights(w)
        it = iter(s)
        got = [next(it) for _ in range(6)]
        cursor = s.completed_num
        c2 = s.rewound_completed(cursor, 2)
        assert 0 <= c2 < cursor
        s2 = ElasticDistributedSampler(
            64, num_replicas=2, rank=0, shuffle=False
        )
        s2.load_state_dict({"epoch": 0, "completed_num": int(c2)})
        s2.set_throughput_weights(w)
        it2 = iter(s2)
        assert [next(it2) for _ in range(2)] == got[-2:]

    def test_trainer_maps_slice_weights_to_replicas(self):
        """apply_slice_throughput splits each slice's share evenly
        over its slice-major replicas (mesh.py hybrid dp layout)."""
        from types import SimpleNamespace

        from dlrover_tpu.trainer.elastic.trainer import ElasticTrainer

        sampler = ElasticDistributedSampler(
            64, num_replicas=4, rank=0, shuffle=False
        )
        fake = SimpleNamespace(
            accel=SimpleNamespace(
                strategy=Strategy(
                    mesh=MeshConfig(dp=4, dcn_axes=("dp",), slices=2)
                )
            ),
            sampler=sampler,
        )
        # slice 0 twice as fast -> 2/3 of the data, split over its 2
        # replicas -> [1/3, 1/3, 1/6, 1/6]
        ElasticTrainer.apply_slice_throughput(fake, [1.0, 2.0])
        assert sampler._weights is not None
        np.testing.assert_allclose(
            sampler._weights, [1 / 3, 1 / 3, 1 / 6, 1 / 6]
        )
        # mismatched slice count resets to equal round-robin
        ElasticTrainer.apply_slice_throughput(fake, [1.0, 2.0, 3.0])
        assert sampler._weights is None
