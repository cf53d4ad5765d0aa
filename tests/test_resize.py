"""Elastic-resize fast path: AOT compile cache, speculative compiler,
on-device resharding, trainer resize, and the master's scale-candidate
publication (ISSUE 2)."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.accel.compile_cache import (
    CompileCache,
    CompileTask,
    SpeculativeCompiler,
    fingerprint,
    mesh_signature,
    tree_signature,
)
from dlrover_tpu.accel.strategy import Strategy
from dlrover_tpu.models import tiny
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh


def _named_sharding(mesh, *spec):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P(*spec))


class TestCompileCache:
    def test_get_or_build_memoizes(self):
        cache = CompileCache(capacity=4)
        calls = []

        def build():
            calls.append(1)
            return object()

        a, hit_a = cache.get_or_build("k1", build)
        b, hit_b = cache.get_or_build("k1", build)
        assert a is b and not hit_a and hit_b
        assert len(calls) == 1
        assert cache.hits == 1 and cache.misses == 1
        assert cache.hit_pct == 50.0

    def test_get_or_compile_executable_roundtrip(self):
        cache = CompileCache(capacity=4)
        mesh = build_mesh(MeshConfig(dp=2), jax.devices()[:2])
        sh = _named_sharding(mesh, "dp")
        f = jax.jit(lambda x: x + 1)
        spec = jax.ShapeDtypeStruct((4, 2), jnp.float32, sharding=sh)
        key = fingerprint("t", mesh_signature(mesh))
        exe, hit = cache.get_or_compile(
            key, lambda: f.lower(spec).compile()
        )
        assert not hit
        exe2, hit2 = cache.get_or_compile(
            key, lambda: (_ for _ in ()).throw(AssertionError("rebuilt"))
        )
        assert hit2 and exe2 is exe
        x = jax.device_put(np.zeros((4, 2), np.float32), sh)
        np.testing.assert_array_equal(np.asarray(exe2(x)), 1.0)

    def test_lru_eviction(self):
        cache = CompileCache(capacity=2)
        for k in ("a", "b", "c"):
            cache.get_or_build(k, lambda: k)
        assert not cache.peek("a") and cache.peek("b") and cache.peek("c")

    def test_stats_record_attached(self):
        from dlrover_tpu.accel.profiler import PipelineStats

        stats = PipelineStats()
        cache = CompileCache(stats=stats)
        cache.get_or_build("x", lambda: 1)
        cache.get_or_build("x", lambda: 1)
        assert stats.compile_cache_misses == 1
        assert stats.compile_cache_hits == 1
        assert stats.compile_cache_hit_pct == 50.0
        d = stats.as_dict()
        assert d["compile_cache_hit_pct"] == 50.0
        assert d["reshard_bytes_device_vs_host"] == [0, 0]

    def test_disk_layer_warm_starts_a_fresh_cache(self, tmp_path):
        """A second cache instance (the replacement-worker analog) must
        load the serialized executable instead of recompiling — or, on
        jaxlibs that cannot serialize executables, degrade to a miss
        (never an error)."""
        from dlrover_tpu.common.jax_compat import serialize_compiled

        mesh = build_mesh(MeshConfig(dp=2), jax.devices()[:2])
        sh = _named_sharding(mesh, "dp")
        f = jax.jit(lambda x: x * 3)
        spec = jax.ShapeDtypeStruct((4, 2), jnp.float32, sharding=sh)
        key = fingerprint("disk", mesh_signature(mesh))
        c1 = CompileCache(cache_dir=str(tmp_path))
        exe, _ = c1.get_or_compile(key, lambda: f.lower(spec).compile())
        serializable = serialize_compiled(exe) is not None
        c2 = CompileCache(cache_dir=str(tmp_path))
        exe2, hit = c2.get_or_compile(
            key, lambda: f.lower(spec).compile()
        )
        assert hit == serializable
        if serializable:
            assert c2.disk_hits == 1
        x = jax.device_put(np.ones((4, 2), np.float32), sh)
        np.testing.assert_array_equal(np.asarray(exe2(x)), 3.0)

    def test_tree_signature_spec_vs_concrete_collide(self):
        """The speculative compiler keys off ShapeDtypeStructs; the
        resize that consumes its work keys off live arrays — the keys
        must collide (weak_type excluded on purpose)."""
        mesh = build_mesh(MeshConfig(dp=2), jax.devices()[:2])
        sh = _named_sharding(mesh, "dp")
        live = {"w": jax.device_put(np.ones((4, 2), np.float32), sh)}
        spec = {
            "w": jax.ShapeDtypeStruct((4, 2), jnp.float32, sharding=sh)
        }
        assert tree_signature(live) == tree_signature(spec)


class TestSpeculativeCompiler:
    def test_background_compile_lands_in_cache(self):
        cache = CompileCache()
        built = []

        def build():
            built.append(1)
            return "exe"

        sc = SpeculativeCompiler(cache, budget_s=30.0)
        try:
            sc.submit([CompileTask(label="m1", key="k1", build=build)])
            assert sc.wait_idle(10.0)
            assert cache.peek("k1") and built == [1]
            # already-cached keys are skipped without a build
            sc.submit([CompileTask(label="m1", key="k1", build=build)])
            assert sc.wait_idle(10.0)
            assert built == [1]
        finally:
            sc.close()

    def test_pause_defers_until_released(self):
        cache = CompileCache()
        paused = {"v": True}
        sc = SpeculativeCompiler(
            cache, pause_fn=lambda: paused["v"], budget_s=30.0
        )
        try:
            sc.submit(
                [CompileTask(label="m", key="kp", build=lambda: "exe")]
            )
            time.sleep(0.3)
            assert not cache.peek("kp")  # staging window holds it off
            paused["v"] = False
            assert sc.wait_idle(10.0)
            assert cache.peek("kp")
        finally:
            sc.close()

    def test_budget_drops_remaining_candidates(self):
        cache = CompileCache()
        sc = SpeculativeCompiler(cache, budget_s=0.0)
        try:
            sc.submit(
                [CompileTask(label="m", key="kb", build=lambda: "exe")]
            )
            assert sc.wait_idle(10.0)
            assert not cache.peek("kb") and sc.dropped == 1
        finally:
            sc.close()

    def test_stale_task_not_requeued_after_replacement(self):
        """A task popped under pause must not resurrect into a queue a
        newer submit() has since replaced (a resize discards stale
        predictions; the old-world candidate would burn the fresh
        budget and an LRU slot)."""
        cache = CompileCache()
        paused = {"v": True}
        sc = SpeculativeCompiler(
            cache, pause_fn=lambda: paused["v"], budget_s=30.0
        )
        try:
            sc.submit(
                [CompileTask(label="old", key="kold", build=lambda: "e")]
            )
            time.sleep(0.2)  # worker pops and requeues under pause
            sc.submit(())  # the prediction is replaced
            paused["v"] = False
            assert sc.wait_idle(10.0)
            time.sleep(0.2)
            assert not cache.peek("kold")
        finally:
            sc.close()

    def test_build_error_does_not_kill_the_thread(self):
        cache = CompileCache()
        sc = SpeculativeCompiler(cache, budget_s=30.0)

        def boom():
            raise RuntimeError("bad candidate")

        try:
            sc.submit(
                [
                    CompileTask(label="bad", key="kx", build=boom),
                    CompileTask(
                        label="good", key="ky", build=lambda: "exe"
                    ),
                ]
            )
            assert sc.wait_idle(10.0)
            assert sc.errors == 1 and cache.peek("ky")
        finally:
            sc.close()


def _sharded_tree(mesh, rows=(8, 16)):
    """A state-like tree with replicated + sharded leaves (distinct
    bit patterns so a stitch error cannot cancel out). ``rows`` sizes
    the sharded leaves — they must divide by every fsdp size used."""
    rng = np.random.default_rng(7)
    rep = _named_sharding(mesh)
    row = _named_sharding(mesh, "fsdp")
    return {
        "scalar": jax.device_put(
            jnp.asarray(np.float32(3.25)), rep
        ),
        "rep": jax.device_put(
            rng.standard_normal((5, 3)).astype(np.float32), rep
        ),
        "sharded": jax.device_put(
            rng.standard_normal((rows[0], 6)).astype(np.float32), row
        ),
        "ints": jax.device_put(
            rng.integers(0, 1 << 30, (rows[1],)).astype(np.int32), row
        ),
    }


def _spec_like(tree, mesh):
    rep = _named_sharding(mesh)
    row = _named_sharding(mesh, "fsdp")

    def spec(path_is_sharded, leaf):
        sh = row if path_is_sharded else rep
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=sh)

    return {
        "scalar": spec(False, tree["scalar"]),
        "rep": spec(False, tree["rep"]),
        "sharded": spec(True, tree["sharded"]),
        "ints": spec(True, tree["ints"]),
    }


class TestReshard:
    def _roundtrip_via_shm_records(self, state, spec):
        """The slow path the reshard replaces: host shard records →
        restore_state (what a shm save/restore does, minus the shm)."""
        from dlrover_tpu.ckpt.sharding import (
            host_shard_records,
            restore_state,
        )

        records = host_shard_records(state)
        by_path = {}
        for r in records:
            by_path.setdefault(r.path, []).append(r)
        return restore_state(spec, lambda p: by_path.get(p, []))

    @pytest.mark.parametrize("old_n,new_n", [(4, 2), (2, 4), (4, 6)])
    def test_bitwise_identical_to_shm_roundtrip(self, old_n, new_n):
        """Acceptance: the on-device reshard must be bitwise-identical
        to a shm save/restore round-trip of the same resize. The 4→6
        case covers a non-power-of-two target world."""
        from dlrover_tpu.ckpt.reshard import reshard_state

        old = build_mesh(MeshConfig(fsdp=old_n), jax.devices()[:old_n])
        new = build_mesh(MeshConfig(fsdp=new_n), jax.devices()[:new_n])
        # sharded-leaf rows must divide by every fsdp size in the pair
        rows = (12, 24) if 6 in (old_n, new_n) else (8, 16)
        state = _sharded_tree(old, rows=rows)
        spec = _spec_like(state, new)
        resharded, report = reshard_state(state, spec)
        expected = self._roundtrip_via_shm_records(state, spec)
        for path in state:
            a = np.asarray(resharded[path])
            b = np.asarray(expected[path])
            assert a.tobytes() == b.tobytes(), path
            assert resharded[path].sharding == spec[path].sharding
        assert not report.fallback_paths
        assert report.device_bytes > 0 and report.host_bytes == 0

    def test_grow_requires_stitching_multiple_sources(self):
        """fsdp 4→2: each target shard is the concat of two old shards
        (the multi-source assembly path)."""
        from dlrover_tpu.ckpt.reshard import reshard_state

        old = build_mesh(MeshConfig(fsdp=4), jax.devices()[:4])
        new = build_mesh(MeshConfig(fsdp=2), jax.devices()[:2])
        x = np.arange(32, dtype=np.float32).reshape(8, 4)
        state = {"w": jax.device_put(x, _named_sharding(old, "fsdp"))}
        spec = {
            "w": jax.ShapeDtypeStruct(
                (8, 4), jnp.float32,
                sharding=_named_sharding(new, "fsdp"),
            )
        }
        out, report = reshard_state(state, spec)
        np.testing.assert_array_equal(np.asarray(out["w"]), x)
        assert report.moved_leaves == 1

    def test_unchanged_sharding_is_reused(self):
        from dlrover_tpu.ckpt.reshard import reshard_state

        mesh = build_mesh(MeshConfig(fsdp=4), jax.devices()[:4])
        state = _sharded_tree(mesh)
        spec = _spec_like(state, mesh)
        out, report = reshard_state(state, spec)
        assert report.reused_leaves == len(state)
        assert out["sharded"] is state["sharded"]

    def test_hole_falls_back_and_merges(self):
        """A leaf with no surviving device source (a replacement
        worker's hole) is reported and filled by merge_fallback; the
        covered leaves keep their on-device arrays."""
        from dlrover_tpu.ckpt.reshard import (
            merge_fallback,
            reshard_state,
        )

        old = build_mesh(MeshConfig(fsdp=4), jax.devices()[:4])
        new = build_mesh(MeshConfig(fsdp=2), jax.devices()[:2])
        state = _sharded_tree(old)
        spec = _spec_like(state, new)
        holey = dict(state)
        holey["rep"] = jax.ShapeDtypeStruct(
            state["rep"].shape, state["rep"].dtype
        )  # no data survived for this leaf
        out, report = reshard_state(holey, spec)
        assert report.fallback_paths == ["rep"]
        assert report.host_bytes == state["rep"].nbytes
        restored = jax.device_put(
            np.asarray(state["rep"]), spec["rep"].sharding
        )
        merged = merge_fallback(
            out, {**out, "rep": restored}, report.fallback_paths
        )
        np.testing.assert_array_equal(
            np.asarray(merged["rep"]), np.asarray(state["rep"])
        )
        assert merged["sharded"] is out["sharded"]

    def test_report_carries_axis_changes_and_stitching(self):
        """Per-dimension reshard visibility (ISSUE 8 satellite): the
        report names which mesh axes changed degree, and counts the
        target shards assembled from multiple sources (fsdp 4->2:
        every target shard concatenates two old shards)."""
        from dlrover_tpu.ckpt.reshard import reshard_state

        old = build_mesh(MeshConfig(fsdp=4), jax.devices()[:4])
        new = build_mesh(MeshConfig(fsdp=2), jax.devices()[:2])
        x = np.arange(32, dtype=np.float32).reshape(8, 4)
        state = {"w": jax.device_put(x, _named_sharding(old, "fsdp"))}
        spec = {
            "w": jax.ShapeDtypeStruct(
                (8, 4), jnp.float32,
                sharding=_named_sharding(new, "fsdp"),
            )
        }
        _, report = reshard_state(state, spec)
        assert report.axis_changes == {"fsdp": (4, 2)}
        assert report.stitched_shards == 2  # both target shards
        assert "fsdp 4->2" in report.describe_axis_changes()

    def test_shape_change_is_a_clear_error(self):
        from dlrover_tpu.ckpt.reshard import reshard_state

        mesh = build_mesh(MeshConfig(fsdp=2), jax.devices()[:2])
        state = {"w": jax.device_put(np.zeros((4, 2), np.float32),
                                     _named_sharding(mesh))}
        spec = {
            "w": jax.ShapeDtypeStruct(
                (8, 2), jnp.float32, sharding=_named_sharding(mesh)
            )
        }
        with pytest.raises(ValueError, match="model change"):
            reshard_state(state, spec)


class TestReshardAxisChange:
    """ISSUE 8 satellite: axis-change stitching beyond the dp/fsdp
    absorb — tp-degree grow/shrink and non-pow2 dp x tp transitions,
    bitwise-parity with a shm save/restore round-trip (mirrors the
    existing 4->6 DP test)."""

    def _tp_tree(self, mesh):
        """Model-shaped leaves: a tp-column-sharded matmul weight, a
        tp-row-sharded output proj, a replicated norm scale. Dims
        divide by every tp degree used (2, 3, 4)."""
        rng = np.random.default_rng(11)
        return {
            "wq": jax.device_put(
                rng.standard_normal((8, 24)).astype(np.float32),
                _named_sharding(mesh, None, "tp"),
            ),
            "wo": jax.device_put(
                rng.standard_normal((24, 8)).astype(np.float32),
                _named_sharding(mesh, "tp", None),
            ),
            "scale": jax.device_put(
                rng.standard_normal((16,)).astype(np.float32),
                _named_sharding(mesh),
            ),
            "batchrow": jax.device_put(
                rng.standard_normal((12, 4)).astype(np.float32),
                _named_sharding(mesh, ("dp", "fsdp")),
            ),
        }

    def _tp_spec(self, tree, mesh):
        specs = {
            "wq": _named_sharding(mesh, None, "tp"),
            "wo": _named_sharding(mesh, "tp", None),
            "scale": _named_sharding(mesh),
            "batchrow": _named_sharding(mesh, ("dp", "fsdp")),
        }
        return {
            k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=specs[k])
            for k, v in tree.items()
        }

    def _roundtrip_via_shm_records(self, state, spec):
        from dlrover_tpu.ckpt.sharding import (
            host_shard_records,
            restore_state,
        )

        records = host_shard_records(state)
        by_path = {}
        for r in records:
            by_path.setdefault(r.path, []).append(r)
        return restore_state(spec, lambda p: by_path.get(p, []))

    @pytest.mark.parametrize(
        "old_mc,old_n,new_mc,new_n",
        [
            # tp grow: dp2xtp2 -> dp2xtp4 (each new shard is a slice)
            (MeshConfig(dp=2, tp=2), 4, MeshConfig(dp=2, tp=4), 8),
            # tp shrink: tp4 -> tp2 (multi-source concat per shard)
            (MeshConfig(dp=2, tp=4), 8, MeshConfig(dp=2, tp=2), 4),
            # non-pow2 dp x tp transitions: 6 = 2x3 -> 3x2 reshapes
            # BOTH axes at once
            (MeshConfig(dp=2, tp=3), 6, MeshConfig(dp=3, tp=2), 6),
            (MeshConfig(dp=3, tp=2), 6, MeshConfig(dp=2, tp=3), 6),
        ],
    )
    def test_bitwise_parity_with_shm_roundtrip(
        self, old_mc, old_n, new_mc, new_n
    ):
        from dlrover_tpu.ckpt.reshard import reshard_state

        old = build_mesh(old_mc, jax.devices()[:old_n])
        new = build_mesh(new_mc, jax.devices()[:new_n])
        state = self._tp_tree(old)
        spec = self._tp_spec(state, new)
        resharded, report = reshard_state(state, spec)
        expected = self._roundtrip_via_shm_records(state, spec)
        for path in state:
            a = np.asarray(resharded[path])
            b = np.asarray(expected[path])
            assert a.tobytes() == b.tobytes(), path
            assert resharded[path].sharding == spec[path].sharding
        assert not report.fallback_paths
        assert report.host_bytes == 0
        assert "tp" in report.axis_changes
        old_tp = old_mc.tp
        new_tp = new_mc.tp
        assert report.axis_changes["tp"] == (old_tp, new_tp)
        if new_tp < old_tp:
            # a tp shrink concatenates old shards: stitching must
            # actually have run
            assert report.stitched_shards > 0


class TestReshardPipelineExpertAxes:
    """ISSUE 13 satellite: warm-resize reshard coverage for pp/ep
    axis-degree changes. ``ReshardReport.axis_changes`` already
    reports them generically; these pin the bitwise grow/shrink
    behavior for stage-stacked and expert-sharded trees (the state
    layouts ``pipeline_state_shardings`` / the ep rules produce),
    alongside ``TestReshardAxisChange``'s tp/dp cases. No test takes
    a dp x pp world through a resize and a step (ROADMAP Queue 2)."""

    def _staged_tree(self, mesh):
        """Pipeline-shaped leaves: a stage-stacked layer weight
        ([stages, lc, d, d] sharded over pp on dim 0), an
        expert-stacked FFN weight ([E, d, f] over ep on dim 0), and a
        replicated head. Dims divide by every degree used (2, 4)."""
        rng = np.random.default_rng(13)
        return {
            "stages": jax.device_put(
                rng.standard_normal((4, 2, 8, 8)).astype(np.float32),
                _named_sharding(mesh, "pp"),
            ),
            "experts": jax.device_put(
                rng.standard_normal((4, 8, 16)).astype(np.float32),
                _named_sharding(mesh, "ep"),
            ),
            "head": jax.device_put(
                rng.standard_normal((8, 12)).astype(np.float32),
                _named_sharding(mesh),
            ),
        }

    def _spec(self, tree, mesh):
        specs = {
            "stages": _named_sharding(mesh, "pp"),
            "experts": _named_sharding(mesh, "ep"),
            "head": _named_sharding(mesh),
        }
        return {
            k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=specs[k])
            for k, v in tree.items()
        }

    @pytest.mark.parametrize(
        "old_mc,old_n,new_mc,new_n,axis",
        [
            # pp grow: pp2 -> pp4 (each new stage shard is a slice)
            (
                MeshConfig(pp=2, dp=2), 4,
                MeshConfig(pp=4, dp=2), 8, "pp",
            ),
            # pp shrink: pp4 -> pp2 (multi-source concat per shard)
            (
                MeshConfig(pp=4, dp=2), 8,
                MeshConfig(pp=2, dp=2), 4, "pp",
            ),
            # ep grow / shrink
            (
                MeshConfig(ep=2, dp=2), 4,
                MeshConfig(ep=4, dp=2), 8, "ep",
            ),
            (
                MeshConfig(ep=4, dp=2), 8,
                MeshConfig(ep=2, dp=2), 4, "ep",
            ),
            # dp2 x pp2 -> dp4 x pp2: dp absorbs the delta, stages
            # stay put (the warm-resize shape of a pipeline world)
            (
                MeshConfig(pp=2, dp=2), 4,
                MeshConfig(pp=2, dp=4), 8, "dp",
            ),
        ],
    )
    def test_bitwise_grow_shrink(
        self, old_mc, old_n, new_mc, new_n, axis
    ):
        from dlrover_tpu.ckpt.reshard import reshard_state

        old = build_mesh(old_mc, jax.devices()[:old_n])
        new = build_mesh(new_mc, jax.devices()[:new_n])
        state = self._staged_tree(old)
        spec = self._spec(state, new)
        resharded, report = reshard_state(state, spec)
        assert not report.fallback_paths
        assert report.host_bytes == 0
        assert axis in report.axis_changes
        assert report.axis_changes[axis] == (
            getattr(old_mc, axis), getattr(new_mc, axis)
        )
        for path in state:
            a = np.asarray(resharded[path])
            b = np.asarray(state[path])
            assert a.tobytes() == b.tobytes(), path
            assert resharded[path].sharding == spec[path].sharding


class TestMeshCandidates:
    """Satellite: candidate enumeration with non-power-of-two device
    counts must produce a valid mesh or a clear error, never a crash."""

    def test_from_dict_ignores_unknown_keys(self):
        m = MeshConfig.from_dict({"dp": 6, "bogus": 7, "tp": 1})
        assert m.dp == 6 and m.num_devices == 6

    def test_build_mesh_six_of_eight(self):
        mesh = build_mesh(MeshConfig(dp=6), jax.devices()[:6])
        assert mesh.devices.size == 6

    def test_build_mesh_count_mismatch_is_clear(self):
        with pytest.raises(ValueError, match="needs 4 devices, have 6"):
            build_mesh(MeshConfig(dp=4), jax.devices()[:6])

    def test_candidates_six_devices_divisible_batch(self):
        from dlrover_tpu.accel.candidates import candidate_strategies

        cands = candidate_strategies(tiny(), 6, batch=12, seq=64)
        assert cands
        assert all(c.mesh.num_devices == 6 for c in cands)
        # every candidate must build a real mesh on 6 devices
        for c in cands[:3]:
            mesh = build_mesh(c.mesh, jax.devices()[:6])
            assert mesh.devices.size == 6

    def test_candidates_six_devices_indivisible_batch_empty(self):
        from dlrover_tpu.accel.candidates import candidate_strategies

        # batch 8 cannot shard over any 6-device factorization of this
        # model: the enumeration must come back empty (the caller turns
        # that into a clear error), not crash
        assert candidate_strategies(tiny(), 6, batch=8, seq=64) == []


class _Tokens:
    def __init__(self, n=128, seq=16, vocab=256):
        rng = np.random.default_rng(0)
        self.data = rng.integers(0, vocab, (n, seq + 1), dtype=np.int32)

    def __len__(self):
        return len(self.data)

    def __getitem__(self, i):
        return {"x": self.data[i][:-1], "y": self.data[i][1:]}


def _make_trainer(**overrides):
    from dlrover_tpu.trainer.elastic.trainer import (
        ElasticTrainer,
        TrainerConfig,
    )

    kw = dict(
        batch_size=8,
        seq_len=16,
        report_metrics=False,
        log_interval=1000,
        prefetch=2,
        donation_aware=False,
        speculative_compile=False,
    )
    kw.update(overrides.pop("tcfg", {}))
    dataset = overrides.pop("dataset", None) or _Tokens()
    return ElasticTrainer(
        # 1 layer: these tests exercise resize machinery, not the
        # model — every saved compile second keeps tier-1 in budget
        model_cfg=tiny(num_layers=1),
        tx=optax.adamw(1e-2),
        dataset=dataset,
        trainer_cfg=TrainerConfig(**kw),
        strategy=Strategy(mesh=MeshConfig(dp=4), dtype="float32"),
        devices=jax.devices()[:4],
        **overrides,
    )


class TestTrainerResize:
    def test_resize_fast_path_end_to_end(self, tmp_path, monkeypatch):
        """ONE trainer covers the whole fast-path story (trainer
        construction + XLA compiles dominate tier-1 wall time, so the
        scenarios share it; the cold-resize leg is separately gated by
        TestResizeBenchSmoke):

        - prediction loop: master publishes candidate_worker_counts →
          tuner file → trainer poll (a poll before the first step must
          leave candidates unconsumed) → background pre-lower, with
          invalid candidates (6 can't shard batch 8; 999 exceeds the
          pool) skipped via a clear error, not a crash;
        - the resize that lands on a predicted mesh is a cache HIT;
        - satellite: the prefetcher is closed and the live sampler
          rewound by the buffered lookahead BEFORE the reshard runs;
        - params are bitwise-preserved across the remap;
        - satellite: eval is memoized per mesh — resizing A→B→A hands
          back the SAME jitted eval step for A, no re-jit;
        - training continues after each resize and the stats record
          hits/reshard bytes."""
        import dataclasses
        import json

        from dlrover_tpu.ckpt import reshard as reshard_mod
        from dlrover_tpu.common import comm
        from dlrover_tpu.common.constants import ConfigPath, NodeEnv
        from dlrover_tpu.data.prefetch import DevicePrefetcher

        pc = comm.ParallelConfig(candidate_worker_counts=[2, 6, 999])
        cfgfile = tmp_path / "paral.json"
        cfgfile.write_text(json.dumps(dataclasses.asdict(pc)))
        monkeypatch.setenv(ConfigPath.ENV_PARAL_CONFIG, str(cfgfile))
        # 1 device per worker at this density, so worker counts map
        # 1:1 to device counts
        monkeypatch.setenv(NodeEnv.NUM_PROCESSES, str(len(jax.devices())))
        # a world size for the candidate mapping only: this process is
        # set up already and must not try to join a distributed system
        from dlrover_tpu.trainer.elastic import distributed

        monkeypatch.setattr(distributed, "_initialized", True)
        t = _make_trainer(
            tcfg={"speculative_compile": True},
            eval_dataset=_Tokens(n=16),
        )
        try:
            t.train(num_steps=1)
            assert t._last_candidates is None  # avals not known yet
            t.train(num_steps=2)
            assert t._last_candidates == [2, 6, 999]
            assert t._spec_compiler is not None
            assert t._spec_compiler.wait_idle(120.0)
            # satellite: a non-divisible count no longer raises — the
            # largest valid mesh <= n wins (6 can't shard batch 8; 4
            # can) and the surplus ranks would sit idle
            assert t._strategy_for_exact(6) is None
            degraded = t._strategy_for(6)
            assert degraded.mesh.num_devices == 4
            m1 = t.evaluate(max_batches=1)
            fn_a = t._eval.step_fn
            assert fn_a is not None
            before = [
                np.asarray(x).tobytes()
                for x in jax.tree_util.tree_leaves(t.state.params)
            ]
            # live prefetcher with device batches on the CURRENT mesh
            t._prefetcher = DevicePrefetcher(
                iter(t.dataloader), placement=t._device_batch, depth=2
            )
            deadline = time.time() + 10
            while (
                t._prefetcher.buffered_batches() < 2
                and time.time() < deadline
            ):
                time.sleep(0.01)
            buffered = t._prefetcher.buffered_batches()
            assert buffered > 0
            t.sampler.epoch, t.sampler.completed_num = 0, 64
            seen = {}
            real = reshard_mod.reshard_state

            def spy(state, spec, stats=None):
                seen.setdefault("prefetcher", t._prefetcher)
                seen.setdefault("completed", t.sampler.completed_num)
                return real(state, spec, stats=stats)

            monkeypatch.setattr(reshard_mod, "reshard_state", spy)
            r = t.resize(2)
            assert r["compile_cache_hit"] is True  # speculative win
            assert r["reshard_bytes_device"] > 0
            assert r["reshard_bytes_host"] == 0
            assert t.mesh.devices.size == 2
            # the satellite's race: prefetcher down, sampler rewound,
            # both BEFORE the reshard touched the state
            assert seen["prefetcher"] is None
            assert (
                seen["completed"]
                == 64 - buffered * 8 * t.sampler.num_replicas
            )
            after = [
                np.asarray(x).tobytes()
                for x in jax.tree_util.tree_leaves(t.state.params)
            ]
            assert before == after  # bitwise across the remap
            assert t._eval.step_fn is None  # stale wrapper dropped
            t.evaluate(max_batches=1)
            fn_b = t._eval.step_fn
            assert fn_b is not fn_a
            t.train(num_steps=4)
            warm = t.resize(4)  # primed by the first steps on dp4
            assert warm["compile_cache_hit"] is True
            m2 = t.evaluate(max_batches=1)
            assert t._eval.step_fn is fn_a  # memo hit, no re-jit
            assert np.isfinite(m1["eval_loss"])
            assert np.isfinite(m2["eval_loss"])
            t.train(num_steps=6)
            assert t.global_step == 6
            s = t.pipeline_stats
            assert s.resize_count == 2
            assert s.compile_cache_hit_pct and s.compile_cache_hit_pct > 0
            assert s.reshard_bytes_host == 0
        finally:
            t.close()


    def test_short_final_batch_falls_back_to_jit(self):
        """An AOT Compiled executable rejects avals the jit wrapper
        would retrace for — the dataloader's short final batch (124
        rows / batch 8 → a tail of 4) must run through the jit
        fallback, not crash the primed step."""
        t = _make_trainer(dataset=_Tokens(n=124), tcfg={"prefetch": 0})
        try:
            t.train(num_steps=16)  # step 16 is the 4-row tail batch
            assert t.global_step == 16
            assert t._programs.aot_exec is not None  # priming did happen
        finally:
            t.close()


class TestScaleCandidatePublication:
    def test_autoscaler_publishes_through_paral_config(self):
        from dlrover_tpu.master.job_auto_scaler import JobAutoScaler
        from dlrover_tpu.master.job_manager import JobManager
        from dlrover_tpu.master.paral_config import ParalConfigService

        svc = ParalConfigService()
        scaler = JobAutoScaler(
            JobManager(),
            target_nodes=4,
            node_unit=1,
            paral_config_service=svc,
        )
        assert scaler.predicted_scale_candidates() == [5, 3]
        scaler.publish_scale_candidates()
        cfg = svc.get_config(0)
        assert cfg.candidate_worker_counts == [5, 3]
        v0 = cfg.dataloader.version
        # unchanged prediction must not churn the config version (the
        # agents' tuner rewrites its file on every bump)
        scaler.publish_scale_candidates()
        assert svc.get_config(0).dataloader.version == v0
        # an optimizer recommendation leads the list
        scaler._last_recommendation = 8
        scaler.publish_scale_candidates()
        assert svc.get_config(0).candidate_worker_counts == [8, 5, 3]
        assert svc.get_config(0).dataloader.version == v0 + 1

    def test_scale_to_moves_the_prediction(self):
        from dlrover_tpu.master.job_auto_scaler import JobAutoScaler
        from dlrover_tpu.master.job_manager import JobManager
        from dlrover_tpu.master.paral_config import ParalConfigService

        svc = ParalConfigService()
        scaler = JobAutoScaler(
            JobManager(),
            target_nodes=4,
            node_unit=2,
            paral_config_service=svc,
        )
        scaler.scale_to(2)
        got = svc.get_config(0).candidate_worker_counts
        assert 4 in got  # one unit up from the new target

    def test_retune_keeps_standing_candidates(self):
        from dlrover_tpu.master.paral_config import ParalConfigService

        svc = ParalConfigService()
        svc.set_candidate_worker_counts([3, 5])
        svc.suggest_initial_config(batch_size=16)
        assert svc.get_config(0).candidate_worker_counts == [3, 5]


class TestReshardMultiRail:
    """ISSUE 16: warm-reshard movement striped across admitted rails
    (bitwise) and the opt-in int8 wire format (lossy, crc over the
    DECODED payload, idempotent on a second hop)."""

    def _state_and_spec(self, rows=1024, cols=64):
        from jax.sharding import NamedSharding, PartitionSpec as P

        old = build_mesh(MeshConfig(fsdp=4), jax.devices()[:4])
        new = build_mesh(MeshConfig(fsdp=2), jax.devices()[:2])
        x = np.random.default_rng(0).standard_normal(
            (rows, cols)
        ).astype(np.float32)
        sh_old = NamedSharding(old, P("fsdp"))
        sh_new = NamedSharding(new, P("fsdp"))
        state = {"w": jax.device_put(x, sh_old)}
        spec = {
            "w": jax.ShapeDtypeStruct(
                (rows, cols), jnp.float32, sharding=sh_new
            )
        }
        return x, state, spec

    def test_striped_movement_stays_bitwise(self):
        from dlrover_tpu.ckpt.reshard import reshard_state

        x, state, spec = self._state_and_spec()
        # 256 KiB payload: drop the floor so striping actually engages
        out, rep = reshard_state(state, spec, stripe_min_bytes=64 << 10)
        np.testing.assert_array_equal(np.asarray(out["w"]), x)
        assert rep.striped_leaves == 1
        assert sum(rep.stripe_rail_bytes.values()) == x.nbytes

    def test_default_floor_leaves_small_moves_serial(self):
        from dlrover_tpu.ckpt.reshard import reshard_state

        x, state, spec = self._state_and_spec()
        out, rep = reshard_state(state, spec)  # 256 KiB < 32 MiB floor
        np.testing.assert_array_equal(np.asarray(out["w"]), x)
        assert rep.striped_leaves == 0
        assert rep.stripe_rail_bytes == {}

    def test_int8_wire_bounded_and_idempotent(self):
        from dlrover_tpu.ckpt.reshard import reshard_state

        x, state, spec = self._state_and_spec()
        out8, rep8 = reshard_state(state, spec, wire_format="int8")
        got = np.asarray(out8["w"])
        assert rep8.wire_format == "int8"
        assert rep8.decoded_crc32 is not None
        assert not np.array_equal(got, x)  # lossy by design
        assert np.max(np.abs(got - x)) <= np.max(np.abs(x)) / 127 * 1.01
        # idempotent: resharding the decoded state reproduces the
        # bytes AND the digest — the bitwise-restore gate's premise
        state2 = {
            "w": jax.device_put(got, state["w"].sharding)
        }
        out8b, rep8b = reshard_state(state2, spec, wire_format="int8")
        np.testing.assert_array_equal(np.asarray(out8b["w"]), got)
        assert rep8b.decoded_crc32 == rep8.decoded_crc32

    def test_striped_int8_same_digest_as_serial_int8(self):
        from dlrover_tpu.ckpt.reshard import reshard_state

        x, state, spec = self._state_and_spec()
        _, rep_serial = reshard_state(state, spec, wire_format="int8")
        out, rep = reshard_state(
            state, spec, wire_format="int8", stripe_min_bytes=64 << 10
        )
        assert rep.striped_leaves == 1
        assert rep.decoded_crc32 == rep_serial.decoded_crc32
        got = np.asarray(out["w"])
        assert np.max(np.abs(got - x)) <= np.max(np.abs(x)) / 127 * 1.01

    def test_unknown_wire_format_is_a_clear_error(self):
        from dlrover_tpu.ckpt.reshard import reshard_state

        _, state, spec = self._state_and_spec()
        with pytest.raises(ValueError, match="wire_format"):
            reshard_state(state, spec, wire_format="int4")
