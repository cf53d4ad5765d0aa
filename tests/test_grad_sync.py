"""Overlap-scheduled gradient sync (parallel/grad_sync.py) + the
satellite fixes that ride with it: fp32 microbatch accumulation,
grad_accum equivalence, fused grad-norm, PipelineStats coverage,
dry-runner comm terms, strategy/opt_lib plumbing."""

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
    shard_batch,
)
from dlrover_tpu.parallel.grad_sync import (
    BucketPlan,
    ensure_residual,
    plan_buckets,
    resolve_plan,
    strip_residual,
    sync_grads,
    zero_residual,
)
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh


def _mesh(n=2):
    return build_mesh(MeshConfig(dp=n), devices=jax.devices()[:n])


def _batch(cfg, batch=8, seq=16, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)


def _fp32_tiny(**kw):
    return dc_replace(
        tiny(num_layers=1), dtype="float32", param_dtype="float32", **kw
    )


def _first_moment(state):
    """Adam's mu leaves after ONE step from zero: (1 - b1) * grad."""
    nodes = jax.tree_util.tree_leaves(
        state.opt_state, is_leaf=lambda t: hasattr(t, "mu")
    )
    (adam,) = [t for t in nodes if hasattr(t, "mu")]
    return jax.tree_util.tree_leaves(adam.mu)


def _assert_same_adam_step(sa, sb, atol, lr=1e-2, eps=1e-8):
    """Two programs that compute the same gradient in another summation
    order, compared after one AdamW step. The gradients must agree to
    fp32 rounding everywhere. The params must agree to ``atol`` wherever
    Adam's ``g / (|g| + eps)`` is well conditioned; where a nonzero |g|
    is within 100x of eps that quotient turns a 1e-10 rounding
    difference into a visible one (measured on jax 0.9.0: one element of
    8192 with g = -4.6e-9 in both programs, apart by 5.8e-11, moved
    2.7e-5), so there the bound is the largest step Adam can take,
    ``lr``."""
    for a, c, ma, mc in zip(
        jax.tree_util.tree_leaves(sa.params),
        jax.tree_util.tree_leaves(sb.params),
        _first_moment(sa),
        _first_moment(sb),
    ):
        a, c = np.asarray(a), np.asarray(c)
        ga, gc = np.asarray(ma) / 0.1, np.asarray(mc) / 0.1
        np.testing.assert_allclose(ga, gc, rtol=1e-4, atol=1e-7)
        ill = (np.minimum(np.abs(ga), np.abs(gc)) <= 100 * eps) & (
            (ga != 0) | (gc != 0)
        )
        np.testing.assert_allclose(a[~ill], c[~ill], atol=atol)
        np.testing.assert_allclose(a[ill], c[ill], atol=lr)
        assert ill.mean() < 0.01  # the carve-out stays a carve-out


# -- bucket planning --------------------------------------------------------
class TestBucketPlan:
    def test_partitions_whole_tree_in_order(self):
        shapes = {
            "a": jax.ShapeDtypeStruct((100,), jnp.float32),
            "b": jax.ShapeDtypeStruct((300,), jnp.float32),
            "c": jax.ShapeDtypeStruct((50,), jnp.float32),
        }
        plan = plan_buckets(shapes, dp=2, bucket_bytes=1200)
        # leaves cover [0, 3) contiguously, no gaps or overlap
        spans = [(b.start, b.stop) for b in plan.buckets]
        assert spans[0][0] == 0 and spans[-1][1] == 3
        for (s0, e0), (s1, e1) in zip(spans, spans[1:]):
            assert e0 == s1
        assert sum(b.elems for b in plan.buckets) == 450

    def test_bucket_size_target_and_padding(self):
        shapes = [jax.ShapeDtypeStruct((101,), jnp.float32)] * 8
        plan = plan_buckets(shapes, dp=4, bucket_bytes=2 * 101 * 4)
        assert plan.num_buckets == 4  # two leaves per bucket
        for b in plan.buckets:
            assert b.elems == 202
            assert b.padded % 4 == 0 and b.padded >= b.elems

    def test_oversized_leaf_gets_own_bucket(self):
        shapes = [
            jax.ShapeDtypeStruct((10,), jnp.float32),
            jax.ShapeDtypeStruct((10_000,), jnp.float32),
            jax.ShapeDtypeStruct((10,), jnp.float32),
        ]
        plan = plan_buckets(shapes, dp=2, bucket_bytes=1024)
        big = [b for b in plan.buckets if b.elems == 10_000]
        assert len(big) == 1

    def test_wire_accounting_int8_vs_raw(self):
        shapes = [jax.ShapeDtypeStruct((1000,), jnp.float32)] * 4
        raw = plan_buckets(shapes, dp=2, bucket_bytes=1 << 20)
        q = plan_buckets(
            shapes, dp=2, bucket_bytes=1 << 20, compress="int8"
        )
        assert raw.wire_bytes == raw.raw_bytes == 16_000
        # 1 byte/elem + 4-byte scale per bucket: ~25% of fp32
        assert q.raw_bytes == 16_000
        assert q.wire_bytes <= 0.30 * q.raw_bytes

    def test_rejects_unknown_compression(self):
        with pytest.raises(ValueError, match="compression"):
            plan_buckets(
                [jax.ShapeDtypeStruct((4,), jnp.float32)],
                dp=2,
                compress="fp4",
            )


# -- sync_grads unit level --------------------------------------------------
class TestSyncGrads:
    def _stacked(self, mesh, dp, tree):
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh = NamedSharding(mesh, P(("dp",)))
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(a, sh), tree
        )

    def test_fp32_sync_is_exact_mean_multi_bucket(self):
        mesh = _mesh(2)
        rng = np.random.default_rng(0)
        tree = {
            "w": rng.standard_normal((2, 64, 3)).astype(np.float32),
            "b": rng.standard_normal((2, 37)).astype(np.float32),
        }
        shapes = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), tree
        )
        # force >1 bucket so bucket boundaries are exercised
        plan = plan_buckets(shapes, dp=2, bucket_bytes=256)
        assert plan.num_buckets > 1
        stacked = self._stacked(mesh, 2, tree)
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

    def test_int8_error_bounded_and_residual_carries(self):
        mesh = _mesh(2)
        rng = np.random.default_rng(1)
        tree = {"w": rng.standard_normal((2, 500)).astype(np.float32)}
        shapes = {"w": jax.ShapeDtypeStruct((500,), jnp.float32)}
        plan = plan_buckets(
            shapes, dp=2, bucket_bytes=1 << 20, compress="int8"
        )
        stacked = self._stacked(mesh, 2, tree)
        res0 = zero_residual(plan, mesh)
        synced, res1, _ = jax.jit(
            lambda t, r: sync_grads(t, mesh, plan, residual=r)
        )(stacked, res0)
        ref = tree["w"].mean(axis=0)
        # per-device rounding error <= scale/2 per element; the mean
        # keeps that bound
        scale = np.abs(tree["w"]).max() / 127.0
        assert float(np.abs(np.asarray(synced["w"]) - ref).max()) <= (
            scale / 2 + 1e-6
        )
        # the dropped quantization error is exactly the new residual
        assert res1 is not None and len(res1) == plan.num_buckets
        assert float(np.abs(np.asarray(res1[0])).max()) > 0

    def test_int8_without_residual_is_structure_preserving(self):
        mesh = _mesh(2)
        tree = {"w": np.ones((2, 16), np.float32)}
        shapes = {"w": jax.ShapeDtypeStruct((16,), jnp.float32)}
        plan = plan_buckets(
            shapes, dp=2, bucket_bytes=1 << 20, compress="int8"
        )
        stacked = self._stacked(mesh, 2, tree)
        synced, res, _ = jax.jit(
            lambda t: sync_grads(t, mesh, plan, residual=None)
        )(stacked)
        assert res is None
        np.testing.assert_allclose(
            np.asarray(synced["w"]), np.ones(16), atol=1e-2
        )


# -- train-step integration -------------------------------------------------
class TestTrainStepSync:
    def test_overlap_matches_gspmd_exactly(self):
        cfg = _fp32_tiny()
        mesh = _mesh(2)
        tx = optax.adamw(1e-2)
        state, _ = init_sharded_state(
            jax.random.PRNGKey(0), cfg, mesh, tx
        )
        x = _batch(cfg)
        b = shard_batch({"x": x, "y": x}, mesh)
        base = build_train_step(cfg, mesh, tx, donate=False)
        sync = build_train_step(
            cfg, mesh, tx, donate=False, comm_overlap=True
        )
        s0, m0 = base(state, b["x"], b["y"])
        s1, m1 = sync(state, b["x"], b["y"])
        assert abs(float(m0["loss"]) - float(m1["loss"])) < 1e-5
        # the fused bucket-walk grad norm replaces optax.global_norm
        assert abs(
            float(m0["grad_norm"]) - float(m1["grad_norm"])
        ) < 1e-4
        _assert_same_adam_step(s0, s1, atol=1e-5)

    def test_grad_accum_syncs_once_per_step(self):
        """The K× wire saving: under grad_accum=K the explicit path
        accumulates LOCAL grads and issues each bucket's collective
        exactly once per optimizer step — asserted on the lowered HLO
        (one reduce_scatter per bucket, none inside the scan)."""
        cfg = _fp32_tiny()
        mesh = _mesh(2)
        tx = optax.adamw(1e-2)
        state, _ = init_sharded_state(
            jax.random.PRNGKey(0), cfg, mesh, tx
        )
        x = _batch(cfg)
        b = shard_batch({"x": x, "y": x}, mesh)
        plan = resolve_plan(
            cfg,
            __import__(
                "dlrover_tpu.accel.strategy", fromlist=["Strategy"]
            ).Strategy(
                mesh=MeshConfig(dp=2), comm_overlap=True
            ),
        )
        for k in (1, 4):
            step = build_train_step(
                cfg, mesh, tx, donate=False,
                comm_overlap=True, grad_accum=k,
            )
            txt = step.lower(state, b["x"], b["y"]).as_text()
            n_rs = len(re.findall(r"reduce_scatter", txt))
            assert n_rs == plan.num_buckets, (
                f"grad_accum={k}: {n_rs} reduce_scatters vs "
                f"{plan.num_buckets} buckets — sync must run exactly "
                f"once per optimizer step"
            )

    # slow tier (budget): the ga-sync *structure* is tier-1-covered by
    # test_grad_accum_syncs_once_per_step (HLO) and its semantics by
    # TestGradAccumEquivalence; this cross-checks the two combined
    @pytest.mark.slow
    def test_grad_accum_sync_numerics(self):
        cfg = _fp32_tiny()
        mesh = _mesh(2)
        tx = optax.adamw(1e-2)
        state, _ = init_sharded_state(
            jax.random.PRNGKey(0), cfg, mesh, tx
        )
        x = _batch(cfg)
        b = shard_batch({"x": x, "y": x}, mesh)
        s1, m1 = build_train_step(
            cfg, mesh, tx, donate=False, comm_overlap=True
        )(state, b["x"], b["y"])
        s4, m4 = build_train_step(
            cfg, mesh, tx, donate=False, comm_overlap=True,
            grad_accum=4,
        )(state, b["x"], b["y"])
        assert abs(float(m1["loss"]) - float(m4["loss"])) < 1e-5
        _assert_same_adam_step(s1, s4, atol=2e-5)

    def test_int8_error_feedback_convergence_parity(self):
        """int8+EF training tracks the fp32 baseline's loss on the same
        data/init."""
        cfg = _fp32_tiny()
        mesh = _mesh(2)
        tx = optax.adamw(1e-2)
        x = _batch(cfg, batch=8, seq=16)
        b = shard_batch({"x": x, "y": x}, mesh)

        def run(compress):
            state, _ = init_sharded_state(
                jax.random.PRNGKey(0), cfg, mesh, tx
            )
            step = build_train_step(
                cfg, mesh, tx, donate=False, comm_overlap=True,
                grad_compress=compress, grad_bucket_mb=1,
            )
            if compress == "int8":
                plan = plan_buckets(
                    jax.eval_shape(lambda: state.params),
                    dp=2, bucket_bytes=1 << 20, compress="int8",
                )
                state = ensure_residual(state, plan, mesh)
            for _ in range(12):
                state, m = step(state, b["x"], b["y"])
            return float(m["loss"]), state

        loss_fp32, _ = run("none")
        loss_int8, s8 = run("int8")
        assert abs(loss_int8 - loss_fp32) < 0.05
        # residual persisted across steps (the EF state is live)
        assert s8.grad_residual is not None

    def test_donating_twin_keeps_the_explicit_sync(self):
        """auto_accelerate strategies carry the grad-sync knobs as
        un-applied opt NAMES; the donating twin must resolve them the
        same way the primary step does, or donated steps silently run
        the GSPMD sync (and skip the error-feedback update)."""
        from dlrover_tpu.accel.accelerate import auto_accelerate
        from dlrover_tpu.accel.strategy import Strategy

        cfg = _fp32_tiny()
        tx = optax.adamw(1e-2)
        res = auto_accelerate(
            cfg, tx, batch=8, seq=16,
            devices=jax.devices()[:2],
            strategy=Strategy(mesh=MeshConfig(dp=2), dtype="float32"),
            donate=False,
            optimizations=("grad_compress",),
        )
        assert res.donating_step_fn is not None
        # knobs arrived as opt names, not fields
        assert res.strategy.comm_overlap is False
        assert "grad_compress" in res.strategy.opts
        state, _ = init_sharded_state(
            jax.random.PRNGKey(0), res.cfg, res.mesh, tx
        )
        plan = resolve_plan(res.cfg, res.strategy)
        state = ensure_residual(state, plan, res.mesh)
        x = _batch(cfg)
        b = shard_batch({"x": x, "y": x}, res.mesh)
        for fn in (res.step_fn, res.donating_step_fn):
            txt = fn.lower(state, b["x"], b["y"]).as_text()
            assert len(re.findall(r"reduce_scatter", txt)) == (
                plan.num_buckets
            )

    def test_unsupported_mesh_falls_back(self):
        """pp/ep candidates must still build when comm_overlap is
        stamped across the whole candidate list (fsdp and tp meshes
        now take the explicit path — tests/test_hybrid_sync.py)."""
        cfg = _fp32_tiny()
        mesh = build_mesh(
            MeshConfig(pp=2), devices=jax.devices()[:2]
        )
        tx = optax.adamw(1e-2)
        state, _ = init_sharded_state(
            jax.random.PRNGKey(0), cfg, mesh, tx
        )
        x = _batch(cfg)
        b = shard_batch({"x": x, "y": x}, mesh)
        step = build_train_step(
            cfg, mesh, tx, donate=False, comm_overlap=True
        )
        _, m = step(state, b["x"], b["y"])
        assert np.isfinite(float(m["loss"]))


# -- satellite: fp32 accumulation under grad_accum --------------------------
def _bf16_ga_fixture():
    cfg = dc_replace(
        tiny(num_layers=1),
        dtype="bfloat16",
        param_dtype="bfloat16",
    )
    mesh = build_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    tx = optax.sgd(1.0)
    state, _ = init_sharded_state(jax.random.PRNGKey(0), cfg, mesh, tx)
    x = _batch(cfg)
    b = shard_batch({"x": x, "y": x}, mesh)
    return cfg, mesh, tx, state, b


class TestFp32Accumulation:
    def test_bf16_params_accumulate_in_fp32_hlo(self):
        """build_train_step used to seed the scan carry with
        zeros_like(params): bf16 params accumulated microbatch grads
        in bf16, losing low bits every add. The carry must be fp32 —
        visible in the lowered HLO as param-shaped f32 accumulators
        (lower-only: no compile, so this regression tripwire stays
        tier-1-cheap; the numeric cross-check is the slow twin)."""
        cfg, mesh, tx, state, b = _bf16_ga_fixture()
        step = build_train_step(
            cfg, mesh, tx, donate=False, grad_accum=4
        )
        txt = step.lower(state, b["x"], b["y"]).as_text()
        acc_shape = f"tensor<{cfg.vocab_size}x{cfg.model_dim}xf32>"
        assert acc_shape in txt, (
            "grad_accum scan must carry fp32 accumulators for bf16 "
            "params (none found in the lowered HLO)"
        )

    @pytest.mark.slow
    def test_bf16_params_fp32_accumulation_numerics(self):
        """Numeric twin of the HLO check: the ga step must match an
        explicit fp32-accumulate-then-cast reference."""
        from dlrover_tpu.models.transformer import loss_fn

        cfg, mesh, tx, state, b = _bf16_ga_fixture()
        x = np.asarray(b["x"])
        K = 4
        step = build_train_step(
            cfg, mesh, tx, donate=False, grad_accum=K
        )
        s_new, _ = step(state, b["x"], b["y"])
        mb = x.shape[0] // K
        acc = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), state.params
        )
        for i in range(K):
            g = jax.grad(
                lambda q: loss_fn(
                    q,
                    b["x"][i * mb : (i + 1) * mb],
                    b["y"][i * mb : (i + 1) * mb],
                    cfg,
                    mesh,
                )
            )(state.params)
            acc = jax.tree_util.tree_map(
                lambda a, gg: a + gg.astype(jnp.float32), acc, g
            )
        ref = jax.tree_util.tree_map(
            lambda a, p: (a / K).astype(p.dtype), acc, state.params
        )
        got = jax.tree_util.tree_map(
            lambda p0, p1: p0 - p1, state.params, s_new.params
        )
        for a, c in zip(
            jax.tree_util.tree_leaves(got),
            jax.tree_util.tree_leaves(ref),
        ):
            # sgd(1.0): update == grads, modulo ONE bf16 apply round
            np.testing.assert_allclose(
                np.asarray(a, np.float32),
                np.asarray(c, np.float32),
                atol=2e-2,
            )


# -- satellite: grad_accum equivalence (default GSPMD path) -----------------
class TestGradAccumEquivalence:
    def test_ga4_matches_ga1_fp32(self):
        cfg = _fp32_tiny()
        mesh = _mesh(2)
        tx = optax.adamw(1e-2)
        state, _ = init_sharded_state(
            jax.random.PRNGKey(0), cfg, mesh, tx
        )
        x = _batch(cfg)
        b = shard_batch({"x": x, "y": x}, mesh)
        s1, m1 = build_train_step(cfg, mesh, tx, donate=False)(
            state, b["x"], b["y"]
        )
        s4, m4 = build_train_step(
            cfg, mesh, tx, donate=False, grad_accum=4
        )(state, b["x"], b["y"])
        assert abs(float(m1["loss"]) - float(m4["loss"])) < 1e-5
        _assert_same_adam_step(s1, s4, atol=2e-5)


# -- satellite: PipelineStats coverage --------------------------------------
class TestPipelineStatsGradSync:
    def test_as_dict_and_summary_cover_grad_sync_fields(self):
        from dlrover_tpu.accel.profiler import PipelineStats

        st = PipelineStats(
            prefetch_hits=3,
            prefetch_misses=1,
            grad_sync_ms=2.5,
            comm_overlap_pct=70.0,
            grad_bytes_wire=25_000,
            grad_bytes_raw=100_000,
        )
        d = st.as_dict()
        assert d["grad_sync_ms"] == 2.5
        assert d["comm_overlap_pct"] == 70.0
        assert d["grad_bytes_wire_vs_raw"] == [25_000, 100_000]
        s = st.summary()
        assert "grad sync" in s and "70.0% overlapped" in s
        assert "24 KiB wire" in s

    def test_defaults_omit_grad_sync(self):
        from dlrover_tpu.accel.profiler import PipelineStats

        st = PipelineStats()
        d = st.as_dict()
        assert d["grad_bytes_wire_vs_raw"] is None
        assert d["comm_overlap_pct"] is None
        assert "grad sync" not in st.summary()
        # round-trippable: every as_dict value is JSON-serializable
        import json

        json.dumps(d)


# -- strategy / opt_lib / dry_runner plumbing -------------------------------
class TestStrategyPlumbing:
    def test_json_roundtrip_with_grad_sync_fields(self):
        from dlrover_tpu.accel.strategy import Strategy

        s = Strategy(
            mesh=MeshConfig(dp=4),
            comm_overlap=True,
            grad_compress="int8",
            grad_bucket_mb=8,
        )
        s2 = Strategy.from_json(s.to_json())
        assert s2 == s
        assert "comm_overlap" in s.describe()
        assert "int8grad" in s.describe()

    def test_old_json_still_parses(self):
        import json as _json

        from dlrover_tpu.accel.strategy import Strategy

        d = _json.loads(Strategy().to_json())
        for k in ("comm_overlap", "grad_compress", "grad_bucket_mb"):
            d.pop(k)
        s = Strategy.from_json(_json.dumps(d))
        assert s.comm_overlap is False
        assert s.grad_compress == "none"

    def test_opt_lib_registrations(self):
        from dlrover_tpu.accel.opt_lib import (
            apply_optimizations,
            registered_optimizations,
        )
        from dlrover_tpu.accel.strategy import Strategy

        assert "comm_overlap" in registered_optimizations()
        assert "grad_compress" in registered_optimizations()
        cfg = tiny()
        _, s = apply_optimizations(
            cfg, Strategy(mesh=MeshConfig(dp=2)), ("grad_compress",)
        )
        # compression implies the explicit sync path
        assert s.comm_overlap and s.grad_compress == "int8"
        assert s.opts == ("grad_compress",)

    def test_resolved_accessors_honor_opts(self):
        from dlrover_tpu.accel.strategy import Strategy

        s = Strategy(mesh=MeshConfig(dp=2), opts=("grad_compress",))
        assert s.resolved_comm_overlap()
        assert s.resolved_grad_compress() == "int8"
        assert resolve_plan(tiny(num_layers=1), s) is not None

    def test_resolve_plan_gates_on_mesh(self):
        from dlrover_tpu.accel.strategy import Strategy

        cfg = tiny(num_layers=1)
        assert resolve_plan(
            cfg, Strategy(mesh=MeshConfig(dp=2))
        ) is None  # not requested
        # ISSUE 13: pp x dp and 3D meshes now get explicit plans; a
        # model that cannot pipeline at the degree (1 layer over pp=2)
        # still falls back
        assert resolve_plan(
            cfg,
            Strategy(mesh=MeshConfig(dp=2, pp=2), comm_overlap=True),
        ) is None
        from dlrover_tpu.parallel.grad_sync import PPSyncPlan

        ppp = resolve_plan(
            tiny(num_layers=2),
            Strategy(mesh=MeshConfig(dp=2, pp=2), comm_overlap=True),
        )
        assert isinstance(ppp, PPSyncPlan) and ppp.pp == 2
        d3 = resolve_plan(
            cfg,
            Strategy(
                mesh=MeshConfig(dp=2, fsdp=2, tp=2), comm_overlap=True
            ),
        )
        assert d3 is not None and d3.three_d and d3.tp == 2
        # a pp x ep composition stays GSPMD (the remaining exotica)
        assert resolve_plan(
            tiny(num_layers=2, num_experts=2),
            Strategy(
                mesh=MeshConfig(dp=2, pp=2, ep=2), comm_overlap=True
            ),
        ) is None
        plan = resolve_plan(
            cfg, Strategy(mesh=MeshConfig(dp=2), comm_overlap=True)
        )
        assert isinstance(plan, BucketPlan) and plan.dp == 2
        # dp x fsdp now plans the ZeRO schedule; dp x tp the bucketed
        # dp sync under the tp submesh (details: test_hybrid_sync.py)
        zp = resolve_plan(
            cfg,
            Strategy(mesh=MeshConfig(dp=2, fsdp=2), comm_overlap=True),
        )
        assert zp is not None and zp.zero and zp.fsdp == 2
        tpp = resolve_plan(
            cfg,
            Strategy(mesh=MeshConfig(dp=2, tp=2), comm_overlap=True),
        )
        assert tpp is not None and tpp.auto_axes == ("tp",)


class TestDryRunnerCommCost:
    def _report(self, strategy):
        from dlrover_tpu.accel.dry_runner import (
            DryRunReport,
            _comm_estimate,
        )

        r = DryRunReport(strategy=strategy, ok=True)
        _comm_estimate(r, tiny(num_layers=1), 8, 16, None)
        return r

    def test_overlap_and_compress_shrink_the_comm_term(self):
        from dlrover_tpu.accel.strategy import Strategy

        plain = self._report(
            Strategy(mesh=MeshConfig(dp=2), grad_accum=4)
        )
        overlap = self._report(
            Strategy(
                mesh=MeshConfig(dp=2), grad_accum=4, comm_overlap=True
            )
        )
        int8 = self._report(
            Strategy(
                mesh=MeshConfig(dp=2),
                grad_accum=4,
                comm_overlap=True,
                grad_compress="int8",
            )
        )
        assert plain.comm_bytes_per_device > 0
        # explicit path: one sync per step instead of per microbatch
        assert (
            overlap.comm_bytes_per_device
            < plain.comm_bytes_per_device
        )
        # + overlap credit on the exposed seconds
        assert overlap.comm_exposed_s < plain.comm_exposed_s
        # + int8 payload
        assert int8.comm_bytes_per_device < overlap.comm_bytes_per_device

    def test_single_device_has_no_comm_term(self):
        from dlrover_tpu.accel.strategy import Strategy

        r = self._report(Strategy(mesh=MeshConfig(dp=1)))
        assert r.comm_bytes_per_device == 0.0
        assert r.comm_exposed_s == 0.0

    def test_unsupported_mesh_fallback_priced_full_precision(self):
        """A pp candidate carrying the compress knob as an opt name
        falls back to GSPMD full-precision sync at runtime — the cost
        model must price it that way, not at int8 wire bytes it never
        gets."""
        from dlrover_tpu.accel.strategy import Strategy

        plain = self._report(
            Strategy(mesh=MeshConfig(dp=2, pp=2, ep=2))
        )
        compressed_opts = self._report(
            Strategy(
                mesh=MeshConfig(dp=2, pp=2, ep=2),
                opts=("grad_compress",),
            )
        )
        assert (
            compressed_opts.comm_bytes_per_device
            == plain.comm_bytes_per_device
        )

    def test_explicit_fsdp_priced_below_gspmd_allreduce(self):
        """An fsdp candidate on the explicit path is priced with the
        ZeRO schedule (reduce-scatter, no gather twin, dp legs on the
        chunk) — strictly below the monolithic all-reduce its GSPMD
        twin pays."""
        from dlrover_tpu.accel.strategy import Strategy

        gspmd = self._report(Strategy(mesh=MeshConfig(dp=2, fsdp=2)))
        explicit = self._report(
            Strategy(
                mesh=MeshConfig(dp=2, fsdp=2), comm_overlap=True
            )
        )
        assert 0 < explicit.comm_bytes_per_device
        assert (
            explicit.comm_bytes_per_device
            < gspmd.comm_bytes_per_device
        )
        assert explicit.comm_exposed_s < gspmd.comm_exposed_s


# -- residual lifecycle -----------------------------------------------------
class TestResidualLifecycle:
    def test_ensure_and_strip_are_inverse_and_idempotent(self):
        from dlrover_tpu.models.train import TrainState

        cfg = _fp32_tiny()
        mesh = _mesh(2)
        plan = plan_buckets(
            jax.eval_shape(
                lambda: __import__(
                    "dlrover_tpu.models.transformer",
                    fromlist=["init_params"],
                ).init_params(jax.random.PRNGKey(0), cfg)
            ),
            dp=2,
            compress="int8",
        )
        state = TrainState(step=0, params={}, opt_state={})
        st2 = ensure_residual(state, plan, mesh)
        assert st2.grad_residual is not None
        assert ensure_residual(st2, plan, mesh) is st2
        st3 = strip_residual(st2)
        assert st3.grad_residual is None
        assert strip_residual(st3) is st3
        # None residual contributes no leaves: old checkpoints load
        assert jax.tree_util.tree_structure(
            state
        ) == jax.tree_util.tree_structure(st3)

    def test_no_plan_is_noop(self):
        from dlrover_tpu.models.train import TrainState

        state = TrainState(step=0, params={}, opt_state={})
        assert ensure_residual(state, None, None) is state


# -- ElasticTrainer integration ---------------------------------------------
class TestTrainerGradSync:
    def test_knobs_flow_and_resize_replans_buckets(self):
        """TrainerConfig knobs → opt names → strategy → bucket plan →
        EF residual → PipelineStats; a resize re-plans for the new dp
        degree and re-seeds the residual (its shapes changed)."""
        from dlrover_tpu.trainer.elastic.trainer import (
            ElasticTrainer,
            TrainerConfig,
        )

        class _Toks:
            def __init__(self, n=64, seq=16, vocab=256):
                rng = np.random.default_rng(0)
                self.d = rng.integers(
                    0, vocab, (n, seq + 1), dtype=np.int32
                )

            def __len__(self):
                return len(self.d)

            def __getitem__(self, i):
                return {"x": self.d[i][:-1], "y": self.d[i][1:]}

        from dlrover_tpu.accel.strategy import Strategy

        tr = ElasticTrainer(
            model_cfg=tiny(num_layers=1),
            tx=optax.adamw(1e-2),
            dataset=_Toks(),
            trainer_cfg=TrainerConfig(
                batch_size=8,
                seq_len=16,
                report_metrics=False,
                log_interval=1000,
                prefetch=0,
                # donation ON: most production steps run the donating
                # twin — it must keep the explicit sync + EF update
                donation_aware=True,
                speculative_compile=False,
                comm_overlap=True,
                grad_compress="int8",
                grad_bucket_mb=1,
            ),
            strategy=Strategy(mesh=MeshConfig(dp=2), dtype="float32"),
            devices=jax.devices()[:2],
        )
        try:
            # knobs became opt names on the strategy
            assert "comm_overlap" in tr.accel.strategy.opts
            assert "grad_compress" in tr.accel.strategy.opts
            plan = tr._grad_sync_plan
            assert plan is not None and plan.dp == 2
            assert plan.compress == "int8"
            assert tr.state.grad_residual is not None
            st = tr.pipeline_stats
            assert st.grad_bytes_raw > 0
            assert st.grad_bytes_wire <= 0.30 * st.grad_bytes_raw
            assert st.comm_overlap_pct is not None
            # checkpoint trees never carry the residual
            assert (
                tr._ckpt_state()["train"].grad_residual is None
            )
            tr.train(num_steps=2)
            assert tr.state.grad_residual is not None
            # donated steps ran the compressed sync: the EF residual
            # moved off its zero seed (a GSPMD-path twin would have
            # passed it through untouched)
            assert any(
                float(jnp.sum(jnp.abs(r))) > 0
                for r in tr.state.grad_residual
            )
            assert tr.pipeline_stats.donated_steps > 0
            tr.resize(4)
            # buckets re-planned for the new world, residual re-seeded
            assert tr._grad_sync_plan.dp == 4
            assert tr.state.grad_residual is not None
            assert all(
                r.shape[0] == 4 for r in tr.state.grad_residual
            )
            tr.train(num_steps=4)
            assert tr.global_step == 4
        finally:
            tr.close()


class TestKnobPlumbing:
    def test_auto_accelerate_stamps_grad_bucket_mb(self):
        """TrainerConfig.grad_bucket_mb reaches the strategy (the
        name-only opt registry cannot carry the integer)."""
        from dlrover_tpu.accel.accelerate import auto_accelerate
        from dlrover_tpu.accel.strategy import Strategy

        res = auto_accelerate(
            _fp32_tiny(),
            optax.adamw(1e-2),
            batch=8,
            seq=16,
            devices=jax.devices()[:2],
            strategy=Strategy(mesh=MeshConfig(dp=2), dtype="float32"),
            donate=False,
            optimizations=("comm_overlap",),
            grad_bucket_mb=8,
        )
        assert res.strategy.grad_bucket_mb == 8

    def test_strategy_for_fallback_preserves_field_knobs(self):
        """A non-divisible resize takes the candidate-enumeration
        fallback; field-carried grad-sync knobs (an explicit Strategy
        without opt names) must survive it."""
        import types

        from dlrover_tpu.accel.strategy import Strategy
        from dlrover_tpu.trainer.elastic.trainer import (
            ElasticTrainer,
            TrainerConfig,
        )

        s = Strategy(
            mesh=MeshConfig(dp=2),
            dtype="float32",
            comm_overlap=True,
            grad_compress="int8",
            grad_bucket_mb=2,
        )
        fake = types.SimpleNamespace(
            accel=types.SimpleNamespace(strategy=s),
            tcfg=TrainerConfig(batch_size=6, seq_len=16),
            _model_cfg=tiny(num_layers=1),
        )
        # 6 % dp4 != 0 -> fast path rejected -> enumeration fallback
        out = ElasticTrainer._strategy_for_exact(fake, 4)
        assert out is not None
        assert out.comm_overlap is True
        assert out.grad_compress == "int8"
        assert out.grad_bucket_mb == 2
